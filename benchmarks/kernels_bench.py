"""Kernel micro-benchmarks.

On this CPU container the Pallas kernels execute in interpret mode
(Python semantics — correctness, not speed), so the honest numbers are:
(a) wall time of the XLA reference op (what the CPU fallback costs),
(b) the kernel's arithmetic model on the v5e target (MXU-bound bound),
and (c) the venue-comparison rows — the same BLAS call dispatched
through each of the runtime's three execution venues (host / generic
XLA offload / pallas kernel path), which is what the `SCILIB_KERNELS`
knob actually races per call site.

    PYTHONPATH=src python -m benchmarks.kernels_bench [--quick] [--out F]

``--quick`` (or ``SCILIB_BENCH_QUICK=1``) shrinks shapes and reps for
CI smoke runs; ``--out`` also writes the CSV rows to a file.
"""
from __future__ import annotations

import argparse
import os
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Row = Tuple[str, float, str]

V5E_FLOPS = 197.0e12

_QUICK = os.environ.get("SCILIB_BENCH_QUICK", "") == "1"

#: execution venues the comparison rows sweep, in VENUES order
_VENUE_CONFIGS = ("host", "xla", "pallas")


def _wall(fn, *args, reps=3) -> float:
    warm = fn(*args)                       # evaluate the warmup once
    (warm[0] if isinstance(warm, tuple) else warm).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
        jax.tree.leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6  # us


def bench(quick: bool = False) -> List[Row]:
    from repro.kernels import ref
    quick = quick or _QUICK
    n = 256 if quick else 512
    reps = 1 if quick else 3
    rows = []
    rng = np.random.default_rng(0)

    # gemm: n^3 f32
    a = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    mm = jax.jit(ref.matmul)
    us = _wall(mm, a, b, reps=reps)
    flops = 2 * n**3
    rows.append((f"kern.gemm{n}.ref_us", round(us, 1),
                 f"v5e_mxu_bound_us={flops / V5E_FLOPS * 1e6:.2f}"))

    # trsm nxn on n/2 rhs
    l = np.tril(rng.standard_normal((n, n)).astype(np.float32) / n)
    np.fill_diagonal(l, 1.0)
    bb = jnp.asarray(rng.standard_normal((n, n // 2)), jnp.float32)
    ts = jax.jit(lambda aa, cc: ref.trsm(aa, cc))
    us = _wall(ts, jnp.asarray(l), bb, reps=reps)
    rows.append((f"kern.trsm{n}.ref_us", round(us, 1),
                 f"v5e_bound_us={n * n * (n // 2) / V5E_FLOPS * 1e6:.2f}"))

    # flash attention 1x8x1024x64 causal
    t = 512 if quick else 1024
    q = jnp.asarray(rng.standard_normal((1, 8, t, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 8, t, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 8, t, 64)), jnp.float32)
    at = jax.jit(lambda *xs: ref.attention(*xs, causal=True))
    us = _wall(at, q, k, v, reps=reps)
    aflops = 4 * 1 * 8 * t * t * 64 / 2
    rows.append((f"kern.attn{t}.ref_us", round(us, 1),
                 f"v5e_bound_us={aflops / V5E_FLOPS * 1e6:.2f}"))

    # interpret-mode correctness spot check counts as the kernel row
    from repro.kernels.gemm import gemm as pallas_gemm
    out = pallas_gemm(a[:256, :256], b[:256, :256], bm=128, bk=128,
                      bn=128, interpret=True)
    err = float(jnp.max(jnp.abs(out - a[:256, :256] @ b[:256, :256])))
    rows.append(("kern.gemm.pallas_interpret_maxerr", round(err, 6),
                 "correctness via interpret mode"))
    return rows


def _venue_config(venue: str):
    """The typed config that forces one execution venue end to end."""
    from repro.core.config import OffloadConfig
    if venue == "host":
        return OffloadConfig(policy="cpu")
    return OffloadConfig(policy="dfu", threshold=1.0,
                         kernel_path=(venue == "pallas"))


def _venue_cps(venue: str, routine: str, n: int, calls: int,
               reps: int) -> float:
    """calls/sec for one routine at one shape through one venue."""
    from repro.core import blas
    from repro.core.policy import host_array
    from repro.core.session import Session
    rng = np.random.default_rng(11)
    blas.clear_caches()
    with Session(_venue_config(venue), record_trace=False) as s:
        with s.scope():
            a = host_array(rng.standard_normal((n, n))
                           .astype("float32") / n)
            b = host_array(rng.standard_normal((n, n)).astype("float32"))
            tri = host_array(
                (np.tril(rng.standard_normal((n, n))) / n
                 + 2.0 * np.eye(n)).astype("float32"))

            def loop():
                if routine == "gemm":
                    for _ in range(calls):
                        blas.gemm(a, b)
                elif routine == "syrk":
                    for _ in range(calls):
                        blas.syrk(a)
                else:
                    for _ in range(calls):
                        blas.trsm(tri, b)

            best = 0.0
            for _ in range(reps + 1):      # first rep warms jit caches
                t0 = time.perf_counter()
                loop()
                s.sync()
                best = max(best, calls / (time.perf_counter() - t0))
            return best


def venue_rows(quick: bool = False) -> List[Row]:
    """host / xla / pallas calls-per-second per routine and shape —
    the comparison the kernel path's per-site racing automates."""
    quick = quick or _QUICK
    shapes = (128,) if quick else (128, 512)
    calls = 10 if quick else 40
    reps = 1 if quick else 3
    rows: List[Row] = []
    for n in shapes:
        for routine in ("gemm", "syrk", "trsm"):
            cps = {v: _venue_cps(v, routine, n, calls, reps)
                   for v in _VENUE_CONFIGS}
            for v in _VENUE_CONFIGS:
                rows.append((f"kern.venue.{routine}{n}.{v}_cps",
                             round(cps[v], 0),
                             f"{routine} {n}^2 f32 via the {v} venue"))
            rows.append((f"kern.venue.{routine}{n}.pallas_vs_xla",
                         round(cps["pallas"] / max(1e-9, cps["xla"]), 3),
                         ">1 means the pallas venue wins this shape"))
    return rows


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.kernels_bench",
        description="Kernel micro-benchmarks + venue comparison rows.")
    ap.add_argument("--quick", action="store_true",
                    help="small shapes / single rep (CI smoke)")
    ap.add_argument("--out", default="",
                    help="also write the CSV rows to this file")
    ap.add_argument("--no-venues", action="store_true",
                    help="skip the dispatch venue comparison rows")
    args = ap.parse_args(argv)
    rows = bench(quick=args.quick)
    if not args.no_venues:
        rows += venue_rows(quick=args.quick)
    lines = ["name,value,derived"]
    lines += [f"{name},{value},{derived}" for name, value, derived in rows]
    print("\n".join(lines))
    if args.out:
        with open(args.out, "w") as f:
            f.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    raise SystemExit(main())
