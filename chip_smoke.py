#!/usr/bin/env python3
"""Chip smoke test: the intercepted BLAS path end to end on a TPU.

    python chip_smoke.py [--seed N]     # one chip: phases (a)-(e)
    python chip_smoke.py --chips 4      # only the four-chip tile scheduler

Drives the offload runtime through the entry points an application uses
(``repro.session`` with an ``OffloadConfig``, ``host_array`` inputs, plain
``jnp`` / ``jax.scipy`` calls) at the paper applications' production
shapes, checks every result against a host reference, and prints one JSON
line per phase:

(a) device   the backend, its memory kinds and the resolved tier mapping;
(b) parsec   PARSEC's tall-skinny dgemm^T (32 x 2400 x 93536, fp64) against
             a 1.8 GB host-resident wavefunction panel under Device
             First-Use: the panel moves once, every later block hits;
(c) lsms     MuST/LSMS KKR solves at n = 6912 through the solver tier
             (blocked LU: panels on the host, trailing updates offloaded);
(d) pallas   the pallas venue's f32 gemm, syrk and trsm at 4096;
(e) host     phase (b)'s product under the CPU-only policy.

The last line of standard output is the verdict,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits 3 and prints no verdict.  Any wrong answer,
any fault-tolerance event (fault, retry, fallback, quarantine), a
pallas-venue call that ran the XLA reference, or a path that ran on the
wrong platform fails the run (exit 1).  Everything runs in this one
process: a chip belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

#: LSMS runs in complex64: the TPU compiler refuses complex128 dot_general
#: (a compiler abort), triangular_solve (abort) and LU (unimplemented).
LSMS_DTYPE = "complex64"
#: LAPACK's acceptance threshold for its xGET02 scaled residual
LAPACK_THRESH = 30.0
#: normwise relative error max|x - truth| / max|truth| allowed for an
#: f32 / complex64 BLAS result.  A product at f32 precision is off by
#: about sqrt(K) u32 per entry (u32 = 2^-24: about 2^-18 at K = 4096,
#: less once normalised by the largest entry); one bf16 pass, a TPU's
#: default for f32, rounds every input to 8 bits and leaves about 2^-9.
#: 2^-16 lies between them, and each phase that uses it also measures a
#: one-bf16-pass control of the same product, which must exceed it.
F32_RTOL = 2.0 ** -16
FP64_U = 2.0 ** -53
#: the offload threshold on N_avg (the runtime's CPU-host default)
THRESHOLD = 500.0
#: matrix edges of the pallas-venue phase and the four-chip phase
PALLAS_N = 4096
FOUR_CHIP_N = 8192


class SmokeError(AssertionError):
    pass


class Checks:
    """A phase's checks: every failed one is recorded, the phase's line
    is printed with them, and then the phase fails (:meth:`done`)."""

    def __init__(self):
        self.failed = []

    def __call__(self, cond, msg):
        if not cond:
            self.failed.append(msg)

    def done(self):
        if self.failed:
            raise SmokeError("; ".join(self.failed))


class CompileClock:
    """Seconds the XLA backend spent compiling, from JAX's own events."""

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration


def health(stats) -> dict:
    return {"faults": stats.faults, "retries": stats.retries,
            "fallbacks": stats.fallbacks, "quarantines": stats.quarantines}


def check_healthy(check, stats) -> None:
    """The fault-tolerance machinery must have had nothing to do: a
    retried or host-finished call would hide a device failure."""
    h = health(stats)
    check(not any(h.values()), f"fault-tolerance events: {h}")
    if stats.breaker is not None:
        states = [d.state for d in stats.breaker.devices()]
        check(all(s == "closed" for s in states),
              f"device breaker states {states}")


def emit(phase: str, t0: float, clock: CompileClock, c0: float, check,
         **rec):
    """Print the phase's line (with any failed checks), then fail it if
    a check failed."""
    rec = {"phase": phase, "seconds": time.perf_counter() - t0,
           "compile_seconds": clock.total - c0, **rec,
           "failed_checks": check.failed}
    print(json.dumps(rec), flush=True)
    check.done()


def routine_row(stats, name: str) -> dict:
    r = stats.per_routine[name]
    return {"calls": r.calls, "offloaded": r.offloaded, "host": r.on_host,
            "bytes_in": r.bytes_in, "bytes_out": r.bytes_out,
            "hits": r.cache_hits, "misses": r.cache_misses,
            "kernel_calls": r.kernel_calls}


def platforms(stats) -> dict:
    return {k: sorted(v) for k, v in stats.platforms.items()}


def rel_err(x, truth) -> float:
    """Normwise relative error max|x - truth| / max|truth|."""
    import numpy as np
    return float(np.max(np.abs(np.asarray(x, np.float64) - truth))
                 / np.max(np.abs(truth)))


def gemm_bound_err(c, a_t, b, u) -> float:
    """Largest |C - A^T B| over its worst-case rounding bound
    ``2 K u (|A|^T |B|)`` (a length-K dot in precision u, taken twice: the
    result and the host reference), computed in fp64 on the host."""
    import numpy as np
    ref = a_t.T @ b
    scale = np.abs(a_t).T @ np.abs(b)
    k = a_t.shape[0]
    return float(np.max(np.abs(np.asarray(c, np.float64) - ref) /
                        (2.0 * k * u * scale)))


# --------------------------------------------------------------------- #
# phases                                                                 #
# --------------------------------------------------------------------- #
def phase_device(clock):
    import jax
    from repro.core import memspace
    t0, c0 = time.perf_counter(), clock.total
    check = Checks()
    d = jax.devices()[0]
    space = memspace.probe()
    check(space.host != space.device, "tiers share one placement")
    check(space.device_platform == "tpu" and space.host_platform == "cpu",
          f"tier mapping {space.describe()}")
    emit("device", t0, clock, c0, check, platform=d.platform,
         kind=d.device_kind, count=len(jax.devices()),
         memory_kinds=[m.kind for m in d.addressable_memories()],
         memspace=space.describe())


def parsec_data(seed: int):
    """The wavefunction panel and the wavefront blocks, made in bulk on
    the host from ``seed`` (PARSEC's production shape, apps/dft.py)."""
    import numpy as np
    from repro.apps import dft
    rng = np.random.default_rng(seed)
    ngrid, nstates, nblock = (dft.PROD["ngrid"], dft.PROD["nstates"],
                              dft.PROD["nblock"])
    panel = rng.standard_normal((ngrid, nstates))
    blocks = [rng.standard_normal((ngrid, nblock)) for _ in range(8)]
    return panel, blocks


def run_parsec(panel_np, blocks_np, policy: str):
    """The application's code: S_blk = Hpsi_blk^T Psi per block, through
    plain ``jnp.einsum`` on host-first-touched inputs."""
    import jax.numpy as jnp
    import numpy as np
    import repro
    from repro import OffloadConfig
    from repro.core import host_array
    with repro.session(OffloadConfig(policy=policy, threshold=THRESHOLD)) as s:
        psi = host_array(panel_np)
        outs = []
        for blk_np in blocks_np:
            blk = host_array(blk_np)
            outs.append(np.asarray(jnp.einsum("gi,gj->ij", blk, psi)))
        stats = s.stats
    return outs, stats


def phase_parsec(clock, panel_np, blocks_np):
    t0, c0 = time.perf_counter(), clock.total
    check = Checks()
    outs, stats = run_parsec(panel_np, blocks_np, "dfu")
    check_healthy(check, stats)
    row = routine_row(stats, "dgemm")
    n = len(blocks_np)
    check(row["calls"] == n and row["offloaded"] == n, f"dgemm {row}")
    # the panel migrates once and hits for every later block; every
    # block is a fresh buffer and moves once
    check(row["misses"] == n + 1 and row["hits"] == n - 1, f"dgemm {row}")
    want = panel_np.nbytes + sum(b.nbytes for b in blocks_np)
    check(row["bytes_in"] == want, f"bytes_in {row['bytes_in']} != {want}")
    check(platforms(stats) == {"offload": ["tpu"]},
          f"platforms {platforms(stats)}")
    errs = [gemm_bound_err(o, b, panel_np, FP64_U)
            for o, b in zip(outs, blocks_np)]
    check(max(errs) <= 1.0, f"dgemm error {max(errs)} x its fp64 bound")
    emit("parsec", t0, clock, c0, check,
         shape=[blocks_np[0].shape[1], panel_np.shape[1],
                panel_np.shape[0]],
         dtype="float64", blocks=n, dgemm=row, platforms=platforms(stats),
         err_over_fp64_bound=max(errs), **health(stats))
    return outs


def phase_host(clock, panel_np, blocks_np, offload_outs):
    import numpy as np
    t0, c0 = time.perf_counter(), clock.total
    check = Checks()
    outs, stats = run_parsec(panel_np, blocks_np, "cpu")
    check_healthy(check, stats)
    row = routine_row(stats, "dgemm")
    n = len(blocks_np)
    check(row["host"] == n and row["offloaded"] == 0, f"dgemm {row}")
    check(platforms(stats) == {"host": ["cpu"]},
          f"platforms {platforms(stats)}")
    errs = [gemm_bound_err(o, b, panel_np, FP64_U)
            for o, b in zip(outs, blocks_np)]
    check(max(errs) <= 1.0, f"host dgemm error {max(errs)} x fp64 bound")
    diff = None
    if offload_outs is not None:
        diff = max(float(np.max(np.abs(h - d)))
                   for h, d in zip(outs, offload_outs))
    emit("host", t0, clock, c0, check, policy="cpu", blocks=n, dgemm=row,
         platforms=platforms(stats), err_over_fp64_bound=max(errs),
         max_abs_diff_vs_offload=diff, **health(stats))


def product_err(x, a_np, b_np, cols: int) -> float:
    """Normwise relative error of ``x`` = a @ b over its first ``cols``
    columns, against the product in fp64 / complex128 on the host."""
    import numpy as np
    wide = np.complex128 if np.iscomplexobj(a_np) else np.float64
    truth = a_np.astype(wide) @ b_np[:, :cols].astype(wide)
    got = np.asarray(x)[:, :cols].astype(wide)
    return float(np.max(np.abs(got - truth)) / np.max(np.abs(truth)))


def phase_lsms(clock, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import repro
    from repro import OffloadConfig
    from repro.apps import lsms
    from repro.core import host_array
    t0, c0 = time.perf_counter(), clock.total
    check = Checks()
    n, nb = lsms.PROD["n"], lsms.PROD["nb"]
    # the blocked LU's trailing-update product, made from the seed: the
    # LU residual of a near-identity KKR matrix barely feels product
    # precision, so the product is held to the f32 limit on its own
    rng = np.random.default_rng(seed + 3)
    l21, u12 = ((rng.standard_normal(s) + 1j * rng.standard_normal(s))
                .astype(LSMS_DTYPE) for s in ((n, nb), (nb, n)))
    cfg = OffloadConfig(policy="dfu", threshold=THRESHOLD, lapack=True,
                        lapack_nb=nb)
    with repro.session(cfg) as s:
        out = lsms.run_mini(atoms=1, energies=2, scf=1, n=n, nb=nb,
                            seed=seed, dtype=LSMS_DTYPE)
        upd = np.asarray(jnp.matmul(host_array(l21), host_array(u12)))
        stats = s.stats
    control = jax.lax.dot(jnp.asarray(l21), jnp.asarray(u12),
                          precision=jax.lax.Precision.DEFAULT)
    err = {"cgemm": product_err(upd, l21, u12, nb),
           "xla_one_bf16_pass": product_err(control, l21, u12, nb)}
    check_healthy(check, stats)
    check(out["n_solves"] == 2, f"solves {out['n_solves']}")
    check(out["max_ratio"] <= LAPACK_THRESH,
          f"LSMS scaled residual {out['max_ratio']} > {LAPACK_THRESH}")
    check(err["cgemm"] <= F32_RTOL < err["xla_one_bf16_pass"],
          f"cgemm error {err} against limit {F32_RTOL}")
    rows = {name: routine_row(stats, name)
            for name in ("cgemm", "ctrsm", "cgetf2")}
    check(rows["cgemm"]["offloaded"] > 0 and rows["ctrsm"]["offloaded"] > 0,
          f"LSMS offload {rows}")
    check(rows["cgetf2"]["host"] == rows["cgetf2"]["calls"] > 0,
          f"LSMS panels {rows['cgetf2']}")
    plats = platforms(stats)
    check(plats.get("offload") == ["tpu"] and
          plats.get("host", ["cpu"]) == ["cpu"], f"platforms {plats}")
    solvers = {k: {"spans": v.spans, "calls": v.calls,
                   "panel_calls": v.panel_calls}
               for k, v in stats.solvers.items()}
    emit("lsms", t0, clock, c0, check, n=n, nb=nb, dtype=LSMS_DTYPE,
         solves=out["n_solves"], max_resid=out["max_resid"],
         lapack_ratio=out["max_ratio"], ratio_limit=LAPACK_THRESH,
         product_rel_err=err, rtol=F32_RTOL, routines=rows,
         solvers=solvers, platforms=plats, **health(stats))


def phase_pallas(clock, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import scipy.linalg
    import repro
    from repro import OffloadConfig
    from repro.core import blas
    from repro.kernels import ref
    t0, c0 = time.perf_counter(), clock.total
    check = Checks()
    n = PALLAS_N
    rng = np.random.default_rng(seed + 1)
    a_np = rng.standard_normal((n, n)).astype(np.float32)
    b_np = rng.standard_normal((n, n)).astype(np.float32)
    # well-conditioned lower triangle: unit-scale diagonal, small below
    l_np = (np.tril(rng.standard_normal((n, n)), -1) / n
            + np.diag(1.0 + rng.random(n))).astype(np.float32)
    a, b, l = jnp.asarray(a_np), jnp.asarray(b_np), jnp.asarray(l_np)
    ref_calls = []
    saved = {name: getattr(ref, name) for name in ("matmul", "syrk", "trsm")}
    for name, fn in saved.items():
        setattr(ref, name, lambda *x, _n=name, _f=fn, **k:
                ref_calls.append(_n) or _f(*x, **k))
    try:
        cfg = OffloadConfig(policy="dfu", threshold=THRESHOLD,
                            kernel_path=True)
        with repro.session(cfg) as s:
            outs = {"gemm": blas.gemm(a, b), "syrk": blas.syrk(a),
                    "trsm": blas.trsm(l, b, side="L", uplo="L")}
            outs = {k: np.asarray(v) for k, v in outs.items()}
            stats = s.stats
            hlo = {
                "gemm": blas._gemm_klean.lower(a, b, block=0),
                "syrk": blas._syrk_klean.lower(a, uplo="L", trans="N",
                                               block=0),
                "trsm": blas._trsm_klean.lower(l, b, side="L", uplo="L",
                                               trans="N", diag="N",
                                               block=0)}
            hlo = {k: "tpu_custom_call" in v.compile().as_text()
                   for k, v in hlo.items()}
    finally:
        for name, fn in saved.items():
            setattr(ref, name, fn)
    check_healthy(check, stats)
    check(not ref_calls, f"pallas venue ran the reference: {ref_calls}")
    check(all(hlo.values()), f"tpu_custom_call missing: {hlo}")
    rows = {r: routine_row(stats, "s" + r) for r in ("gemm", "syrk", "trsm")}
    check(all(r["kernel_calls"] == 1 for r in rows.values()),
          f"pallas venue calls {rows}")
    check(platforms(stats) == {"offload": ["tpu"]},
          f"platforms {platforms(stats)}")
    # parity: the pallas venue and the XLA formulation at f32 precision,
    # each against the host's fp64 answer; the one-bf16-pass products
    # are the control the limit must reject
    highest, default = jax.lax.Precision.HIGHEST, jax.lax.Precision.DEFAULT
    xla = {"gemm": jnp.matmul(a, b, precision=highest),
           "syrk": jnp.tril(jnp.matmul(a, a.T, precision=highest)),
           "trsm": jax.scipy.linalg.solve_triangular(l, b, lower=True)}
    control = {"gemm": jnp.matmul(a, b, precision=default),
               "syrk": jnp.tril(jnp.matmul(a, a.T, precision=default))}
    a64, b64, l64 = (x.astype(np.float64) for x in (a_np, b_np, l_np))
    truth = {"gemm": a64 @ b64, "syrk": np.tril(a64 @ a64.T),
             "trsm": scipy.linalg.solve_triangular(l64, b64, lower=True)}
    err = {k: {"pallas": rel_err(outs[k], truth[k]),
               "xla": rel_err(np.asarray(xla[k]), truth[k])}
           for k in truth}
    for k, v in control.items():
        err[k]["xla_one_bf16_pass"] = rel_err(np.asarray(v), truth[k])
    check(all(e["pallas"] <= F32_RTOL and e["xla"] <= F32_RTOL
              for e in err.values()), f"pallas / XLA error {err}")
    check(all(err[k]["xla_one_bf16_pass"] > F32_RTOL for k in control),
          f"one-bf16-pass control within the f32 limit: {err}")
    emit("pallas", t0, clock, c0, check, n=n, dtype="float32",
         routines=rows, tpu_custom_call=hlo, ref_calls=len(ref_calls),
         rel_err=err, rtol=F32_RTOL, platforms=platforms(stats),
         **health(stats))


def phase_four_chips(clock, seed: int):
    """The tile scheduler over four real chips: a 2x2-tiled gemm and a
    column-tiled trsm, each compared with the same call on one chip, and
    user code combining the tiled result with host data."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import repro
    from repro import OffloadConfig
    from repro.core import blas
    t0, c0 = time.perf_counter(), clock.total
    check = Checks()
    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices")
    n = FOUR_CHIP_N
    rng = np.random.default_rng(seed + 2)
    a_np = rng.standard_normal((n, n)).astype(np.float32)
    b_np = rng.standard_normal((n, n)).astype(np.float32)
    l_np = (np.tril(rng.standard_normal((n, n)), -1) / n
            + np.diag(1.0 + rng.random(n))).astype(np.float32)

    def calls(devices):
        from repro.core import host_array
        cfg = OffloadConfig(policy="dfu", threshold=THRESHOLD, devices=devices)
        with repro.session(cfg) as s:
            a, b, l = host_array(a_np), host_array(b_np), host_array(l_np)
            c = jnp.matmul(a, b)
            out = {"gemm": np.asarray(c),
                   "trsm": np.asarray(blas.trsm(l, b, side="L",
                                                uplo="L")),
                   # user code: the result combined with a host input
                   "gemm_plus_a": np.asarray(c + a)}
            blocks = [store.get(key) for store in s.runtime.block_stores
                      for key in list(store.keys())]
            homes = {str(device) for block in blocks if block is not None
                     for device in block.devices()}
            return out, s.stats, homes

    one, st1, _ = calls(1)
    four, st4, homes = calls(4)
    for st in (st1, st4):
        check_healthy(check, st)
    tiles = {d: v.tiles for d, v in sorted(st4.per_device.items())}
    check(len(tiles) == 4 and all(t >= 1 for t in tiles.values()),
          f"tiles per device {tiles}")
    check(len(homes) == 4, f"blocks resident on {sorted(homes)}")
    sharded = {r: st4.per_routine[r].sharded for r in ("sgemm", "strsm")}
    check(all(v == 1 for v in sharded.values()), f"sharded {sharded}")
    diff = {k: float(np.max(np.abs(four[k] - one[k])) /
                     max(1e-30, float(np.max(np.abs(one[k])))))
            for k in one}
    check(all(v <= 1e-5 for v in diff.values()),
          f"4-chip vs 1-chip rel diff {diff}")
    emit("four_chips", t0, clock, c0, check, n=n, dtype="float32",
         tiles=tiles, block_homes=sorted(homes), sharded=sharded,
         rel_diff_vs_one_chip=diff, platforms=platforms(st4),
         **health(st4))


def run_phase(name, fn, *args):
    """Run one phase; a failure is reported and the remaining phases
    still run (each prints its own line).  Returns (ok, result)."""
    import traceback
    try:
        return True, fn(*args)
    except SmokeError as exc:
        print(f"chip_smoke: phase {name} FAILED: {exc}", file=sys.stderr)
    except Exception:  # noqa: BLE001 - any error fails the phase
        print(f"chip_smoke: phase {name} FAILED:\n"
              f"{traceback.format_exc()[-6000:]}", file=sys.stderr)
    return False, None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print("chip_smoke: the repro package is not beside this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        # the runtime's host tier is the CPU backend's device
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax
    jax.config.update("jax_enable_x64", True)
    from repro import compile_cache
    compile_cache.enable()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
              f"nothing run", file=sys.stderr)
        return 3
    clock = CompileClock()
    if args.chips == 4:
        results = [run_phase("four_chips", phase_four_chips, clock,
                             args.seed)]
    else:
        results = [run_phase("device", phase_device, clock)]
        panel, blocks = parsec_data(args.seed)
        ok, outs = run_phase("parsec", phase_parsec, clock, panel, blocks)
        results.append((ok, None))
        results.append(run_phase("host", phase_host, clock, panel, blocks,
                                 outs))
        del panel, blocks, outs
        results.append(run_phase("lsms", phase_lsms, clock, args.seed))
        results.append(run_phase("pallas", phase_pallas, clock, args.seed))
    if not all(ok for ok, _ in results):
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
