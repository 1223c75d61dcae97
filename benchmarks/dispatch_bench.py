"""Dispatch fast-path benchmark: seed (sync, uncached) vs fast (async,
cached) runtime, on the two workloads the tentpole targets.

* ``smallgemm`` — a loop of sub-threshold 64^3 sgemms from one call
  site.  The paper's point: interception overhead must be ~zero for
  calls that *stay on the host*; the seed runtime spent ~200us/call on
  re-created device scalars, re-derived thresholds and a mandatory
  ``block_until_ready``.
* ``dfuchain`` — a 100-call chained DFU workload (``C = A @ C``) above
  the threshold: placement-registry hits plus async submission.
* ``shardscale`` — the same chained workload under the multi-device
  tile scheduler (``devices`` in 1/2/4): tiles/sec, per-device
  moved bytes and byte-cap eviction counters.  On this CPU container
  every logical device tier shares one physical CPU, so the numbers
  measure scheduler overhead and movement accounting, not speedup.
* ``adaptive`` — the small-gemm loop under ``adaptive=True``: the
  per-site warmup probes both paths, locks, and steady state should
  approach the fast path (the lock costs two dict hops per call).
* ``evict`` — eviction pressure: a round-robin working set sized at
  2x the ``device_bytes`` cap, run once per eviction policy
  (``evict`` in lru/lfu/refetch).  Reports calls/sec plus the
  refetched GB the cap cost — how each policy's victim choice trades
  throughput against link traffic under constant pressure.
* ``kernel`` — the pallas dispatch venue (``SCILIB_KERNELS``): the
  chained offloaded gemm loop at two shape classes with the kernel
  path off (generic XLA offload) vs on (kernel-backed closures), plus
  an adaptive run that round-robins host/XLA/pallas probes and reports
  which venue the call site locked.
* ``precision`` — split fp64 emulation (``SCILIB_PRECISION``): the
  offloaded fp64 gemm loop at two shape classes, native vs ``split2``
  vs ``split3``, reporting calls/sec *and* the measured max relative
  error of each scheme — the speedup column is only meaningful next to
  the accuracy column it was bought with.
* ``solver`` — the LAPACK solver tier (``SCILIB_LAPACK``): one
  factorization per timing for gesv/potrf/syev in three modes — host
  (the span-wrapped drivers under ``policy=cpu``), offload (the raw
  blocked kernels under DFU, no spans), and offload+pin (the drivers
  under DFU: spans pin the factor buffer for their lifetime).  gesv
  and potrf run at n=512/1024; syev runs one size class down
  (256/512) because its per-column tridiagonalization is python-
  dispatch-bound at laptop scale and the rank-2k updates it feeds the
  runtime are what the comparison is about.
* ``faults`` — fault-tolerance overhead: the chained workload under
  the Mem-Copy policy (every call stages transfers, so every call is
  exposed to injection) at 5% transfer faults.  Three configs: clean
  (no injection — the guard's fixed cost), default retries (faults
  absorbed in place), and retries=0 (every fault becomes a host
  fallback).  Reports calls/sec and the fallback percentage.

Modes are selected with the runtime's own knobs — typed
``OffloadConfig`` objects, no env mutation — so the comparison runs
the *same* code path the library ships:

* seed: ``sync=True`` + ``dispatch_cache=False`` (per-call blocking +
  per-call re-derivation, the seed's behaviour),
* fast: the defaults (async + dispatch cache).

    PYTHONPATH=src python -m benchmarks.dispatch_bench

``SCILIB_BENCH_QUICK=1`` shrinks every loop for CI smoke runs, and
``--record-trace PATH`` dumps the dfuchain workload's BLAS trace for
the autotuner walkthrough (``python -m repro.tools.autotune PATH``).
"""
from __future__ import annotations

import os
import time
from typing import List, Tuple

import numpy as np

Row = Tuple[str, float, str]

_QUICK = os.environ.get("SCILIB_BENCH_QUICK", "") == "1"

SMALL_N = 64
SMALL_CALLS = 40 if _QUICK else 400
CHAIN_N = 256
CHAIN_CALLS = 20 if _QUICK else 100
SHARD_N = 512
SHARD_CALLS = 6 if _QUICK else 30
#: eviction-pressure working set: a hot set of small matrices reused
#: every phase + a cold set of big matrices streamed once per phase.
#: Uniform sizes/frequencies make every policy degenerate to LRU order;
#: this mix makes recency (lru), frequency (lfu) and refetch cost
#: (refetch) rank victims differently, which is the comparison's point.
EVICT_HOT_N, EVICT_HOT = 160, 4
EVICT_COLD_N, EVICT_COLD = 320, 6
EVICT_PHASES = 2 if _QUICK else 8
EVICT_CALLS = EVICT_PHASES * (3 * EVICT_HOT + EVICT_COLD)
PREC_NS = (256,) if _QUICK else (256, 1024)
PREC_CALLS = 4 if _QUICK else 10
PREC_ROUNDS = 2 if _QUICK else 4
SOLVER_NS = (192,) if _QUICK else (512, 1024)
SOLVER_EIG_NS = (128,) if _QUICK else (256, 512)
SOLVER_NRHS = 32
SOLVER_NB = 128
REPS = 1 if _QUICK else 3


def _mode_config(mode: str, **fields):
    """The typed config for one benchmark mode (plus extra fields);
    resets the blas-level caches so reps start cold."""
    from repro.core import blas
    from repro.core.config import OffloadConfig
    blas.clear_caches()
    if mode == "seed":
        base = OffloadConfig(sync=True, dispatch_cache=False)
    elif mode == "adaptive":
        base = OffloadConfig(adaptive=True)
    else:
        base = OffloadConfig()
    return base.replace(**fields)


def _sweep(fn, runtime, calls: int) -> float:
    """calls/sec, best of REPS (first rep also warms compile caches)."""
    best = 0.0
    for _ in range(REPS):
        t0 = time.perf_counter()
        fn()
        runtime.sync()
        best = max(best, calls / (time.perf_counter() - t0))
    return best


def _bench_smallgemm(mode: str) -> float:
    from repro.core import blas
    from repro.core import runtime as rtm
    from repro.core.policy import host_array
    rng = np.random.default_rng(0)
    # default threshold: every call stays host
    rt = rtm.install(config=_mode_config(mode), record_trace=False)
    try:
        a = host_array(rng.standard_normal((SMALL_N, SMALL_N))
                       .astype("float32"))
        b = host_array(rng.standard_normal((SMALL_N, SMALL_N))
                       .astype("float32"))

        def loop():
            for _ in range(SMALL_CALLS):
                blas.gemm(a, b, alpha=1.0, beta=0.0)

        return _sweep(loop, rt, SMALL_CALLS)
    finally:
        rtm.uninstall()


def _bench_dfuchain(mode: str) -> float:
    from repro.core import blas
    from repro.core import runtime as rtm
    from repro.core.policy import host_array
    rng = np.random.default_rng(1)
    rt = rtm.install(config=_mode_config(mode, threshold=100.0),
                     record_trace=False)
    try:
        a = host_array(rng.standard_normal((CHAIN_N, CHAIN_N))
                       .astype("float32") / CHAIN_N)

        def loop():
            c = a
            for _ in range(CHAIN_CALLS):
                c = blas.gemm(a, c)
            return c

        return _sweep(loop, rt, CHAIN_CALLS)
    finally:
        rtm.uninstall()


def _bench_shardscale(n_dev: int) -> Tuple[float, float, int, int]:
    """Chained DFU gemms under ``devices=n_dev`` with a per-device
    byte cap sized to put the block LRU under pressure.  Returns
    (calls/sec, tiles/sec, evictions, moved bytes) summed over devices."""
    from repro.core import blas
    from repro.core import runtime as rtm
    from repro.core.policy import host_array
    rng = np.random.default_rng(2)
    rt = rtm.install(config=_mode_config(
        "fast", threshold=100.0, devices=n_dev,
        device_bytes=3 * SHARD_N * SHARD_N * 4), record_trace=False)
    try:
        a = host_array(rng.standard_normal((SHARD_N, SHARD_N))
                       .astype("float32") / SHARD_N)

        def loop():
            c = a
            for _ in range(SHARD_CALLS):
                c = blas.gemm(a, c)
            return c

        cps = _sweep(loop, rt, SHARD_CALLS)
        st = rt.stats.per_routine["sgemm"]
        tiles_per_call = st.tiles / max(1, st.calls)
        evs = sum(d.evictions for d in rt.stats.per_device.values())
        moved = sum(d.moved_bytes for d in rt.stats.per_device.values())
        return cps, cps * tiles_per_call, evs, moved
    finally:
        rtm.uninstall()


def _bench_eviction(evict_policy: str) -> Tuple[float, int, int]:
    """Round-robin gemms over a working set 2x the ``device_bytes``
    cap: constant pressure, every policy choosing different victims.
    Returns (calls/sec, evictions, refetched bytes) summed over reps."""
    from repro.core import blas
    from repro.core import runtime as rtm
    from repro.core.policy import host_array
    working = (EVICT_HOT * EVICT_HOT_N ** 2
               + EVICT_COLD * EVICT_COLD_N ** 2) * 4
    rng = np.random.default_rng(5)
    rt = rtm.install(config=_mode_config(
        "fast", threshold=100.0, device_bytes=working // 2,
        evict=evict_policy), record_trace=False)
    try:
        hot = [host_array(rng.standard_normal((EVICT_HOT_N, EVICT_HOT_N))
                          .astype("float32")) for _ in range(EVICT_HOT)]
        cold = [host_array(rng.standard_normal(
            (EVICT_COLD_N, EVICT_COLD_N)).astype("float32"))
            for _ in range(EVICT_COLD)]

        def loop():
            for _ in range(EVICT_PHASES):
                for _ in range(3):          # hot phase: reuse to exploit
                    for h in hot:
                        blas.gemm(h, h)
                for c in cold:              # cold scan: streams through
                    blas.gemm(c, c)

        cps = _sweep(loop, rt, EVICT_CALLS)
        return cps, rt.stats.evictions, rt.stats.refetched_bytes
    finally:
        rtm.uninstall()


def _bench_kernelpath(n: int, kernel: bool) -> float:
    """Chained offloaded gemms at shape n with the pallas venue off/on.
    Returns calls/sec."""
    from repro.core import blas
    from repro.core import runtime as rtm
    from repro.core.policy import host_array
    rng = np.random.default_rng(6)
    rt = rtm.install(config=_mode_config(
        "fast", threshold=100.0, kernel_path=kernel), record_trace=False)
    try:
        a = host_array(rng.standard_normal((n, n))
                       .astype("float32") / n)

        def loop():
            c = a
            for _ in range(CHAIN_CALLS):
                c = blas.gemm(a, c)
            return c

        return _sweep(loop, rt, CHAIN_CALLS)
    finally:
        rtm.uninstall()


def _bench_kernel_adaptive(n: int) -> Tuple[str, float]:
    """Adaptive warmup racing all three venues at shape n: returns the
    locked venue and the locked steady-state calls/sec."""
    from repro.core import blas
    from repro.core import runtime as rtm
    from repro.core.policy import host_array
    rng = np.random.default_rng(7)
    rt = rtm.install(config=_mode_config(
        "adaptive", threshold=100.0, kernel_path=True,
        adaptive_warmup=9), record_trace=False)
    try:
        a = host_array(rng.standard_normal((n, n))
                       .astype("float32") / n)

        def loop():
            c = a
            for _ in range(CHAIN_CALLS):
                c = blas.gemm(a, c)
            return c

        cps = _sweep(loop, rt, CHAIN_CALLS)
        venue = next((p.locked_venue for p in rt.callsites
                      if p.locked is not None), "")
        return venue or "unlocked", cps
    finally:
        rtm.uninstall()


def _bench_precision(n: int):
    """Offloaded fp64 gemm chain, native vs split2 vs split3 at shape n.

    The schemes run in *interleaved* short rounds on one runtime
    (``apply_config`` flips ``precision`` between rounds) rather than
    one-scheme-at-a-time sweeps: on shared/burstable containers a
    sequential sweep hands whichever scheme runs first the cold-burst
    clocks and every later scheme the throttled ones, which reads as a
    fake native win.  Best round per scheme is reported, like
    everywhere else in this bench.

    Returns ``{scheme: (calls/sec, max relative error vs native
    fp64)}`` — the accuracy each scheme's throughput was bought with.
    """
    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.core import blas
    from repro.core import runtime as rtm
    from repro.core.policy import host_array
    schemes = ("", "split2", "split3")
    rng = np.random.default_rng(8)
    cfg = _mode_config("fast", threshold=100.0)
    rt = rtm.install(config=cfg, record_trace=False)
    best = {s: 0.0 for s in schemes}
    err = {}
    try:
        a = host_array(rng.standard_normal((n, n)) / n)
        b = host_array(rng.standard_normal((n, n)))
        for _ in range(PREC_ROUNDS):
            for s in schemes:
                rt.apply_config(cfg.replace(precision=s))
                c = a
                t0 = time.perf_counter()
                for _ in range(PREC_CALLS):
                    c = blas.gemm(a, c)
                rt.sync()
                best[s] = max(best[s],
                              PREC_CALLS / (time.perf_counter() - t0))
        ref = np.asarray(a) @ np.asarray(b)
        for s in schemes:
            rt.apply_config(cfg.replace(precision=s))
            out = np.asarray(blas.gemm(a, b))
            rt.sync()
            err[s] = float(np.max(np.abs(out - ref))
                           / np.max(np.abs(ref)))
        return {s: (best[s], err[s]) for s in schemes}
    finally:
        rtm.uninstall()


def _bench_solver(kind: str, n: int, mode: str) -> float:
    """One LAPACK-tier factorization, three ways.  ``host`` runs the
    span-wrapped drivers under ``policy=cpu`` (spans open but nothing
    pins or offloads), ``offload`` runs the raw blocked kernels under
    DFU (no spans, so the factor competes in the LRU like any buffer),
    ``pin`` runs the drivers under DFU (the span pins the factor for
    its lifetime).  Returns solves/sec, best rep."""
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    from repro.core import lapack
    from repro.core import runtime as rtm
    from repro.core.policy import host_array
    from repro.solvers import drivers
    from repro.solvers import eigen
    rng = np.random.default_rng(9)
    fields = ({"policy": "cpu"} if mode == "host"
              else {"threshold": 100.0})
    rt = rtm.install(config=_mode_config("fast", **fields),
                     record_trace=False)
    raw = mode == "offload"
    try:
        if kind == "gesv":
            a = host_array(jnp.asarray(
                rng.standard_normal((n, n)) / n + np.eye(n)))
            b = host_array(jnp.asarray(
                rng.standard_normal((n, SOLVER_NRHS))))
            if raw:
                def run():
                    lu, piv = lapack.getrf(a, nb=SOLVER_NB)
                    return lapack.getrs(lu, piv, b)
            else:
                def run():
                    return drivers.gesv(a, b, nb=SOLVER_NB)
        elif kind == "potrf":
            g = rng.standard_normal((n, n)) / n
            a = host_array(jnp.asarray(g @ g.T + np.eye(n)))
            if raw:
                def run():
                    return lapack.potrf(a, SOLVER_NB)
            else:
                def run():
                    return drivers.potrf(a, SOLVER_NB)
        else:
            g = rng.standard_normal((n, n))
            a = host_array(jnp.asarray((g + g.T) / 2))
            if raw:
                def run():
                    return eigen.syev(a, nb=SOLVER_NB)
            else:
                def run():
                    return drivers.syev(a, SOLVER_NB)
        best = 0.0
        for _ in range(REPS):       # first rep warms the compile caches
            t0 = time.perf_counter()
            jax.block_until_ready(run())
            rt.sync()
            best = max(best, 1.0 / (time.perf_counter() - t0))
        return best
    finally:
        rtm.uninstall()


def _bench_faults(spec: str, retries: int) -> Tuple[float, float, int]:
    """Chained Mem-Copy gemms under an injected transfer-fault rate.
    Returns (calls/sec, fallback %, retries) over all reps."""
    from repro.core import blas
    from repro.core import runtime as rtm
    from repro.core.policy import host_array
    rng = np.random.default_rng(4)
    rt = rtm.install(config=_mode_config(
        "fast", policy="memcopy", threshold=100.0, faults=spec,
        retries=retries, backoff_ms=0.0, breaker=0),
        record_trace=False)
    try:
        a = host_array(rng.standard_normal((CHAIN_N, CHAIN_N))
                       .astype("float32") / CHAIN_N)

        def loop():
            c = a
            for _ in range(CHAIN_CALLS):
                c = blas.gemm(a, c)
            return c

        cps = _sweep(loop, rt, CHAIN_CALLS)
        st = rt.stats.per_routine["sgemm"]
        return (cps, 100.0 * st.fallbacks / max(1, st.calls),
                rt.stats.retries)
    finally:
        rtm.uninstall()


def _record_chain_trace(path: str) -> None:
    """Run the dfuchain workload with trace recording on and dump the
    trace for the autotuner walkthrough (docs/PERF.md)."""
    from repro.core import blas
    from repro.core import runtime as rtm
    from repro.core.policy import host_array
    rng = np.random.default_rng(3)
    rt = rtm.install(config=_mode_config("fast", threshold=100.0),
                     record_trace=True)
    try:
        a = host_array(rng.standard_normal((CHAIN_N, CHAIN_N))
                       .astype("float32") / CHAIN_N)
        c = a
        for _ in range(CHAIN_CALLS):
            c = blas.gemm(a, c)
        rt.sync()
        rt.trace.dump(path)
        print(f"# trace: {len(rt.trace)} calls -> {path}")
    finally:
        rtm.uninstall()


def bench() -> List[Row]:
    rows: List[Row] = []
    # each bench builds its own typed OffloadConfig: no env mutation,
    # nothing to save/restore
    small = {m: _bench_smallgemm(m)
             for m in ("seed", "fast", "adaptive")}
    chain = {m: _bench_dfuchain(m) for m in ("seed", "fast")}
    shard = {n: _bench_shardscale(n) for n in (1, 2, 4)}
    evict = {p: _bench_eviction(p)
             for p in ("lru", "lfu", "refetch")}
    faults = {
        "clean": _bench_faults("", 2),
        "retry": _bench_faults("transfer:p=0.05,seed=7", 2),
        "fallback": _bench_faults("transfer:p=0.05,seed=7", 0),
    }
    rows.append(("dispatch.smallgemm64.seed_cps", round(small["seed"], 0),
                 "sync + uncached (seed runtime)"))
    rows.append(("dispatch.smallgemm64.fast_cps", round(small["fast"], 0),
                 "async + dispatch cache"))
    rows.append(("dispatch.smallgemm64.speedup",
                 round(small["fast"] / small["seed"], 2),
                 "acceptance: >= 2x"))
    rows.append(("dispatch.smallgemm64.adaptive_cps",
                 round(small["adaptive"], 0),
                 "adaptive=True: warmup probes + locked steady state"))
    rows.append(("dispatch.dfuchain100.seed_cps", round(chain["seed"], 0),
                 "sync + uncached (seed runtime)"))
    rows.append(("dispatch.dfuchain100.fast_cps", round(chain["fast"], 0),
                 "async + dispatch cache"))
    rows.append(("dispatch.dfuchain100.speedup",
                 round(chain["fast"] / chain["seed"], 2),
                 "chained DFU workload"))
    for n, (cps, tps, evs, moved) in sorted(shard.items()):
        rows.append((f"dispatch.shard.gemm512.d{n}_cps", round(cps, 0),
                     f"chained gemm, devices={n}"))
        rows.append((f"dispatch.shard.gemm512.d{n}_tiles_ps",
                     round(tps, 0),
                     "tile kernels/sec across device tiers"))
        rows.append((f"dispatch.shard.gemm512.d{n}_evictions", evs,
                     "per-device byte-cap LRU evictions (summed)"))
        rows.append((f"dispatch.shard.gemm512.d{n}_moved_mb",
                     round(moved / 1e6, 1),
                     "block bytes moved to device tiers (summed)"))
    for n in (128, 512):
        xla_cps = _bench_kernelpath(n, False)
        pal_cps = _bench_kernelpath(n, True)
        rows.append((f"dispatch.kernel.gemm{n}.xla_cps",
                     round(xla_cps, 0),
                     "offloaded chain, generic XLA venue"))
        rows.append((f"dispatch.kernel.gemm{n}.pallas_cps",
                     round(pal_cps, 0),
                     "offloaded chain, SCILIB_KERNELS=1"))
        rows.append((f"dispatch.kernel.gemm{n}.pallas_speedup",
                     round(pal_cps / max(1e-9, xla_cps), 3),
                     ">1 means the pallas venue wins this shape class"))
    venue, cps = _bench_kernel_adaptive(128)
    rows.append(("dispatch.kernel.adaptive128_cps", round(cps, 0),
                 f"3-venue warmup locked: {venue}"))
    for n in PREC_NS:
        prec = _bench_precision(n)
        nat_cps, nat_err = prec[""]
        rows.append((f"dispatch.precision.dgemm{n}.native_cps",
                     round(nat_cps, 0), "offloaded fp64 chain, native"))
        for s in ("split2", "split3"):
            s_cps, s_err = prec[s]
            rows.append((f"dispatch.precision.dgemm{n}.{s}_cps",
                         round(s_cps, 0),
                         f"offloaded fp64 chain, SCILIB_PRECISION={s}"))
            rows.append((f"dispatch.precision.dgemm{n}.{s}_maxrel",
                         float(f"{s_err:.3g}"),
                         "measured max relative error vs native fp64"))
            rows.append((f"dispatch.precision.dgemm{n}.{s}_speedup",
                         round(s_cps / max(1e-9, nat_cps), 3),
                         ">1 means the split scheme wins this shape"))
    for pol, (cps, evs, refetched) in evict.items():
        rows.append((f"dispatch.evict.mixed.{pol}_cps", round(cps, 0),
                     f"working set 2x cap, evict={pol}"))
        rows.append((f"dispatch.evict.mixed.{pol}_evictions", evs,
                     "cap-pressure evictions (all reps)"))
        rows.append((f"dispatch.evict.mixed.{pol}_refetched_gb",
                     round(refetched / 1e9, 3),
                     "GB re-moved for evicted-then-reused buffers"))
    for kind, ns in (("gesv", SOLVER_NS), ("potrf", SOLVER_NS),
                     ("syev", SOLVER_EIG_NS)):
        for n in ns:
            sps = {m: _bench_solver(kind, n, m)
                   for m in ("host", "offload", "pin")}
            rows.append((f"dispatch.solver.{kind}{n}.host_sps",
                         round(sps["host"], 3),
                         "span-wrapped drivers, policy=cpu"))
            rows.append((f"dispatch.solver.{kind}{n}.offload_sps",
                         round(sps["offload"], 3),
                         "raw blocked kernels under DFU (no spans)"))
            rows.append((f"dispatch.solver.{kind}{n}.pin_sps",
                         round(sps["pin"], 3),
                         "drivers under DFU: span pins the factor"))
            rows.append((f"dispatch.solver.{kind}{n}.pin_speedup",
                         round(sps["pin"] / max(1e-9, sps["host"]), 3),
                         ">1 means offload+pin beats the host path"))
    labels = {"clean": "no injection (guard fixed cost)",
              "retry": "5% transfer faults, retries=2 (absorbed)",
              "fallback": "5% transfer faults, retries=0 (host falls)"}
    for key, (cps, fb_pct, nretries) in faults.items():
        rows.append((f"dispatch.faults.{key}_cps", round(cps, 0),
                     labels[key]))
        rows.append((f"dispatch.faults.{key}_fallback_pct",
                     round(fb_pct, 2), "calls served on the host path"))
    rows.append(("dispatch.faults.retry_retries", faults["retry"][2],
                 "transient faults absorbed in place (all reps)"))
    return rows


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--record-trace", default="",
                    help="also dump the dfuchain workload's BLAS trace "
                         "here (autotuner input)")
    args = ap.parse_args()
    print("name,value,derived")
    for name, value, derived in bench():
        print(f"{name},{value},{derived}")
    if args.record_trace:
        _record_chain_trace(args.record_trace)


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
