"""Serving-state placement benchmark: the paper's Table 3/5 accounting
applied to LM decode state (DESIGN.md §4).

Measures, per policy, the real bytes moved between the host and device
tiers while generating with a small LM, plus a GH200-modeled cost of
that movement for a production-sized cache (qwen2.5-32b at 32k context,
batch 128 — the decode_32k cell's cache).

:func:`load_bench` adds the multi-tenant serving axis: a closed-loop
request load generator at 1/8/32/128 concurrent streams, each stream an
independent session drawing on one shared device pool, reporting
p50/p95/p99 request latency and aggregate calls/sec per stream count
(``SCILIB_BENCH_QUICK=1`` shrinks the request counts for CI).
"""
from __future__ import annotations

import os
import threading
import time
from typing import List, Tuple

Row = Tuple[str, float, str]

_QUICK = os.environ.get("SCILIB_BENCH_QUICK", "") == "1"

#: closed-loop concurrency levels (streams = concurrent sessions)
STREAMS = (1, 8, 32, 128)
REQUESTS_PER_STREAM = 4 if _QUICK else 16
POOL_MB = 64          # shared pool capacity across all tenants


def _percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile of an already-sorted sample."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def _request(blas, arrays) -> None:
    """One serving request: a small decode-step-shaped BLAS chain
    (gemm attention-score shape, syrk state update, trsm solve)."""
    a, b, s, t = arrays
    out = blas.gemm(a, b)
    blas.syrk(s)
    blas.trsm(t, out)


def load_bench() -> List[Row]:
    """Request-level closed-loop load generator over concurrent
    multi-tenant sessions sharing one device pool."""
    import numpy as np

    from repro.core import blas
    from repro.core import residency as res
    from repro.core import session as ses
    from repro.core.config import OffloadConfig
    from repro.core.policy import host_array

    n = 96
    rng = np.random.default_rng(0)
    a = host_array(rng.standard_normal((n, n)).astype("float32"))
    b = host_array(rng.standard_normal((n, n)).astype("float32"))
    s = host_array(rng.standard_normal((n, n)).astype("float32"))
    t = host_array(np.tril(rng.standard_normal((n, n)) + n)
                   .astype("float32"))
    arrays = (a, b, s, t)
    cfg = OffloadConfig(policy="dfu", threshold=1.0, sync=True)

    rows: List[Row] = []
    for n_streams in STREAMS:
        pool = res.SharedDevicePool(POOL_MB << 20,
                                    name=f"load-{n_streams}")
        latencies_ms: List[List[float]] = [[] for _ in range(n_streams)]
        barrier = threading.Barrier(n_streams + 1)
        errors: List[BaseException] = []

        def worker(idx: int) -> None:
            try:
                with ses.session(cfg, record_trace=False,
                                 intercept=False,
                                 name=f"stream-{idx}", pool=pool):
                    _request(blas, arrays)      # warm compile caches
                    barrier.wait()
                    for _ in range(REQUESTS_PER_STREAM):
                        t0 = time.perf_counter()
                        _request(blas, arrays)
                        latencies_ms[idx].append(
                            (time.perf_counter() - t0) * 1e3)
            except BaseException as exc:        # propagate to the row
                errors.append(exc)
                try:
                    barrier.abort()
                except Exception:
                    pass

        threads = [threading.Thread(target=worker, args=(i,),
                                    name=f"load-{n_streams}-{i}")
                   for i in range(n_streams)]
        for th in threads:
            th.start()
        barrier.wait()
        t0 = time.perf_counter()
        for th in threads:
            th.join()
        wall = time.perf_counter() - t0
        if errors:
            raise errors[0]
        lat = sorted(ms for per in latencies_ms for ms in per)
        calls = len(lat)
        tag = f"serve.load.{n_streams}str"
        note = f"{calls} reqs, {n_streams} sessions, shared pool"
        rows.append((f"{tag}.p50_ms",
                     round(_percentile(lat, 50), 3), note))
        rows.append((f"{tag}.p95_ms",
                     round(_percentile(lat, 95), 3), note))
        rows.append((f"{tag}.p99_ms",
                     round(_percentile(lat, 99), 3), note))
        rows.append((f"{tag}.req_per_s",
                     round(calls / max(wall, 1e-9), 1), note))
    return rows


def main() -> None:
    """CLI for the load generator (CI artifact): ``--out`` writes the
    CSV rows to a file in addition to stdout."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="", help="also write CSV here")
    args = ap.parse_args()
    lines = ["name,value,derived"]
    for name, value, derived in load_bench():
        lines.append(f"{name},{value},{derived}")
    text = "\n".join(lines) + "\n"
    print(text, end="")
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text)


def bench() -> List[Row]:
    import jax
    import jax.numpy as jnp

    from repro.memtier import GH200
    from repro.models import get_config
    from repro.models.registry import Model
    from repro.train import Server, ServeConfig

    cfg = get_config("mamba2_1_3b").reduced()
    model = Model.from_config(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, 16),
                                0, cfg.vocab)
    rows: List[Row] = []
    moved = {}
    for policy in ("dfu", "memcopy", "pinned"):
        srv = Server(model, params,
                     ServeConfig(max_len=80, offload_policy=policy,
                                 cache_dtype=jnp.float32))
        srv.generate(prompt, 32)
        s = srv.stats
        moved[policy] = s.bytes_host_to_dev + s.bytes_dev_to_host
        rows.append((f"serve.{policy}.moved_MB",
                     round(moved[policy] / 1e6, 2),
                     f"migrations={s.migrations} reuses={s.cache_reuses}"))
    rows.append(("serve.memcopy_vs_dfu_traffic",
                 round(moved["memcopy"] / max(1, moved["dfu"]), 1),
                 "per-token roundtrips vs one first-use migration"))

    # production-scale projection: qwen2.5-32b decode_32k cache
    big = get_config("qwen2_5_32b")
    cache_bytes = (big.n_layers * 2 * big.n_kv_heads * big.head_dim
                   * 32768 * 128 * 2)          # bf16, batch 128
    link = GH200.link_bw
    tokens = 1024
    t_dfu = cache_bytes / GH200.effective_migrate_bw()
    t_memcopy = 2 * cache_bytes * tokens / link
    rows.append(("serve.proj32k.cache_GB", round(cache_bytes / 1e9, 1),
                 "qwen2.5-32b kv cache @32k x128"))
    rows.append(("serve.proj32k.dfu_move_s", round(t_dfu, 2),
                 "one first-use migration"))
    rows.append(("serve.proj32k.memcopy_move_s", round(t_memcopy, 1),
                 f"2 transfers/token x {tokens} tokens"))
    return rows


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
