"""PARSEC proxy: tall-skinny dgemm offload (paper Table 5).

    PYTHONPATH=src python examples/parsec_dft.py

Runs a real Chebyshev-filtered subspace iteration (Ritz values verified
against dense eigh) under the interception layer, then replays the
production tall-skinny dgemm stream through the GH200 model: Mem-Copy
drowns in transfers, the access counter strands the 1.8 GB panel on the
host, Device First-Use moves it once.
"""
import jax
jax.config.update("jax_enable_x64", True)

import repro.core as scilib
from repro.apps import dft
from repro.memtier import GH200, replay_trace


def main():
    print("== runnable mini-PARSEC (subspace iteration) ==")
    runtime = scilib.install(policy="dfu", threshold=200)
    out = dft.run_mini(ngrid=1024, nstates=32)
    stats = scilib.uninstall()
    print(f"ritz_min={out['ritz_min']:.6f} exact={out['exact_min']:.6f} "
          f"max_err(low half)={out['max_err_low_half']:.2e}")
    assert out["max_err_low_half"] < 1e-6
    print(stats.report())

    print("\n== production-scale trace replay (GH200 constants) ==")
    trace = dft.production_trace()
    reports = replay_trace(trace, spec=GH200,
                           policies=("cpu", "memcopy", "counter", "dfu"))
    print(f"{'policy':10s}{'total_s':>10s}{'dgemm_s':>10s}"
          f"{'movement_s':>12s}{'reuse':>8s}")
    for p, r in reports.items():
        print(f"{p:10s}{r.total_s:10.1f}"
              f"{r.blas_device_s + r.blas_host_s:10.1f}"
              f"{r.movement_s:12.2f}{r.mean_reuse:8.1f}")
    print(f"\nDFU speedup vs CPU: "
          f"{reports['cpu'].total_s / reports['dfu'].total_s:.2f}x "
          f"(paper Table 5: ~1.9x total, ~10x on dgemm)")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
