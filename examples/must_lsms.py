"""MuST/LSMS proxy under every data-movement policy (paper Table 3).

    PYTHONPATH=src python examples/must_lsms.py [--atoms 4 --energies 4]

Runs REAL multiple-scattering solves (zgetrf/zgetrs through the
intercepted BLAS) under cpu / memcopy / dfu policies, verifies the
physics is identical, then replays the production-scale trace through
the GH200 memtier model to reproduce the paper's Table 3 structure.
"""
import argparse

import jax
jax.config.update("jax_enable_x64", True)

import repro.core as scilib
from repro.apps import lsms
from repro.memtier import GH200, replay_trace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--atoms", type=int, default=3)
    ap.add_argument("--energies", type=int, default=3)
    ap.add_argument("--scf", type=int, default=2)
    ap.add_argument("--n", type=int, default=160)
    args = ap.parse_args()

    print("== runnable mini-LSMS through the interception layer ==")
    results = {}
    for policy in ("cpu", "memcopy", "dfu"):
        runtime = scilib.install(policy=policy, threshold=100)
        out = lsms.run_mini(atoms=args.atoms, energies=args.energies,
                            scf=args.scf, n=args.n)
        stats = scilib.uninstall()
        results[policy] = out
        g = stats.per_routine.get("zgemm")
        print(f"policy={policy:8s} energy={out['energy']:+.6f} "
              f"resid={out['max_resid']:.2e} solves={out['n_solves']} "
              f"zgemm calls={g.calls if g else 0}")
    e0 = results["cpu"]["energy"]
    for p, r in results.items():
        assert abs(r["energy"] - e0) < 1e-8, (p, r["energy"], e0)
    print("energies identical across policies: OK\n")

    print("== production-scale trace replay (GH200 constants) ==")
    trace = lsms.production_trace()
    reports = replay_trace(trace, spec=GH200,
                           policies=("cpu", "memcopy", "counter", "dfu"))
    print(f"{'policy':10s}{'total_s':>10s}{'blas_s':>10s}"
          f"{'movement_s':>12s}{'reuse':>8s}")
    for p, r in reports.items():
        print(f"{p:10s}{r.total_s:10.1f}"
              f"{r.blas_device_s + r.blas_host_s:10.1f}"
              f"{r.movement_s:12.2f}{r.mean_reuse:8.1f}")
    speedup = reports["cpu"].total_s / reports["dfu"].total_s
    print(f"\nDFU speedup vs CPU: {speedup:.2f}x "
          f"(paper Table 3: ~2.8x on zgemm+ztrsm-dominated runtime)")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
