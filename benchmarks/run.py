"""Benchmark harness: one function per paper table + kernels + roofline.

Prints ``name,value,derived`` CSV (the derived column carries the
paper's measured number for the same quantity where one exists).

    PYTHONPATH=src python -m benchmarks.run [--only t3,t5]
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: dispatch,t1,t3,t4,t5,t6,t7,t8,"
                         "kern,serve,roofline")
    args = ap.parse_args()
    only = set(filter(None, args.only.split(",")))

    from benchmarks import (dispatch_bench, kernels_bench,
                            roofline_report, serve_bench, tables)
    suites = [
        ("dispatch", dispatch_bench.bench),
        ("t1", tables.table1_stream),
        ("t3", tables.table3_must),
        ("t4", tables.table4_scaling),
        ("t5", tables.table5_parsec),
        ("t6", tables.table6_counter),
        ("t7", tables.table7_pagesize),
        ("t8", tables.table8_alignment),
        ("kern", kernels_bench.bench),
        ("serve", serve_bench.bench),
        ("roofline", roofline_report.report),
    ]
    print("name,value,derived")
    failures = 0
    for tag, fn in suites:
        if only and tag not in only:
            continue
        t0 = time.time()
        try:
            for name, value, derived in fn():
                print(f"{name},{value},{derived}")
        except Exception as e:  # keep the harness running
            failures += 1
            print(f"{tag}.ERROR,nan,{type(e).__name__}: {e}")
        print(f"#{tag} done in {time.time() - t0:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
