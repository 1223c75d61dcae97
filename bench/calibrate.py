#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1-12 --seconds 5 \
        [--controls f32,bf16] [--control-seeds 1-3]

In one process (a chip belongs to one process), for each seed: a run of
the cell as ``bench/run.py`` makes it, with a short window, and the
numbers its check compares.  Then, on the ``--control-seeds`` (the
first three seeds unless given), the same run with the plain reference
put in the program's place at each precision of ``--controls``.
Each reading is one JSON line on standard output.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:] = [p for p in sys.path
               if pathlib.Path(p or ".").resolve() != _ROOT / "bench"]
sys.path.insert(0, str(_ROOT))

from bench import run  # noqa: E402


def seeds(spec: str) -> list:
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def reading(kind: str, seed: int, line: dict) -> dict:
    return {"kind": kind, "seed": seed, "correct": line["correct"],
            "steps": line["attempted"], "failed": line["failed"],
            "checks": {k: v["value"] for k, v in line["checks"].items()},
            "setup": line["setup"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--controls", default="")
    ap.add_argument("--control-seeds", default=None,
                    help="seeds of the controls (default: the first "
                         "three of --seeds)")
    args = ap.parse_args(argv)
    wl = run.cell(args.workload)
    run.configure_jax()
    devices = run.chips(wl["chips"])
    app = run.load_module("apps", wl["config"])
    quiet = lambda _msg: None  # noqa: E731
    all_seeds = seeds(args.seeds) if args.seeds else []
    control_seeds = (seeds(args.control_seeds) if args.control_seeds
                     else all_seeds[:3])
    for s in all_seeds:
        line = run.measure(args.workload, s, args.seconds, False,
                           devices=devices, log=quiet)
        print(json.dumps(reading("program", s, line)), flush=True)
    for s in control_seeds:
        for name in filter(None, args.controls.split(",")):
            line = run.measure(
                args.workload, s, args.seconds, False, devices=devices,
                replace=lambda st, name=name: app.control(st, name),
                log=quiet)
            print(json.dumps(reading(f"control:{name}", s, line)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
