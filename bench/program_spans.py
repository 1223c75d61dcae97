#!/usr/bin/env python3
"""The runtime's own spans and counters over one traced window of a cell.

The runtime marks its stages with host spans whose names start with
``scilib.`` (``repro.core.trace.SPANS``: the dispatch decision, operand
placement, submission, the host path, each move over the host link, each
solver, the LU's panel, permutation and stores, garbage-collection
pauses), and counts the bytes and host seconds of every link move by
direction (``RuntimeStats.link_*``, read flat through
``RuntimeStats.flat()``).  ``bench/trace.py`` reads only the drivers'
spans, and a metric reader sees only what ``bench/run.py``'s ``Window``
carries; this module reads the rest from the same trace and the same
window:

* :func:`read` — every ``scilib.*`` host event of a trace;
* :func:`seconds` — their seconds per name, clipped to the window;
* :func:`name_gaps` — the accelerator's longest idle gaps, named as
  ``bench/trace.py`` names them, with the innermost program span that
  covers at least half of a gap put after the application span:
  ``lsms.lu_factor/scilib.lu.panel:jit__getf2@host``.  Without such a
  span a label is exactly ``bench/trace.py``'s.

Run as a script, it measures one traced window of a cell exactly as
``bench/run.py --trace 1`` does and prints that result line with
``program``: the window's difference of the runtime's flat counters,
the program spans' seconds, the idle gaps named by them (each with the
seconds every program span overlaps it), and the readings they give (``link_mb_all_per_step``, ``link_host_ms_per_step``,
``lu_panel_span_share``)::

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>

It needs a TPU, as ``bench/run.py`` does.  On a program without the
spans or counters the readings are left out.
"""
from __future__ import annotations

import argparse
import collections
import json
import pathlib
import sys
from typing import Dict, List, Optional, Sequence, Tuple

_ROOT = pathlib.Path(__file__).resolve().parents[1]
# as a script, this directory would shadow the standard library's ``trace``
sys.path[:] = [p for p in sys.path
               if pathlib.Path(p or ".").resolve() != _ROOT / "bench"]
for _p in (str(_ROOT / "src"), str(_ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import trace as tracing  # noqa: E402

PREFIX = "scilib."
LU_PANEL = "scilib.lu.panel"

Span = Tuple[str, float, float]


def read(path: str) -> List[Span]:
    """Every host event of ``path`` (an ``.xplane.pb``) whose name starts
    with ``scilib.``, as ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events if e.name.startswith(PREFIX))
    return out


def seconds(spans: Sequence[Span], window: tracing.Interval
            ) -> Dict[str, float]:
    """Seconds per span name inside ``window``."""
    out: Dict[str, float] = collections.defaultdict(float)
    for n, s, e in spans:
        out[n] += tracing.overlap((s, e), window) * 1e-9
    return {n: v for n, v in out.items() if v > 0}


def innermost(gap: tracing.Interval, spans: Sequence[Span]
              ) -> Optional[str]:
    """The innermost span name that covers at least half of ``gap``:
    of the names whose spans overlap the gap by half its length or more,
    the one whose overlapping span started last."""
    cover: Dict[str, float] = collections.defaultdict(float)
    start: Dict[str, float] = {}
    for n, s, e in spans:
        ov = tracing.overlap(gap, (s, e))
        if ov > 0:
            cover[n] += ov
            start[n] = max(start.get(n, s), s)
    inside = [n for n, c in cover.items() if c >= 0.5 * (gap[1] - gap[0])]
    return max(inside, key=lambda n: start[n]) if inside else None


def _idle(ev: tracing.Events, device: str, top: int):
    """The ``top`` longest idle gaps, longest first, as
    :func:`bench.trace.reduce_events` finds them, and the window's
    host-backend executions."""
    lo, hi = ev.window
    host = [(n, max(s, lo), min(e, hi)) for n, s, e in ev.host_exec
            if e > lo and s < hi]
    if device == "tpu":
        union = tracing.merge(i for ops in ev.device_ops
                              for i in tracing.clip(ops, lo, hi))
    else:
        union = tracing.merge((s, e) for _, s, e in host)
    return sorted(tracing.gaps(union, lo, hi),
                  key=lambda g: g[0] - g[1])[:top], host


def gap_spans(ev: tracing.Events, program: Sequence[Span],
              device: str = "tpu", top: int = 10
              ) -> List[Dict[str, float]]:
    """For each of the longest idle gaps, the seconds each program span
    name overlaps it: what the host was doing there."""
    idle, _ = _idle(ev, device, top)
    return [seconds(program, g) for g in idle]


def name_gaps(ev: tracing.Events, program: Sequence[Span],
              device: str = "tpu", top: int = 10
              ) -> List[Tuple[str, float]]:
    """The ``top`` longest idle gaps of the window, longest first, each
    named as :func:`bench.trace.reduce_events` names it with
    ``/<program span>`` after the application span."""
    idle, host = _idle(ev, device, top)
    out = []
    for g in idle:
        label = tracing._name_gap(g, ev.spans, host)
        prog = innermost(g, program)
        if prog is not None:
            app, _, rest = label.partition(":")
            label = f"{app}/{prog}" + (f":{rest}" if rest else "")
        out.append((label, (g[1] - g[0]) * 1e-9))
    return out


def readings(counters: Dict[str, float], spans: Dict[str, float],
             steps: int, window_s: float) -> Dict[str, float]:
    """What the window's counter differences and span seconds give:
    link megabytes and host milliseconds per step over both directions,
    and the LU panel span's share of the window."""
    out = {}
    if "link_h2d_bytes" in counters:
        out["link_mb_all_per_step"] = (counters["link_h2d_bytes"]
                                       + counters["link_d2h_bytes"]
                                       ) / steps / 1e6
        out["link_host_ms_per_step"] = 1e3 * (
            counters["link_h2d_seconds"]
            + counters["link_d2h_seconds"]) / steps
    if spans.get(LU_PANEL):
        out["lu_panel_span_share"] = 100.0 * spans[LU_PANEL] / window_s
    return out


def _flat(st) -> Dict[str, float]:
    flat = getattr(st.session.stats, "flat", None)
    return flat() if flat is not None else {}


def measure(workload: str, seed: int, window: float, *, devices=None,
            overrides=None, log=print) -> dict:
    """One traced window of ``workload`` through ``bench.run.measure``,
    with the program's counters taken at the window's two ends and its
    spans read from the window's trace before the harness deletes it."""
    from bench import run
    app = run.load_module("apps", run.cell(workload)["config"])
    got: dict = {}

    def reduce(log_dir, span_names=(), device="tpu", top=10):
        path = tracing.xplane_file(log_dir)
        ev = tracing.read_events(path, span_names)
        program = read(path)
        got["spans"] = seconds(program, ev.window)
        got["gaps"] = name_gaps(ev, program, device=device, top=top)
        got["gap_spans"] = gap_spans(ev, program, device=device, top=top)
        got["window_s"] = (ev.window[1] - ev.window[0]) * 1e-9
        return tracing.reduce_events(ev, device=device, top=top)

    def before(st):
        got["before"] = _flat(st)
        return {}

    def close(st):
        got["after"] = _flat(st)
        app_close(st)

    app_close, reduce_of = app.close, tracing.reduce
    app.close, tracing.reduce = close, reduce
    try:
        line = run.measure(workload, seed, window, True, devices=devices,
                           overrides=overrides, replace=before, log=log)
    finally:
        app.close, tracing.reduce = app_close, reduce_of
    base = got["before"]
    counters = {k: v - base.get(k, 0) for k, v in got["after"].items()}
    steps = line["attempted"]
    line["program"] = {
        "counters": counters, "spans": got["spans"],
        "idle_gaps": [[n, s, by_span] for (n, s), by_span
                      in zip(got["gaps"], got["gap_spans"])],
        "readings": readings(counters, got["spans"], steps,
                             got["window_s"])}
    return line


def main(argv=None) -> int:
    from bench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        wl = run.cell(args.workload)
        run.configure_jax()
        devices = run.chips(wl["chips"])
        line = measure(args.workload, args.seed, args.seconds,
                       devices=devices,
                       log=lambda m: print(m, file=sys.stderr))
    except run.BenchError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
