"""Reduce a ``jax.profiler`` trace to the benchmark's device and host numbers.

The measured window is the host annotation ``bench.window``; everything is
clipped to it.  Two kinds of execution are read:

* the accelerator: events on the ``XLA Ops`` line of every ``/device:TPU:N``
  plane give the busy time (the union of their intervals, averaged over
  the chips), and events on the ``XLA Modules`` line give the device time
  of each jitted program under its stable jit name (``jit__gemm_kernel``);
* the host backend: XLA's CPU client runs a program as
  ``ThunkExecutor::Execute*`` events, on its own threads or inline on the
  caller's.  They carry no program name, so each is attributed to the
  latest ``PjitFunction(<f>)`` dispatch that started before it, as
  ``jit_<f>``.

Idle gaps of the accelerator are named by what the host was doing in
them: the application span (``jax.profiler.TraceAnnotation``) that covers
most of the gap, and the host-backend program that ran in it, if any ran
for at least half of it.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
TPU_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_CPU_EXEC = "ThunkExecutor::Execute"
_DISPATCH = re.compile(r"^PjitFunction\((.+)\)$")
# a module event may carry its program id: "jit__gemm_kernel(42)"
_PROGRAM_ID = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, non-overlapping union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` that the merged ``busy`` leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def program_name(event_name: str) -> str:
    return _PROGRAM_ID.sub("", event_name)


@dataclasses.dataclass
class Events:
    """The trace's events, as ``(name, start_ns, end_ns)`` per kind."""

    window: Interval
    chips: int
    device_ops: List[List[Interval]]            # per chip
    device_modules: List[Tuple[str, float, float]]
    host_exec: List[Tuple[str, float, float]]   # host backend, by program
    spans: List[Tuple[str, float, float]]       # application annotations


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                    # accelerator busy, mean over chips
    programs: Dict[str, float]       # accelerator seconds per program
    host_programs: Dict[str, float]  # host-backend seconds per program
    host_busy_s: float               # union of host-backend executions
    spans: Dict[str, float]          # seconds per application span
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def xplane_file(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read_events(path: str, span_names: Sequence[str] = ()) -> Events:
    """Read the events this module reduces from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    wanted = set(span_names)
    window = None
    device_ops: List[List[Interval]] = []
    modules: List[Tuple[str, float, float]] = []
    dispatches: List[Tuple[float, str]] = []
    execs: List[Interval] = []
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if TPU_PLANE.match(plane.name):
            ops: List[Interval] = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.extend((program_name(e.name), e.start_ns,
                                    e.start_ns + e.duration_ns)
                                   for e in line.events)
            device_ops.append(ops)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name.startswith(_CPU_EXEC):
                    execs.append((e.start_ns, e.start_ns + e.duration_ns))
                elif name == WINDOW:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif name in wanted:
                    spans.append((name, e.start_ns,
                                  e.start_ns + e.duration_ns))
                else:
                    m = _DISPATCH.match(name)
                    if m:
                        dispatches.append((e.start_ns, "jit_" + m.group(1)))
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    return Events(window=window, chips=len(device_ops),
                  device_ops=device_ops, device_modules=modules,
                  host_exec=_attribute(merge(execs), sorted(dispatches)),
                  spans=spans)


def _attribute(execs: List[Interval], dispatches: List[Tuple[float, str]]
               ) -> List[Tuple[str, float, float]]:
    """Name each host-backend execution by the latest dispatch that
    started before it."""
    out, i, last = [], 0, "unattributed"
    for s, e in execs:
        while i < len(dispatches) and dispatches[i][0] <= s:
            last = dispatches[i][1]
            i += 1
        out.append((last, s, e))
    return out


def reduce_events(ev: Events, device: str = "tpu",
                  top: int = 10) -> Reduced:
    """Reduce to busy time, per-program time and named idle gaps.

    ``device="tpu"`` takes the accelerator planes as the device;
    ``device="cpu"`` takes the host backend's executions in their place
    (a trace recorded without an accelerator)."""
    lo, hi = ev.window
    host = [(n, max(s, lo), min(e, hi)) for n, s, e in ev.host_exec
            if e > lo and s < hi]
    host_programs: Dict[str, float] = collections.defaultdict(float)
    for n, s, e in host:
        host_programs[n] += (e - s) * 1e-9
    if device == "tpu":
        if not ev.chips:
            raise ValueError("the trace holds no TPU plane")
        per_chip = [merge(clip(ops, lo, hi)) for ops in ev.device_ops]
        busy_ns = sum(length(b) for b in per_chip) / len(per_chip)
        union = merge(i for b in per_chip for i in b)
        programs: Dict[str, float] = collections.defaultdict(float)
        for n, s, e in ev.device_modules:
            programs[n] += overlap((s, e), (lo, hi)) * 1e-9
    elif device == "cpu":
        union = merge((s, e) for _, s, e in host)
        busy_ns = length(union)
        programs = host_programs
    else:
        raise ValueError(f"unknown device {device!r}")
    spans: Dict[str, float] = collections.defaultdict(float)
    for n, s, e in ev.spans:
        spans[n] += overlap((s, e), (lo, hi)) * 1e-9
    idle = sorted(gaps(union, lo, hi), key=lambda g: g[0] - g[1])[:top]
    named = [(_name_gap(g, ev.spans, host), (g[1] - g[0]) * 1e-9)
             for g in idle]
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9,
                   programs=dict(programs),
                   host_programs=dict(host_programs),
                   host_busy_s=length(merge((s, e) for _, s, e in host))
                   * 1e-9,
                   spans=dict(spans), idle_gaps=named)


def _name_gap(gap: Interval, spans, host) -> str:
    by_span: Dict[str, float] = collections.defaultdict(float)
    for n, s, e in spans:
        by_span[n] += overlap(gap, (s, e))
    label = max(by_span, key=by_span.get) if any(by_span.values()) \
        else "outside spans"
    by_prog: Dict[str, float] = collections.defaultdict(float)
    for n, s, e in host:
        by_prog[n] += overlap(gap, (s, e))
    if by_prog:
        prog = max(by_prog, key=by_prog.get)
        if by_prog[prog] >= 0.5 * (gap[1] - gap[0]):
            label += f":{prog}@host"
    return label


def reduce(log_dir: str, span_names: Sequence[str] = (),
           device: str = "tpu", top: int = 10) -> Reduced:
    """Read the trace written under ``log_dir`` and reduce it."""
    return reduce_events(read_events(xplane_file(log_dir), span_names),
                         device=device, top=top)


def top_programs(programs: Dict[str, float], top: int = 10
                 ) -> List[List[object]]:
    return [[n, s] for n, s in sorted(programs.items(),
                                      key=lambda kv: -kv[1])[:top]]


def program_seconds(programs: Dict[str, float], pattern: str
                    ) -> Optional[float]:
    """Seconds of the programs whose name matches ``pattern`` (a regex),
    or None when none ran."""
    rx = re.compile(pattern)
    hits = [s for n, s in programs.items() if rx.search(n)]
    return sum(hits) if hits else None
