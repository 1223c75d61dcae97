#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: ``bench/workloads/<cell>.json``
names its configuration and traffic, ``bench/configs/<config>.json``
holds the sizes, the offload settings and the correctness limits,
``bench/apps/<config>.py`` plays the application, and each per-layer
metric is read by ``bench/metrics/<metric>.py``.

A run makes its inputs from the seed, warms up every shape of the cell
(set-up), runs whole application steps for ``--seconds`` (the window,
which ends on a step boundary), reads the device's peak memory, frees
the program's state, and then compares what the window produced with a
plain reference on the host.  The last line of standard output is one
JSON object; the numbers compared, each with its limit, are the last
lines of standard error.  With ``--trace 1`` the window runs under the
profiler and the line carries the per-layer metrics instead of the
end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result.  Everything runs in this one process: the host
tier is the CPU backend of the process that holds the chip.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache: a fixed directory of the checkout
CACHE_DIR = ROOT / ".jax_cache"
# the benchmark's modules are imported as ``bench.*``: its own directory,
# which Python puts first for a script, would shadow the standard
# library's ``trace``
sys.path[:] = [p for p in sys.path
               if pathlib.Path(p or ".").resolve() != BENCH]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import trace as tracing  # noqa: E402

#: the counters of a routine's row that a window takes differences of
COUNTERS = ("calls", "offloaded", "on_host", "seconds", "bytes_in",
            "bytes_out")


class BenchError(Exception):
    """A cell, configuration, metric or device the benchmark cannot use."""


# --------------------------------------------------------------------- #
# finding things by name                                                 #
# --------------------------------------------------------------------- #
def load_json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


_MODULES: Dict[str, object] = {}


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, imported once."""
    key = f"{kind}/{name}"
    if key not in _MODULES:
        path = BENCH / kind / f"{name}.py"
        if not path.is_file():
            raise BenchError(f"no {kind[:-1]} module named {name!r} "
                             f"({path})")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        _MODULES[key] = mod
    return _MODULES[key]


def benchmark() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no {path}")
    return json.loads(path.read_text())


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def cell(name: str) -> dict:
    """The workload file, held to its entry in BENCHMARK.json."""
    wl = load_json("workloads", name)
    entry = [w for w in benchmark()["workloads"] if w["name"] == name]
    if not entry:
        raise BenchError(f"workload {name!r} is not in BENCHMARK.json")
    for key in ("config", "traffic", "chips"):
        if wl[key] != entry[0][key]:
            raise BenchError(f"{name}: {key} is {wl[key]!r} in its file "
                             f"and {entry[0][key]!r} in BENCHMARK.json")
    return wl


def metrics_of(workload: str, per_layer: bool) -> List[dict]:
    """The BENCHMARK.json entries of the metrics ``workload`` reports."""
    group = benchmark()["per_layer" if per_layer else "end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


# --------------------------------------------------------------------- #
# the window                                                             #
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class Window:
    """What the per-layer metric readers read: one measured window."""

    steps: int
    seconds: float                      # host clock, whole steps
    counters: Dict[str, Dict[str, float]]   # per routine, window deltas
    calls: list                         # the runtime's call records
    flops_per_step: float
    peaks: dict
    trace: Optional[tracing.Reduced] = None


class CompileClock:
    """Backend compiles and their seconds, from JAX's own events."""

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def snapshot(stats) -> Dict[str, Dict[str, float]]:
    return {r: {k: getattr(v, k) for k in COUNTERS}
            for r, v in stats.per_routine.items()}


def delta(before, after) -> Dict[str, Dict[str, float]]:
    out = {}
    for r, row in after.items():
        base = before.get(r, {})
        d = {k: row[k] - base.get(k, 0) for k in COUNTERS}
        if d["calls"]:
            out[r] = d
    return out


def configure_jax() -> None:
    """x64 (fp64 and complex cells), and the persistent compilation
    cache in the checkout with every program kept: the solver tier
    lowers about a hundred small programs, each under JAX's default
    minimum of one second of compiling."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        # the runtime's host tier is the CPU backend's device
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax
    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips(count: int) -> list:
    """The TPU devices this cell runs on; BenchError without them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's default backend is "
                         f"{devices[0].platform}")
    if len(devices) < count:
        raise BenchError(f"the cell asks for {count} chips and JAX sees "
                         f"{len(devices)}")
    return devices[:count]


def memory_peak(devices) -> Optional[int]:
    peaks_in_use = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                    for d in devices]
    peaks_in_use = [p for p in peaks_in_use if p is not None]
    return max(peaks_in_use) if peaks_in_use else None


def measure(workload: str, seed: int, seconds: float, trace: bool, *,
            devices: Optional[list] = None,
            overrides: Optional[dict] = None,
            replace: Optional[Callable] = None,
            log=print) -> dict:
    """One run of ``workload``: set-up, window, check.  Returns the
    result line as a dict.

    ``devices``: the chips (None: the default device, no platform
    check).  ``overrides``: configuration keys replaced (sizes for a
    small run; nested dicts are merged).  ``replace``: a function of the
    application's state returning attributes to put in place of its
    callables after set-up: a control, or a fault planted under the
    window.
    """
    import jax
    wl = cell(workload)
    cfg = load_json("configs", wl["config"])
    for k, v in (overrides or {}).items():
        cfg[k] = {**cfg[k], **v} if isinstance(v, dict) else v
    app = load_module("apps", wl["config"])
    if devices is None:
        devices = jax.devices()[:1]
    clock = CompileClock()
    st = app.setup(cfg, wl["params"], seed)
    setup_s = time.perf_counter() - _T0
    setup_compiles = (clock.count, clock.seconds)
    if replace is not None:
        for k, v in replace(st).items():
            setattr(st, k, v)
    stats, calls = st.session.stats, st.session.trace.calls
    before, n0 = snapshot(stats), len(calls)
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    c0 = clock.count
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        t0 = t = time.perf_counter()
        step_times = []
        while not step_times or t - t0 < seconds:
            app.step(st)
            now = time.perf_counter()
            step_times.append(now - t)
            t = now
    steps, window_s = len(step_times), t - t0
    window_compiles = clock.count - c0
    if trace:
        jax.profiler.stop_trace()
    win = Window(steps=steps, seconds=window_s,
                 counters=delta(before, snapshot(stats)),
                 calls=list(calls[n0:]),
                 flops_per_step=app.flops_per_step(cfg),
                 peaks=peaks(devices[0].device_kind)
                 if devices[0].platform == "tpu" else {})
    mem = memory_peak(devices)
    app.close(st)
    platform = devices[0].platform
    if trace:
        try:
            win.trace = tracing.reduce(log_dir, app.SPANS,
                                       device=platform)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
    checks, failed = app.check(st)
    correct = steps > 0 and failed == 0 and all(v <= lim
                                                for _, v, lim in checks)
    step_times.sort()
    log(f"setup_s {setup_s:.3f} (compiles {setup_compiles[0]}, "
        f"{setup_compiles[1]:.3f} s compiling); window {window_s:.3f} s, "
        f"{steps} steps (min {step_times[0]:.4f}, median "
        f"{step_times[steps // 2]:.4f}, max {step_times[-1]:.4f} s), "
        f"{window_compiles} compiles in the window; memory peak {mem} "
        f"bytes")
    e2e = {"step_s": window_s / steps, "setup_s": setup_s}
    metrics = {}
    for m in metrics_of(workload, per_layer=trace):
        value = (load_module("metrics", m["name"]).read(win) if trace
                 else e2e[m["name"]])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    line = {"correct": bool(correct), "attempted": steps, "failed": failed,
            "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = win.trace.busy_s
        device["window_s"] = win.trace.window_s
        line["breakdown"] = {
            "device_ops": tracing.top_programs(win.trace.programs),
            "idle_gaps": [[n, s] for n, s in win.trace.idle_gaps]}
    line["setup"] = {"compiles": setup_compiles[0],
                     "compile_s": setup_compiles[1],
                     "window_compiles": window_compiles}
    line["checks"] = {n: {"value": v, "limit": lim}
                      for n, v, lim in checks}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the system under test is not beside the benchmark "
              f"({ROOT / 'src' / 'repro'})", file=sys.stderr)
        return 2
    try:
        wl = cell(args.workload)
        configure_jax()
        devices = chips(wl["chips"])
        peaks(devices[0].device_kind)
        line = measure(args.workload, args.seed, args.seconds,
                       bool(args.trace), devices=devices,
                       log=lambda m: print(m, file=sys.stderr))
    except BenchError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
