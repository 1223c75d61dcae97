"""The runtime's ``scilib.*`` spans in a CPU-profiled trace, idle gaps
named by them, the readings of the program's counters, and the stage
metric readers on synthetic windows."""
import gc
import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import program_spans as ps  # noqa: E402
from bench import run  # noqa: E402
from bench import trace as tracing  # noqa: E402


def _events():
    """A window 0..100 ns: device busy 0-10 and 60-100, so one gap
    10-60, inside the application span, with a host getf2 in it."""
    return tracing.Events(
        window=(0, 100), chips=1, device_ops=[[(0, 10), (60, 100)]],
        device_modules=[], host_exec=[("jit__getf2", 12, 55)],
        spans=[("lsms.lu_factor", 5, 95)])


def test_gap_named_by_the_innermost_program_span():
    program = [("scilib.solver.getrf", 5, 95), ("scilib.lu.panel", 11, 58),
               ("scilib.link.d2h", 11, 14)]
    [(label, s)] = ps.name_gaps(_events(), program)
    assert label == "lsms.lu_factor/scilib.lu.panel:jit__getf2@host"
    assert s == pytest.approx(50e-9)
    assert ps.gap_spans(_events(), program) == [{
        "scilib.solver.getrf": pytest.approx(50e-9),
        "scilib.lu.panel": pytest.approx(47e-9),
        "scilib.link.d2h": pytest.approx(3e-9)}]


def test_program_span_under_half_the_gap_is_left_out():
    # the panel span covers 20 of the 50 ns; the solver span all of it
    program = [("scilib.solver.getrf", 5, 95), ("scilib.lu.panel", 40, 60)]
    [(label, _)] = ps.name_gaps(_events(), program)
    assert label == "lsms.lu_factor/scilib.solver.getrf:jit__getf2@host"
    [(label, _)] = ps.name_gaps(_events(), [("scilib.lu.panel", 40, 60)])
    assert label == "lsms.lu_factor:jit__getf2@host"


def test_labels_without_program_spans_are_the_harness_labels():
    ev = _events()
    ev.host_exec = []
    assert ps.name_gaps(ev, []) == tracing.reduce_events(ev).idle_gaps
    assert ps.name_gaps(_events(), []) == \
        tracing.reduce_events(_events()).idle_gaps
    [(label, _)] = ps.name_gaps(ev, [("scilib.host.gc", 10, 60)])
    assert label == "lsms.lu_factor/scilib.host.gc"


def test_span_seconds_are_clipped_to_the_window():
    spans = [("scilib.lu.panel", -50, 50), ("scilib.lu.panel", 60, 70),
             ("scilib.decide", 200, 300)]
    assert ps.seconds(spans, (0, 100)) == {
        "scilib.lu.panel": pytest.approx(60e-9)}


def test_readings_of_the_window():
    counters = {"link_h2d_bytes": 3e6, "link_d2h_bytes": 1e6,
                "link_h2d_seconds": 0.002, "link_d2h_seconds": 0.004}
    got = ps.readings(counters, {"scilib.lu.panel": 4.0}, steps=2,
                      window_s=10.0)
    assert got == {"link_mb_all_per_step": pytest.approx(2.0),
                   "link_host_ms_per_step": pytest.approx(3.0),
                   "lu_panel_span_share": pytest.approx(40.0)}
    # a program without the counters or the spans gives nothing
    assert ps.readings({}, {}, steps=2, window_s=10.0) == {}


def _window(calls):
    return run.Window(steps=2, seconds=1.0, counters={}, calls=calls,
                      flops_per_step=0.0, peaks={})


def _call(routine, seconds, decide, place):
    return types.SimpleNamespace(routine=routine, seconds=seconds,
                                 decide_seconds=decide,
                                 place_seconds=place)


@pytest.mark.parametrize("metric,want", [("decide_us_per_call", 30.0),
                                         ("submit_us_per_call", 700.0)])
def test_stage_metric_readers(metric, want):
    read = run.load_module("metrics", metric).read
    calls = [_call("zgemm", 1e-3, 20e-6, 0.5e-3),
             _call("ztrsm", 2e-3, 40e-6, 1.1e-3),
             _call("cgetf2", 0.0, 0.0, 0.0)]       # panels are left out
    assert read(_window(calls)) == pytest.approx(want)
    assert read(_window([_call("cgetf2", 0.0, 0.0, 0.0)])) is None
    # call records of a program without stage seconds
    old = [types.SimpleNamespace(routine="zgemm", seconds=1e-3)]
    assert read(_window(old)) is None


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A small LU and one gemm through a session, and a forced garbage
    collection, recorded by the CPU profiler inside a window."""
    import jax
    import jax.numpy as jnp
    import repro
    from repro.core import blas
    from repro.core.policy import host_array
    from repro.solvers import drivers

    rng = np.random.default_rng(5)
    a = jnp.asarray(rng.standard_normal((64, 64)).astype(np.float32))
    b = rng.standard_normal((64, 64)).astype(np.float32)
    cfg = repro.OffloadConfig(threshold=1, lapack=True)
    with repro.session(cfg):                      # compile outside
        drivers.getrf(a, nb=16)
        blas.gemm(host_array(b), host_array(b))
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            with repro.session(cfg) as s:
                lu, _ = drivers.getrf(a, nb=16)
                blas.gemm(host_array(b), host_array(b))
                gc.collect()
                s.sync()
            lu.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    return tracing.xplane_file(log_dir)


def test_runtime_spans_in_a_profiled_run(profiled):
    from repro.core import trace as trc
    ev = tracing.read_events(profiled, trc.SPANS)
    names = {n for n, _, _ in ev.spans}
    assert {"scilib.lu.panel", "scilib.lu.permute", "scilib.lu.store",
            "scilib.decide", "scilib.place", "scilib.submit",
            "scilib.solver.getrf", "scilib.link.h2d", "scilib.link.d2h",
            "scilib.host.gc"} <= names
    assert names == {n for n, _, _ in ps.read(profiled)}
    # four panels, each inside the solver span
    getrf = [(s, e) for n, s, e in ev.spans if n == "scilib.solver.getrf"]
    panels = [(s, e) for n, s, e in ev.spans if n == "scilib.lu.panel"]
    assert len(getrf) == 1 and len(panels) == 4
    assert all(getrf[0][0] <= s and e <= getrf[0][1] for s, e in panels)


def test_harness_reduction_ignores_the_runtime_spans(profiled):
    """bench/trace.py reads only the spans it is asked for: with the
    runtime's spans in the trace, its reduction names nothing after
    them, and naming the gaps without program spans gives its labels."""
    ev = tracing.read_events(profiled)
    red = tracing.reduce_events(ev, device="cpu")
    assert red.spans == {}
    assert not any("scilib" in n for n, _ in red.idle_gaps)
    assert ps.name_gaps(ev, [], device="cpu") == red.idle_gaps
    named = ps.name_gaps(ev, ps.read(profiled), device="cpu")
    assert [s for _, s in named] == [s for _, s in red.idle_gaps]
