"""The trace reduction on a trace recorded by the CPU profiler, and the
operation and byte counts against hand counts of the cells' shapes."""
import pathlib
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cost  # noqa: E402
from bench import trace as tracing  # noqa: E402

SLEEP_S = 0.08


def test_interval_arithmetic():
    busy = tracing.merge([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert tracing.length(busy) == 7
    assert tracing.gaps(busy, -1, 12) == [(-1, 0), (3, 5), (9, 12)]
    assert tracing.clip(busy, 2, 6) == [(2, 3), (5, 6)]
    assert tracing.program_name("jit__gemm_kernel(42)") == "jit__gemm_kernel"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A window with two jitted programs on the CPU backend, then a host
    sleep inside its own span: the sleep is the longest idle gap."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _getf2(x):
        return jax.lax.fori_loop(0, 8, lambda i, c: (c @ c) * 0.01, x)

    @jax.jit
    def _gemm_kernel(a, b):
        return a @ b

    x = jnp.ones((256, 256), jnp.float32)
    _getf2(x).block_until_ready()
    _gemm_kernel(x, x).block_until_ready()
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            with jax.profiler.TraceAnnotation("app.compute"):
                for _ in range(3):
                    _gemm_kernel(x, x).block_until_ready()
                    _getf2(x).block_until_ready()
            with jax.profiler.TraceAnnotation("app.sleep"):
                time.sleep(SLEEP_S)
        wall = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    spans = ("app.compute", "app.sleep")
    return (tracing.reduce(log_dir, spans, device="cpu"), wall,
            tracing.read_events(tracing.xplane_file(log_dir), spans))


def test_window_and_busy_union(recorded):
    red, wall, ev = recorded
    assert 0.9 * wall <= red.window_s <= wall + 0.01
    assert 0 < red.busy_s < red.window_s - SLEEP_S
    # busy is the union: no more than the programs' sum, no less than any
    assert red.busy_s <= sum(red.programs.values()) + 1e-9
    assert red.busy_s >= max(red.programs.values()) - 1e-9
    lo, hi = ev.window
    union = tracing.merge(tracing.clip([(s, e) for _, s, e in ev.host_exec],
                                       lo, hi))
    assert red.busy_s == pytest.approx(tracing.length(union) * 1e-9)
    assert red.idle_share == pytest.approx(1 - red.busy_s / red.window_s)
    assert red.idle_share > SLEEP_S / red.window_s * 0.9


def test_time_per_program_by_jit_name(recorded):
    red, _, _ = recorded
    assert red.programs["jit__gemm_kernel"] > 0
    assert red.programs["jit__getf2"] > 0
    assert tracing.program_seconds(red.programs, r"^jit_.*gemm") == \
        pytest.approx(red.programs["jit__gemm_kernel"])
    assert tracing.program_seconds(red.programs, r"nothing") is None
    assert [n for n, _ in tracing.top_programs(red.programs, 1)] == \
        [max(red.programs, key=red.programs.get)]


def test_host_annotations_are_attributed(recorded):
    red, _, _ = recorded
    assert red.spans["app.sleep"] >= SLEEP_S
    assert red.spans["app.compute"] > 0
    name, seconds = red.idle_gaps[0]
    assert name == "app.sleep"
    assert seconds >= 0.9 * SLEEP_S
    assert all(s <= seconds for _, s in red.idle_gaps)


def test_tpu_reduction_refuses_a_trace_without_a_tpu(recorded):
    _, _, ev = recorded
    with pytest.raises(ValueError):
        tracing.reduce_events(ev, device="tpu")


@pytest.mark.parametrize("m,n,k,batch,dtype,flops,nbytes", [
    # PARSEC: S_blk = Hpsi_blk^T Psi, 32 x 2400 x 93536, fp64
    (32, 2400, 93536, 1, np.float64, 1.4367e10, 1.8205e9),
    # LSMS build: block-diagonal t times G, complex64 at n = 6912 with
    # 216 blocks of 32: 8 n^2 kkrsz real operations; t's blocks and G
    # read, t G written
    (32, 6912, 32, 216, np.complex64, 8 * 6912.0 ** 2 * 32,
     (216 * 32 * 32 + 2 * 6912 ** 2) * 8),
])
def test_cost_against_hand_counts(m, n, k, batch, dtype, flops, nbytes):
    assert cost.gemm_flops(m, n, k, dtype, batch) == pytest.approx(
        flops, rel=1e-4)
    assert cost.gemm_bytes(m, n, k, dtype, batch=batch) == pytest.approx(
        nbytes, rel=1e-4)


def test_cost_of_the_solver_and_the_roofline():
    n = 6912
    assert cost.getrf_flops(n, np.complex64) == pytest.approx(8 / 3 * n ** 3)
    assert cost.getrs_flops(n, 32, np.complex64) == 8 * n * n * 32
    # a trailing update reads and writes C
    assert cost.gemm_bytes(10, 20, 5, np.float32, has_c=True) == \
        (50 + 100 + 2 * 200) * 4
    v5e = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # PARSEC's product is bound by bytes: 1.8205e9 / 819e9 = 2.22 ms
    assert cost.roofline_s(1.4367e10, 1.8205e9, v5e) == \
        pytest.approx(2.2228e-3, rel=1e-3)
    # LSMS's build is bound by bytes: G read and t G written, 766 MB /
    # 819 GB/s = 0.94 ms against 1.22e10 / 197e12 = 0.062 ms
    build = (cost.gemm_flops(32, n, 32, np.complex64, 216),
             cost.gemm_bytes(32, n, 32, np.complex64, batch=216))
    assert cost.roofline_s(*build, v5e) == pytest.approx(build[1] / 819e9)
    assert cost.roofline_s(*build, v5e) == pytest.approx(9.355e-4, rel=1e-3)
