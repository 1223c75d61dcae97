"""Offload runtime, policies, interception, threshold, serving placement."""
import gc
import importlib.util
import pathlib
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
import repro.core as core
from repro import OffloadConfig
from repro.core import blas, memspace
from repro.core.policy import host_array
from repro.core.threshold import n_avg, should_offload

RNG = np.random.default_rng(2)


def test_threshold_navg_gemm():
    assert n_avg("zgemm", 600, 600, 600) == pytest.approx(600.0)
    off, nav = should_offload("dgemm", 32, 2400, 93536, threshold=500)
    assert off and nav > 1900  # the PARSEC skinny shape offloads


def test_threshold_below_stays_host():
    with core.offload("dfu", threshold=500) as rt:
        a = jnp.ones((64, 64), jnp.float32)
        jnp.matmul(a, a)
    assert rt.stats.per_routine["sgemm"].on_host == 1


def test_dtype_the_device_compiler_refuses_stays_host(monkeypatch):
    """On a TPU complex128 BLAS-3 aborts the compiler: such calls run on
    the host path; complex64 still offloads."""
    import types
    with jax.enable_x64(True), core.offload("dfu", threshold=10) as rt:
        monkeypatch.setattr(rt, "memspace",
                            types.SimpleNamespace(device_platform="tpu"))
        for dt in ("complex128", "complex64"):
            a = jnp.ones((128, 128), dt)
            jnp.matmul(a, a)
        assert rt.stats.per_routine["zgemm"].on_host == 1
        assert rt.stats.per_routine["cgemm"].offloaded == 1


@pytest.mark.parametrize("routine", ["sgemm", "cgemm", "strsm", "ssyrk"])
def test_host_path_never_lowers_a_tpu_kernel(monkeypatch, routine):
    """With a TPU as the default backend, the host path's operands sit on
    the CPU device: the kernels are chosen for the platform the call is
    lowered for, so a sub-threshold call reaches the reference, never a
    Pallas TPU kernel (which cannot be lowered for the CPU)."""
    from repro.kernels import ops as kops
    monkeypatch.setattr(kops, "_backend", lambda: "tpu")
    monkeypatch.delenv("SCILIB_PALLAS", raising=False)
    # shapes no other test traces, so the jitted kernels trace afresh
    dt = "complex64" if routine == "cgemm" else "float32"
    a_np = (RNG.standard_normal((40, 40)) + 2 * np.eye(40)).astype(dt)
    b_np = RNG.standard_normal((40, 24)).astype(dt)
    with core.offload("cpu") as rt:
        a, b = host_array(a_np), host_array(b_np)
        if routine == "strsm":
            got = blas.trsm(jnp.tril(a), b, side="L", uplo="L")
            want = np.linalg.solve(np.tril(a_np), b_np)
        elif routine == "ssyrk":
            got = jnp.tril(blas.syrk(b))
            want = np.tril(b_np @ b_np.T)
        else:
            got, want = jnp.matmul(a, b), a_np @ b_np
        got = np.asarray(got)
    assert rt.stats.per_routine[routine].on_host == 1
    assert rt.stats.platforms == {"host": {"cpu"}}
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_dfu_migrates_once_and_reuses(monkeypatch):
    # asserts uncapped move-once semantics: pin the cap off so the CI
    # eviction-stress job's global SCILIB_DEVICE_BYTES can't evict here
    monkeypatch.delenv("SCILIB_DEVICE_BYTES", raising=False)
    with core.offload("dfu", threshold=100) as rt:
        a = host_array(RNG.standard_normal((512, 512)).astype("float32"))
        b = host_array(RNG.standard_normal((512, 512)).astype("float32"))
        c = jnp.matmul(a, b)
        for _ in range(5):
            c = jnp.matmul(a, c)
        st = rt.stats.per_routine["sgemm"]
        assert st.offloaded == 6
        # a and b moved once; a hit 5 more times, outputs chain for free
        assert st.bytes_in == a.nbytes + b.nbytes
        assert st.cache_hits >= 5
    assert memspace.tier_of(c) == memspace.DEVICE


def test_dfu_registry_never_keeps_a_buffer_alive(monkeypatch):
    """Registering a device-born output, or an operand already on the
    device, records residency without holding the buffer: a dropped
    intermediate is freed and leaves the registry, so a long chain of
    calls (a blocked LU's trailing updates) does not fill HBM."""
    monkeypatch.delenv("SCILIB_DEVICE_BYTES", raising=False)
    with core.offload("dfu", threshold=100) as rt:
        a = host_array(RNG.standard_normal((512, 512)).astype("float32"))
        c = jnp.matmul(a, a)
        d = jnp.matmul(c, a)              # c is registered as an operand
        rt.sync()
        held = rt.placements.resident_bytes
        assert held == a.nbytes + c.nbytes + d.nbytes
        dead = weakref.ref(c)
        del c
        gc.collect()
        assert dead() is None
        assert rt.placements.resident_bytes == held - d.nbytes
        hits = rt.stats.per_routine["sgemm"].cache_hits
        jnp.matmul(d, a)                  # live buffers still hit
        assert rt.stats.per_routine["sgemm"].cache_hits == hits + 2


def _quickstart():
    path = (pathlib.Path(__file__).resolve().parents[1] / "examples"
            / "quickstart.py")
    spec = importlib.util.spec_from_file_location("quickstart", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("policy", ["cpu", "memcopy", "dfu"])
def test_quickstart_application_code_on_real_tiers(policy):
    """The quickstart's unmodified application code over the backend's
    real memory tiers: no op mixes placements, the results match plain
    execution, and user code keeps computing on every array handed back
    and on the host inputs."""
    a_np = RNG.standard_normal((384, 384)).astype("float32")
    b_np = RNG.standard_normal((384, 384)).astype("float32")
    a, b = host_array(a_np), host_array(b_np)
    space = memspace.active()
    assert space.host != space.device
    assert memspace.tier_of(a) == memspace.HOST
    app = _quickstart().application_code
    with repro.session(OffloadConfig(policy=policy,
                                     threshold=100.0)) as s:
        outs = app(a, b)
        stats = s.stats
    want = {"cpu": {"host": {"cpu"}}}.get(policy)
    if want is not None:
        assert stats.platforms == want
    else:
        assert stats.platforms["offload"] == {jax.default_backend()}
    for got, ref in zip(outs, app(jnp.asarray(a_np), jnp.asarray(b_np))):
        np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)
        # user code computing on the result, and on it with a host input
        assert np.isfinite(float(jnp.sum(got * 2.0 + a[0, 0])))
    assert np.isfinite(float(jnp.sum(a @ b[:, :8])))


def test_memcopy_roundtrips_every_call():
    with core.offload("memcopy", threshold=100) as rt:
        a = host_array(RNG.standard_normal((512, 512)).astype("float32"))
        b = host_array(RNG.standard_normal((512, 512)).astype("float32"))
        out = None
        for _ in range(3):
            out = jnp.matmul(a, b)
        st = rt.stats.per_routine["sgemm"]
        assert st.bytes_in == 3 * (a.nbytes + b.nbytes)
        assert st.bytes_out == 3 * out.nbytes
    assert memspace.tier_of(out) == memspace.HOST


def test_policies_numerically_identical():
    a_np = RNG.standard_normal((300, 300)).astype("float32")
    b_np = RNG.standard_normal((300, 300)).astype("float32")
    outs = {}
    for pol in ("cpu", "memcopy", "counter", "dfu", "pinned"):
        with core.offload(pol, threshold=100):
            a, b = host_array(a_np), host_array(b_np)
            outs[pol] = np.asarray(jnp.matmul(a, b))
    for pol, out in outs.items():
        np.testing.assert_allclose(out, outs["cpu"], rtol=1e-5,
                                   atol=1e-5, err_msg=pol)


def test_einsum_interception_transposes():
    with core.offload("dfu", threshold=10) as rt:
        a = jnp.asarray(RNG.standard_normal((128, 64)), jnp.float32)
        b = jnp.asarray(RNG.standard_normal((128, 96)), jnp.float32)
        out = jnp.einsum("ji,jk->ik", a, b)
        assert rt.stats.per_routine["sgemm"].calls == 1
    np.testing.assert_allclose(out, np.asarray(a).T @ np.asarray(b),
                               rtol=1e-4, atol=1e-4)


def test_interception_restores_symbols():
    orig = jnp.matmul
    with core.offload("dfu"):
        assert jnp.matmul is not orig
    assert jnp.matmul is orig


def test_jit_tracing_passes_through():
    with core.offload("dfu", threshold=10) as rt:
        @jax.jit
        def f(x):
            return jnp.matmul(x, x)

        x = jnp.asarray(RNG.standard_normal((64, 64)), jnp.float32)
        f(x)
        # traced calls pass through to the original symbol: they are
        # counted as uninstrumented, never as offloaded BLAS calls
        assert "sgemm" not in rt.stats.per_routine
        assert rt.stats.uninstrumented_calls >= 1


def test_trace_recorded_and_replayable():
    from repro.memtier import GH200, replay_trace
    with core.offload("dfu", threshold=100) as rt:
        a = host_array(RNG.standard_normal((512, 512)).astype("float32"))
        for _ in range(4):
            a_out = jnp.matmul(a, a)
        trace = rt.trace
    assert len(trace) == 4
    reports = replay_trace(trace, spec=GH200, policies=("cpu", "dfu"))
    assert reports["dfu"].total_s < reports["cpu"].total_s
