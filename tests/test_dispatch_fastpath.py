"""The fast dispatch path: memspace tier mapping, dispatch cache,
byte-capped LRU placement registry, async-vs-sync equivalence."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as core
from repro.core import blas, memspace
from repro.core import runtime as rtm
from repro.core import threshold as thr
from repro.core.policy import host_array

RNG = np.random.default_rng(7)


def _f32(shape):
    return RNG.standard_normal(shape).astype("float32")


# --------------------------------------------------------------------- #
# memspace tier mapping                                                  #
# --------------------------------------------------------------------- #
def test_memspace_probe_matches_backend():
    ms = memspace.probe()
    import jax
    kinds = [m.kind for m in jax.devices()[0].addressable_memories()]
    assert ms.device_kind in kinds
    assert ms.host_kind in kinds
    # the installed backend expresses two real tiers
    assert ms.host != ms.device and ms.host_kind != ms.device_kind


def test_probe_refuses_backend_without_two_placements(monkeypatch):
    """No simulated fallback: a CPU device without an unpinned_host kind,
    or an accelerator with no CPU backend beside it, is refused."""
    import types

    import jax

    one_kind = types.SimpleNamespace(
        platform="cpu",
        addressable_memories=lambda: [types.SimpleNamespace(kind="device")])
    with pytest.raises(RuntimeError, match="unpinned_host"):
        memspace.probe(device=one_kind, n_devices=1)

    def no_cpu(backend=None):
        raise RuntimeError(f"Unknown backend {backend}")

    monkeypatch.setattr(jax, "devices", no_cpu)
    with pytest.raises(RuntimeError, match="no CPU backend"):
        memspace.probe(device=types.SimpleNamespace(platform="tpu"),
                       n_devices=1)


def test_simulated_tiers_track_identity_and_movement():
    x = host_array(_f32((32, 32)))
    assert memspace.tier_of(x) == memspace.HOST
    y = memspace.put(x, memspace.DEVICE)
    assert memspace.tier_of(y) == memspace.DEVICE
    # the source keeps its own tier: Mem-Copy round trips stay observable
    assert memspace.tier_of(x) == memspace.HOST
    assert y is not x             # a real transfer, to a new buffer
    # same-tier put is the identity (no spurious copies on the fast path)
    assert memspace.put(y, memspace.DEVICE) is y
    # untagged fresh arrays behave device-resident, like on accelerators
    assert memspace.tier_of(jnp.ones((4, 4))) == memspace.DEVICE


def test_single_kind_backend_runs_all_policies():
    """Every policy runs over the backend's real tiers and counts its
    movement."""
    a_np, b_np = _f32((300, 300)), _f32((300, 300))
    for pol in ("cpu", "memcopy", "counter", "dfu", "pinned"):
        with core.offload(pol, threshold=100) as rt:
            a, b = host_array(a_np), host_array(b_np)
            out = jnp.matmul(a, b)
        assert np.isfinite(np.asarray(out)).all(), pol
        st = rt.stats.per_routine["sgemm"]
        assert st.calls == 1, pol
        if pol in ("memcopy", "dfu", "pinned"):
            assert st.bytes_in == a.nbytes + b.nbytes, pol


# --------------------------------------------------------------------- #
# dispatch cache                                                         #
# --------------------------------------------------------------------- #
def test_dispatch_cache_one_threshold_derivation(monkeypatch):
    calls = []
    real = thr.should_offload

    def counting(*a, **kw):
        calls.append(a)
        return real(*a, **kw)

    monkeypatch.setattr(thr, "should_offload", counting)
    with core.offload("dfu", threshold=100) as rt:
        a = host_array(_f32((256, 256)))
        b = host_array(_f32((256, 256)))
        for _ in range(4):
            jnp.matmul(a, b)
        st = rt.stats.per_routine["sgemm"]
        assert len(calls) == 1          # derived once, cached thereafter
        assert st.dispatch_misses == 1
        assert st.dispatch_hits == 3
        # a different call-site shape is a fresh decision
        c = host_array(_f32((128, 256)))
        jnp.matmul(c, b)
        assert len(calls) == 2


def test_dispatch_cache_reuses_scalars_and_kernels():
    blas.clear_caches()
    with core.offload("dfu", threshold=100):
        a = host_array(_f32((256, 256)))
        blas.gemm(a, a, alpha=2.0)
        n_scalars = len(blas._SCALARS)
        n_bound = len(blas._BOUND)
        blas.gemm(a, a, alpha=2.0)
        # steady state: no new device scalars, no new bound kernels
        assert len(blas._SCALARS) == n_scalars
        assert len(blas._BOUND) == n_bound
        assert n_bound >= 1


def test_dispatch_cache_env_disable(monkeypatch):
    monkeypatch.setenv("SCILIB_DISPATCH_CACHE", "0")
    with core.offload("dfu", threshold=100) as rt:
        a = host_array(_f32((256, 256)))
        jnp.matmul(a, a)
        jnp.matmul(a, a)
        st = rt.stats.per_routine["sgemm"]
        assert st.dispatch_hits == 0
        assert st.dispatch_misses == 2
    monkeypatch.setenv("SCILIB_DISPATCH_CACHE", "1")
    core.install("dfu")  # refresh the blas-level flag
    core.uninstall()


def test_unhashable_alpha_still_correct():
    """Array-valued alpha can't key the cache; the call must fall back to
    per-call binding, not crash or corrupt the cache."""
    with core.offload("dfu", threshold=100):
        a = host_array(_f32((128, 128)))
        al = jnp.asarray(3.0, jnp.float32)
        out = blas.gemm(a, a, alpha=al)
    want = 3.0 * (np.asarray(a) @ np.asarray(a))
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4,
                               atol=1e-4)


# --------------------------------------------------------------------- #
# byte-capped LRU placement registry                                     #
# --------------------------------------------------------------------- #
def test_lru_eviction_at_byte_cap():
    nbytes = 256 * 256 * 4
    rt = rtm.install("dfu", threshold=10, record_trace=False,
                     device_bytes=2 * nbytes)
    try:
        xs = [host_array(_f32((256, 256))) for _ in range(3)]
        outs = [blas.gemm(x, x) for x in xs]
        assert rt.stats.evictions >= 1
        assert rt.stats.evicted_bytes >= nbytes
        assert rt.resident_bytes() <= 2 * nbytes
        st = rt.stats.per_routine["sgemm"]
        # the first operand was evicted; re-using it re-migrates (pays
        # bytes again) instead of silently reading a stale placement
        before = st.bytes_in
        blas.gemm(xs[0], xs[0])
        assert st.bytes_in == before + xs[0].nbytes
        del outs
    finally:
        rtm.uninstall()


def test_lru_cap_env_knob(monkeypatch):
    monkeypatch.setenv("SCILIB_DEVICE_BYTES", str(512 * 1024))
    rt = rtm.install("dfu", threshold=10, record_trace=False)
    try:
        assert rt.device_bytes_cap == 512 * 1024
    finally:
        rtm.uninstall()


def test_no_cap_means_no_eviction():
    rt = rtm.install("dfu", threshold=10, record_trace=False)
    try:
        for _ in range(4):
            blas.gemm(host_array(_f32((128, 128))),
                      host_array(_f32((128, 128))))
        assert rt.stats.evictions == 0
    finally:
        rtm.uninstall()


# --------------------------------------------------------------------- #
# async execution                                                        #
# --------------------------------------------------------------------- #
def test_async_vs_sync_numerically_identical(monkeypatch):
    a_np, b_np = _f32((300, 300)), _f32((300, 300))
    outs = {}
    for sync in ("", "1"):
        monkeypatch.setenv("SCILIB_SYNC", sync)
        for pol in ("cpu", "memcopy", "counter", "dfu", "pinned"):
            with core.offload(pol, threshold=100):
                a, b = host_array(a_np), host_array(b_np)
                outs[(pol, sync)] = np.asarray(jnp.matmul(a, b))
    ref = outs[("cpu", "1")]
    for key, out in outs.items():
        np.testing.assert_array_equal(out, ref, err_msg=str(key))


def test_sync_drains_pending():
    rt = rtm.install("dfu", threshold=10, record_trace=False)
    try:
        assert not rt.sync_mode
        a = host_array(_f32((256, 256)))
        blas.gemm(a, a)
        assert len(rt._pending) == 1
        rt.sync()
        assert len(rt._pending) == 0
    finally:
        rtm.uninstall()


def test_sync_mode_env(monkeypatch):
    monkeypatch.setenv("SCILIB_SYNC", "1")
    rt = rtm.install("dfu", threshold=10, record_trace=False)
    try:
        assert rt.sync_mode
        a = host_array(_f32((256, 256)))
        blas.gemm(a, a)
        assert len(rt._pending) == 0    # sync mode never defers
    finally:
        rtm.uninstall()


# --------------------------------------------------------------------- #
# threshold backend detection + batched einsum interception              #
# --------------------------------------------------------------------- #
def test_threshold_backend_detection():
    assert thr.detect_device_key("tpu", "TPU v5e") == "tpu-v5e"
    assert thr.detect_device_key("tpu", "TPU v4") == "tpu"
    assert thr.detect_device_key("gpu", "NVIDIA GH200 480GB") == "gh200"
    assert thr.detect_device_key("gpu", "NVIDIA H100") == "gpu"
    assert thr.detect_device_key("cpu", "cpu") == "cpu"
    assert thr.DEVICE_DEFAULTS["tpu-v5e"] == 384.0
    assert thr.DEVICE_DEFAULTS[thr.detect_device_key()] == \
        thr.default_threshold()


def test_threshold_env_override_still_wins(monkeypatch):
    monkeypatch.setenv("SCILIB_THRESHOLD", "123.5")
    rt = rtm.install("dfu", record_trace=False)
    try:
        assert rt.threshold == 123.5
    finally:
        rtm.uninstall()


@pytest.mark.parametrize("spec,ta,tb", [
    ("bij,bjk->bik", "N", "N"),
    ("bji,bjk->bik", "T", "N"),
    ("bij,bkj->bik", "N", "T"),
    ("bji,bkj->bik", "T", "T"),
])
def test_batched_einsum_intercepted(spec, ta, tb):
    sa = (3, 48, 32) if ta == "N" else (3, 32, 48)
    sb = (3, 32, 24) if tb == "N" else (3, 24, 32)
    a = jnp.asarray(_f32(sa))
    b = jnp.asarray(_f32(sb))
    with core.offload("dfu", threshold=10) as rt:
        out = jnp.einsum(spec, a, b)
        st = rt.stats.per_routine["sgemm"]
        assert st.calls == 1
    want = np.einsum(spec, np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4,
                               atol=1e-4)


def test_matmul_benign_kwargs_still_offload():
    """precision=None / preferred_element_type == operand dtype are
    no-ops; NumPy-style callers passing them must still hit the offload
    path instead of bailing to the original symbol."""
    a_np = _f32((256, 256))
    with core.offload("dfu", threshold=100) as rt:
        a = host_array(a_np)
        out1 = jnp.matmul(a, a, precision=None)
        out2 = jnp.matmul(a, a, preferred_element_type=jnp.float32)
        out3 = jnp.dot(a, a, precision=None,
                       preferred_element_type=jnp.float32)
        # explicit None defaults (what NumPy-style wrappers forward)
        jnp.matmul(a, a, precision=None, preferred_element_type=None)
        st = rt.stats.per_routine["sgemm"]
        assert st.calls == 4             # all four routed to offload
        # (uninstrumented may be nonzero from jit-compile pass-throughs
        # of the kernels themselves — count deltas, not absolutes)
        before = rt.stats.uninstrumented_calls
        # a genuine accumulation-type change is NOT benign: fall through
        out4 = jnp.matmul(a, a, preferred_element_type=jnp.float64)
        assert st.calls == 4
        assert rt.stats.uninstrumented_calls == before + 1
    want = np.asarray(a) @ np.asarray(a)
    # out4 went through the original symbol (x64 may be disabled, so
    # dtype promotion is backend-dependent; the routing is what matters)
    for out in (out1, out2, out3, out4):
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4,
                                   atol=1e-4)


def test_non_gemm_batched_einsum_falls_through():
    a = jnp.asarray(_f32((3, 8, 8)))
    with core.offload("dfu", threshold=10) as rt:
        jnp.einsum("bii->b", a)             # trace: not a gemm
        jnp.einsum("bij,bij->b", a, a)      # inner product: not a gemm
        assert "sgemm" not in rt.stats.per_routine
        assert rt.stats.uninstrumented_calls == 2


def test_mismatched_batch_dims_fall_through():
    a = jnp.asarray(_f32((2, 8, 8)))
    b = jnp.asarray(_f32((1, 8, 8)))       # broadcasting batch: fall back
    with core.offload("dfu", threshold=10) as rt:
        out = jnp.einsum("bij,bjk->bik", a, b)
        assert "sgemm" not in rt.stats.per_routine
    want = np.einsum("bij,bjk->bik", np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4,
                               atol=1e-4)
