"""Multi-device sharded dispatch: SCILIB_DEVICES simulated tiers, tile
decomposition correctness vs the single-device path, round-robin-with-
affinity scheduling, per-device byte-cap eviction, trace + simulator
coverage of the device dimension."""
import contextlib
import os

import numpy as np
import pytest

import repro.core as core
from repro.core import blas, memspace
from repro.core import runtime as rtm
from repro.core.policy import host_array
from repro.core.trace import Trace
from repro.memtier.simulator import MemTierSimulator

RNG = np.random.default_rng(11)


def _mat(n, dtype="float32", m=None):
    m = n if m is None else m
    x = RNG.standard_normal((m, n))
    if np.dtype(dtype).kind == "c":
        x = x + 1j * RNG.standard_normal((m, n))
    return x.astype(dtype)


@contextlib.contextmanager
def devices(n):
    """Force an n-tier simulated device layout for the enclosed runtime."""
    old = os.environ.get("SCILIB_DEVICES")
    os.environ["SCILIB_DEVICES"] = str(n)
    memspace.install()              # re-probe the tier layout now
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("SCILIB_DEVICES", None)
        else:
            os.environ["SCILIB_DEVICES"] = old
        memspace.install()          # re-probe with the restored env


# --------------------------------------------------------------------- #
# tier enumeration                                                       #
# --------------------------------------------------------------------- #
def test_scilib_devices_enumerates_simulated_tiers():
    with devices(4):
        rt = rtm.install("dfu", record_trace=False)
        try:
            assert memspace.active().n_devices == 4
            assert rt.n_devices == 4
        finally:
            rtm.uninstall()
    rt = rtm.install("dfu", record_trace=False)
    try:
        assert rt.n_devices == len(__import__("jax").devices())
    finally:
        rtm.uninstall()


def test_put_block_tags_device_index():
    with devices(3):
        x = host_array(_mat(64))
        y = memspace.put_block(x, 2)
        assert memspace.tier_of(y) == memspace.DEVICE
        assert memspace.device_of(y) == 2
        assert memspace.device_of(x) is None      # host-resident source
        assert memspace.put_block(y, 2) is y      # same-home is identity


def test_sharded_result_mixes_with_host_data():
    """A sharded call hands its result back uncommitted, like a whole-call
    DEVICE placement: user code can combine it with a host_array (on a
    TPU, committed tiles would pin it to one chip and such an op would
    be refused)."""
    import jax.numpy as jnp
    a_np, b_np = _mat(512), _mat(512)
    with devices(4), core.offload("dfu", threshold=50) as rt:
        a, b = host_array(a_np), host_array(b_np)
        c = jnp.matmul(a, b)
        assert rt.stats.per_routine["sgemm"].sharded == 1
        assert not c.committed
        d = np.asarray(c + a)
    np.testing.assert_allclose(d, a_np @ b_np + a_np, rtol=1e-4, atol=1e-3)


# --------------------------------------------------------------------- #
# tile decomposition correctness vs the single-device path               #
# --------------------------------------------------------------------- #
def _single_then_sharded(fn, n_dev=4):
    """Run fn() under a 1-device runtime and an n-device runtime."""
    with core.offload("dfu", threshold=50):
        ref = np.asarray(fn())
    with devices(n_dev):
        with core.offload("dfu", threshold=50) as rt:
            got = np.asarray(fn())
    return ref, got, rt


@pytest.mark.parametrize("dtype", ["float32", "complex64"])
@pytest.mark.parametrize("trans_a,trans_b", [("N", "N"), ("T", "N"),
                                             ("N", "T")])
def test_gemm_tiles_match_single_device(dtype, trans_a, trans_b):
    a_np, b_np, c_np = _mat(384, dtype), _mat(384, dtype), _mat(384, dtype)

    def fn():
        return blas.gemm(host_array(a_np), host_array(b_np),
                         host_array(c_np), alpha=1.5, beta=0.5,
                         trans_a=trans_a, trans_b=trans_b)

    ref, got, rt = _single_then_sharded(fn)
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)
    pre = "s" if dtype == "float32" else "c"
    st = rt.stats.per_routine[pre + "gemm"]
    assert st.sharded == 1 and st.tiles >= 4
    assert len(rt.stats.per_device) == 4
    assert all(d.tiles >= 1 for d in rt.stats.per_device.values())
    assert all(d.moved_bytes > 0 for d in rt.stats.per_device.values())


@pytest.mark.parametrize("dtype,conj", [("float32", False),
                                        ("complex64", False),
                                        ("complex64", True)])
@pytest.mark.parametrize("uplo,trans", [("L", "N"), ("U", "T")])
def test_syrk_tiles_match_single_device(dtype, conj, uplo, trans):
    a_np, c_np = _mat(360, dtype), _mat(360, dtype)
    routine = blas.herk if conj else blas.syrk

    def fn():
        return routine(host_array(a_np), host_array(c_np), uplo=uplo,
                       trans=trans, alpha=1.25, beta=0.75)

    ref, got, rt = _single_then_sharded(fn)
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)
    pre = "s" if dtype == "float32" else "c"
    st = rt.stats.per_routine[pre + ("herk" if conj else "syrk")]
    assert st.sharded == 1 and st.tiles >= 4   # g=3: 6 stored-tri tiles


@pytest.mark.parametrize("dtype,conj", [("float32", False),
                                        ("complex64", False),
                                        ("complex64", True)])
@pytest.mark.parametrize("uplo,trans", [("L", "N"), ("U", "T")])
def test_syr2k_tiles_match_single_device(dtype, conj, uplo, trans):
    """syr2k/her2k ride the syrk triangle grid (the last level-3 gap in
    the tile scheduler): sharded result must match the single-device
    path bit-for-bit in structure and within tolerance in values."""
    a_np, b_np, c_np = _mat(360, dtype), _mat(360, dtype), _mat(360, dtype)
    routine = blas.her2k if conj else blas.syr2k

    def fn():
        return routine(host_array(a_np), host_array(b_np),
                       host_array(c_np), uplo=uplo, trans=trans,
                       alpha=1.25, beta=0.75)

    ref, got, rt = _single_then_sharded(fn)
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)
    pre = "s" if dtype == "float32" else "c"
    st = rt.stats.per_routine[pre + ("her2k" if conj else "syr2k")]
    assert st.sharded == 1 and st.tiles >= 4   # g=3: 6 stored-tri tiles
    assert len(rt.stats.per_device) == 4


def test_syr2k_no_c_tiles_match_single_device():
    a_np, b_np = _mat(360), _mat(360)

    def fn():
        return blas.syr2k(host_array(a_np), host_array(b_np), uplo="L",
                          alpha=0.5)

    ref, got, rt = _single_then_sharded(fn)
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)
    assert rt.stats.per_routine["ssyr2k"].sharded == 1


@pytest.mark.parametrize("dtype", ["float32", "complex64"])
@pytest.mark.parametrize("side", ["L", "R"])
def test_trsm_tiles_match_single_device(dtype, side):
    n = 384
    l_np = np.tril(_mat(n, dtype)) + n * np.eye(n, dtype=dtype)
    b_np = _mat(n, dtype)

    def fn():
        return blas.trsm(host_array(l_np), host_array(b_np), side=side,
                         uplo="L", alpha=2.0)

    ref, got, rt = _single_then_sharded(fn)
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)
    pre = "s" if dtype == "float32" else "c"
    st = rt.stats.per_routine[pre + "trsm"]
    assert st.sharded == 1 and st.tiles == 4   # 4 independent panels


def test_symm_trmm_tiles_match_single_device():
    a_np, b_np = _mat(384), _mat(384)

    def fn_symm():
        return blas.symm(host_array(a_np), host_array(b_np), side="L",
                         uplo="U", alpha=1.5)

    def fn_trmm():
        return blas.trmm(host_array(np.tril(a_np)), host_array(b_np),
                         side="R", uplo="L", alpha=0.5)

    for fn, name in ((fn_symm, "ssymm"), (fn_trmm, "strmm")):
        ref, got, rt = _single_then_sharded(fn)
        np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)
        assert rt.stats.per_routine[name].sharded == 1


def test_small_matrix_falls_back_to_single_device():
    """Below SCILIB_TILE_MIN per tile edge the plan builder declines and
    the call takes the unsharded offload path."""
    a_np = _mat(96)
    with devices(4):
        with core.offload("dfu", threshold=10) as rt:
            out = blas.gemm(host_array(a_np), host_array(a_np))
    st = rt.stats.per_routine["sgemm"]
    assert st.offloaded == 1 and st.sharded == 0
    np.testing.assert_allclose(np.asarray(out), a_np @ a_np,
                               rtol=2e-3, atol=2e-3)


def test_batched_calls_not_sharded():
    a_np = RNG.standard_normal((3, 256, 256)).astype("float32")
    with devices(4):
        with core.offload("dfu", threshold=10) as rt:
            blas.gemm(a_np, a_np)
    st = rt.stats.per_routine["sgemm"]
    assert st.offloaded == 1 and st.sharded == 0


def test_singleton_batch_axis_not_sharded():
    """ndim==3 with batch 1 uses the batched kernels: 2-D tile coords
    must not address it (this crashed before the ndim gate)."""
    a_np = RNG.standard_normal((1, 256, 256)).astype("float32")
    with devices(2):
        with core.offload("dfu", threshold=10) as rt:
            out = blas.gemm(a_np, a_np)
    st = rt.stats.per_routine["sgemm"]
    assert st.offloaded == 1 and st.sharded == 0
    np.testing.assert_allclose(np.asarray(out), a_np @ a_np,
                               rtol=2e-3, atol=2e-3)


def test_counter_policy_never_sharded():
    """R1-R4 are per-operand host-vs-device rules: sharding would turn
    the access-counter model into DFU, so it stays single-device."""
    a_np = _mat(512)
    with devices(4):
        with core.offload("counter", threshold=50) as rt:
            blas.gemm(a_np, a_np, a_np)   # written C qualifies nothing
    st = rt.stats.per_routine["sgemm"]
    assert st.offloaded == 1 and st.sharded == 0
    assert rt.stats.per_device == {}


# --------------------------------------------------------------------- #
# round-robin with affinity                                              #
# --------------------------------------------------------------------- #
def test_first_call_spreads_round_robin():
    with devices(4):
        with core.offload("dfu", threshold=50) as rt:
            a = host_array(_mat(512))
            blas.gemm(a, a)
    assert sorted(rt.stats.per_device) == [0, 1, 2, 3]
    assert [d.tiles for _, d in sorted(rt.stats.per_device.items())] == \
        [1, 1, 1, 1]
    assert rt.trace.calls[0].devices == (0, 1, 2, 3)


def test_affinity_reuses_resident_blocks():
    with devices(4):
        with core.offload("dfu", threshold=50) as rt:
            a, b = host_array(_mat(512)), host_array(_mat(512))
            blas.gemm(a, b)
            st = rt.stats.per_routine["sgemm"]
            moved_first = st.bytes_in
            blas.gemm(a, b)
            # every block of every tile was already resident on the tile's
            # device: zero new movement, one schedule per prior placement
            assert st.bytes_in == moved_first
            assert all(d.affinity_hits >= 2
                       for d in rt.stats.per_device.values())
            # and the schedule is stable: same device per tile
            assert rt.trace.calls[0].devices == rt.trace.calls[1].devices


def test_tie_break_spreads_chained_grid():
    """Chained 2-D grids replicate A row blocks across devices; the
    scheduled-load tie-breaker must keep all devices busy rather than
    funneling each grid row onto its lowest-scoring device."""
    with devices(4):
        with core.offload("dfu", threshold=50) as rt:
            a = host_array(_mat(512).astype("float32") / 512)
            c = a
            for _ in range(4):
                c = blas.gemm(a, c)
    tiles = [d.tiles for _, d in sorted(rt.stats.per_device.items())]
    assert len(tiles) == 4
    assert all(t >= 2 for t in tiles), tiles   # 16 tiles, nobody idle


def test_memcopy_stages_every_call_round_robin():
    """Non-persistent staging: no affinity, movement every call."""
    with devices(4):
        with core.offload("memcopy", threshold=50) as rt:
            a, b = host_array(_mat(512)), host_array(_mat(512))
            blas.gemm(a, b)
            st = rt.stats.per_routine["sgemm"]
            moved_first = st.bytes_in
            blas.gemm(a, b)
    assert st.bytes_in == 2 * moved_first
    assert all(d.affinity_hits == 0 for d in rt.stats.per_device.values())
    assert st.bytes_out > 0           # gathered outputs bounce to host


# --------------------------------------------------------------------- #
# per-device byte caps                                                   #
# --------------------------------------------------------------------- #
def test_per_device_byte_cap_evicts_lru_blocks():
    cap = int(1.8e6)
    with devices(2):
        rt = rtm.install("dfu", threshold=50, record_trace=False,
                         device_bytes=cap)
        try:
            a = host_array(_mat(512).astype("float32") / 512)
            c = a
            for _ in range(8):
                c = blas.gemm(a, c)
            assert any(d.evictions > 0
                       for d in rt.stats.per_device.values())
            for dev in range(rt.n_devices):
                assert rt.device_resident_bytes(dev) <= cap
        finally:
            rtm.uninstall()


def test_no_cap_no_device_evictions():
    with devices(2):
        rt = rtm.install("dfu", threshold=50, record_trace=False)
        try:
            a = host_array(_mat(512))
            blas.gemm(a, a)
            assert all(d.evictions == 0
                       for d in rt.stats.per_device.values())
        finally:
            rtm.uninstall()


# --------------------------------------------------------------------- #
# stats report / single-device invariance                                #
# --------------------------------------------------------------------- #
def test_report_shows_per_device_counters():
    with devices(4):
        with core.offload("dfu", threshold=50) as rt:
            a = host_array(_mat(512))
            blas.gemm(a, a)
    rep = rt.stats.report()
    for frag in ("device", "dev0", "dev3", "GB moved", "affinity"):
        assert frag in rep, rep


def test_single_device_path_has_no_shard_state():
    with core.offload("dfu", threshold=50) as rt:
        a = host_array(_mat(512))
        blas.gemm(a, a)
    st = rt.stats.per_routine["sgemm"]
    assert rt.n_devices == 1
    assert st.sharded == 0 and st.tiles == 0
    assert rt.stats.per_device == {}
    assert rt.trace.calls[0].devices == ()


# --------------------------------------------------------------------- #
# trace + simulator device dimension                                     #
# --------------------------------------------------------------------- #
def test_trace_devices_roundtrip(tmp_path):
    with devices(4):
        with core.offload("dfu", threshold=50) as rt:
            a = host_array(_mat(512))
            blas.gemm(a, a)
    path = str(tmp_path / "trace.json")
    rt.trace.dump(path)
    loaded = Trace.load(path)
    assert loaded.calls[0].devices == rt.trace.calls[0].devices
    assert len(loaded.calls[0].devices) == 4


def _big_trace():
    t = Trace()
    a = t.new_buffer(4000 * 4000 * 8, "A")
    b = t.new_buffer(4000 * 4000 * 8, "B")
    c = t.new_buffer(4000 * 4000 * 8, "C")
    for _ in range(3):
        t.gemm("d", 4000, 4000, 4000, a, b, c)
    return t


def test_simulator_multidevice_dfu_scales():
    t = _big_trace()
    one = MemTierSimulator(policy="dfu", threshold=500).run(t)
    four = MemTierSimulator(policy="dfu", threshold=500,
                            n_devices=4).run(t)
    assert four.n_devices == 4
    # concurrent tiles: device BLAS time shrinks with the device count
    assert four.blas_device_s < one.blas_device_s
    assert four.total_s < one.total_s
    # each buffer still migrates exactly once, onto one device
    assert four.bytes_host_to_dev == one.bytes_host_to_dev
    assert sum(four.per_device_h2d.values()) == four.bytes_host_to_dev
    assert set(four.per_device_h2d) <= set(range(4))
    assert len(four.per_device_h2d) >= 2    # round-robin spread buffers


def test_simulator_single_device_unchanged_by_field():
    t = _big_trace()
    rep = MemTierSimulator(policy="dfu", threshold=500).run(t)
    assert rep.n_devices == 1 and rep.per_device_h2d == {}


def test_simulator_multidevice_honors_evict_lru():
    """A working set beyond one device's HBM: without evict_lru the
    overflow buffer stays remote; with it, LRU residents bounce to host
    (same contract as the single-device path)."""
    from repro.memtier.spec import GH200
    tiny = GH200.with_(device_capacity=96 << 20)     # 96 MB HBM
    t = Trace()
    bufs = [t.new_buffer(60 << 20, f"B{i}") for i in range(3)]
    for i in range(3):
        t.gemm("d", 3000, 3000, 3000, bufs[i], bufs[i],
               bufs[(i + 1) % 3])
    keep = MemTierSimulator(tiny, policy="dfu", threshold=100,
                            n_devices=2).run(t)
    evict = MemTierSimulator(tiny, policy="dfu", threshold=100,
                             n_devices=2, evict_lru=True).run(t)
    assert keep.bytes_dev_to_host == 0
    assert evict.bytes_dev_to_host > 0
    assert evict.bytes_host_to_dev > keep.bytes_host_to_dev


# --------------------------------------------------------------------- #
# mesh integration                                                       #
# --------------------------------------------------------------------- #
def test_offload_mesh_over_device_tiers():
    from repro.launch import mesh
    with devices(4):
        devs = mesh.offload_devices()
        assert len(devs) == 4          # logical tiers wrap real devices
        m = mesh.make_offload_mesh()
        assert m.axis_names == ("blas",)
        assert m.shape["blas"] >= 1
