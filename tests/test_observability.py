"""The runtime's own observability: link counters per direction, the
dispatch stage seconds, the flat counter dict, the report's link line,
and the garbage-collection span hook."""
import gc

import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import blas, lapack, memspace
from repro.core import trace as trc
from repro.core.config import OffloadConfig
from repro.core.policy import host_array
from repro.solvers import drivers

RNG = np.random.default_rng(11)


def _f32(shape):
    return RNG.standard_normal(shape).astype(np.float32)


def _link(stats):
    return (stats.link_h2d_bytes, stats.link_d2h_bytes)


def test_dfu_move_is_counted_once_as_h2d():
    with repro.session(OffloadConfig(threshold=8)) as s:
        a = host_array(_f32((128, 128)))      # numpy -> HOST: no link
        assert _link(s.stats) == (0, 0)
        blas.gemm(a, a)
        blas.gemm(a, a)                       # resident: no move
        s.sync()
        assert s.stats.link_h2d_bytes == a.nbytes
        assert s.stats.link_h2d_bytes == s.stats.total_moved_bytes
        assert s.stats.link_d2h_bytes == 0
        assert s.stats.link_h2d_seconds > 0


def test_memcopy_counts_both_directions():
    with repro.session(OffloadConfig(policy="memcopy", threshold=8)) as s:
        a = host_array(_f32((64, 64)))
        out = blas.gemm(a, a)
        s.sync()
        assert memspace.tier_of(out) == memspace.HOST
        st = s.stats.per_routine["sgemm"]
        assert s.stats.link_h2d_bytes == st.bytes_in
        assert s.stats.link_d2h_bytes == st.bytes_out == out.nbytes


def test_noop_puts_and_device_copies_are_not_link_moves():
    with repro.session(OffloadConfig(threshold=8)) as s:
        d = jnp.asarray(_f32((32, 32)))         # born on the device tier
        assert memspace.put(d, memspace.DEVICE) is d
        memspace.put_block(d, 0)                # device -> device tier
        assert _link(s.stats) == (0, 0)
        h = host_array(_f32((32, 32)))
        memspace.put_block(h, 0)
        assert _link(s.stats) == (h.nbytes, 0)


def test_moves_outside_a_session_are_not_counted():
    h = host_array(_f32((16, 16)))
    memspace.put(h, memspace.DEVICE)
    with repro.session(OffloadConfig(threshold=8)) as s:
        assert _link(s.stats) == (0, 0)


@pytest.mark.parametrize("m,n,nb", [(96, 96, 32), (80, 64, 24)])
def test_getrf_counts_its_panel_round_trips(m, n, nb):
    """A device-resident factor's panels are factored on the device and
    stay there: the link counters see the BLAS rows' bytes and each
    panel's int32 pivots coming to the host, no panel bytes, and every
    panel counts as an offloaded getf2."""
    a = jnp.asarray(_f32((m, n)) + 4 * np.eye(m, n, dtype=np.float32))
    assert memspace.tier_of(a) == memspace.DEVICE
    with repro.session(OffloadConfig(threshold=1, lapack=True)) as s:
        before = s.stats.flat()
        lu, piv = drivers.getrf(a, nb=nb)
        s.sync()
        after = s.stats.flat()
    diff = {k: after[k] - before.get(k, 0) for k in after}
    blas_bytes = sum(v for k, v in diff.items()
                     if k.endswith((".bytes_in", ".bytes_out")))
    pivots = 4 * min(m, n)
    assert diff["link_h2d_bytes"] + diff["link_d2h_bytes"] == (
        blas_bytes + pivots)
    panels = len(range(0, min(m, n), nb))
    assert diff["sgetf2.calls"] == diff["sgetf2.offloaded"] == panels
    assert diff["sgetf2.on_host"] == 0
    ref_lu, ref_piv = lapack.getrf(a, nb=nb)
    np.testing.assert_array_equal(np.asarray(piv), np.asarray(ref_piv))
    np.testing.assert_allclose(np.asarray(lu), np.asarray(ref_lu),
                               rtol=1e-6, atol=1e-6)


def test_stage_seconds():
    with repro.session(OffloadConfig(threshold=8)) as s:
        a = host_array(_f32((128, 128)))
        for _ in range(3):
            blas.gemm(a, a)
        blas.gemm(host_array(_f32((4, 4))), host_array(_f32((4, 4))))
        s.sync()
        for r in s.stats.per_routine.values():
            assert r.decide_seconds > 0
            assert 0 <= r.place_seconds <= r.seconds
        for c in s.trace.calls:
            assert c.decide_seconds > 0
            assert 0 <= c.place_seconds <= c.seconds
        st = s.stats.per_routine["sgemm"]
        assert st.decide_seconds == pytest.approx(
            sum(c.decide_seconds for c in s.trace.calls))
        # only the offloaded calls place anything
        assert [c.place_seconds > 0 for c in s.trace.calls] == \
            [True] * 3 + [False]


def test_flat_counters_and_report_link_line():
    with repro.session(OffloadConfig(threshold=8, lapack=True)) as s:
        a = host_array(_f32((64, 64)))
        blas.gemm(a, a)
        drivers.getrf(jnp.asarray(_f32((48, 48))), nb=16)
        s.sync()
        stats = s.stats
        flat = stats.flat()
        rep = stats.report()
    assert flat["sgemm.calls"] == stats.per_routine["sgemm"].calls > 1
    assert flat["sgemm.bytes_in"] == a.nbytes
    assert flat["link_h2d_bytes"] == stats.link_h2d_bytes
    assert flat["solver.getrf.spans"] == 1
    assert all(isinstance(v, (int, float)) for v in flat.values())
    assert not any(k in flat for k in ("callsites", "breaker",
                                       "platforms"))
    link = [ln for ln in rep.splitlines() if ln.startswith("link: ")]
    assert len(link) == 1
    assert "h2d" in link[0] and "d2h" in link[0] and "GB" in link[0]


def test_gc_span_hook_is_refcounted_by_sessions():
    # a session another test left open keeps the hook installed
    before = gc.callbacks.count(trc._on_gc)
    with repro.session(OffloadConfig()):
        assert gc.callbacks.count(trc._on_gc) == 1
        with repro.session(OffloadConfig()):
            assert gc.callbacks.count(trc._on_gc) == 1
            gc.collect()
        assert gc.callbacks.count(trc._on_gc) == 1
    assert gc.callbacks.count(trc._on_gc) == before
    assert not trc._GC_OPEN


def test_span_names_share_one_prefix():
    assert len(set(trc.SPANS)) == len(trc.SPANS)
    assert all(n.startswith("scilib.") for n in trc.SPANS)
    assert set(trc.SOLVER_SPANS.values()) <= set(trc.SPANS)
