"""LAPACK solver tier: drivers vs oracles, interception, spans,
live==replay counters, factor pinning, and default-off bit-identity."""
import json

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl
import numpy as np
import pytest
import scipy.linalg as sla

import repro
from repro.core import blas, lapack, memspace
from repro.core import runtime as rtm
from repro.core.config import OffloadConfig
from repro.core.policy import host_array
from repro.core.trace import Trace
from repro.memtier.simulator import MemTierSimulator
from repro.memtier.spec import SPECS
from repro.solvers import drivers
from repro.solvers import eigen
import repro.tools.autotune as at

RNG = np.random.default_rng(7)

DTYPES = ("float32", "float64", "complex64", "complex128")


def _tol(dtype) -> float:
    return 5e-3 if jnp.dtype(dtype).itemsize <= 8 and \
        np.finfo(np.dtype(dtype)).eps > 1e-10 else 1e-9


def _rand(shape, dtype):
    x = RNG.standard_normal(shape)
    if np.issubdtype(np.dtype(dtype), np.complexfloating):
        x = x + 1j * RNG.standard_normal(shape)
    return np.asarray(x, dtype=dtype)


def _diag_dominant(n, dtype):
    a = _rand((n, n), dtype) / n
    return np.asarray(a + np.eye(n), dtype=dtype)


def _hpd(n, dtype):
    g = _rand((n, n), dtype) / n
    return np.asarray(g @ g.conj().T + np.eye(n), dtype=dtype)


def _hermitian(n, dtype):
    g = _rand((n, n), dtype)
    return np.asarray((g + g.conj().T) / 2, dtype=dtype)


@pytest.fixture(scope="module", autouse=True)
def x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


# --------------------------------------------------------------------- #
# getrf: rectangular / partial-block regressions                         #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("shape,nb", [
    ((7, 7), 3), ((10, 6), 4), ((6, 10), 4),
    ((130, 70), 48), ((70, 130), 48), ((100, 100), 48),
])
def test_getrf_rectangular_and_partial_blocks(shape, nb):
    """Non-square inputs and ragged final blocks factor correctly:
    A[piv] == L @ U with unit-lower L of shape (m, k) and U (k, n)."""
    m, n = shape
    a = jnp.asarray(_rand(shape, "float64"))
    lu, piv = lapack.getrf(a, nb=nb)
    k = min(m, n)
    low = np.tril(np.asarray(lu)[:, :k], -1) + np.eye(m, k)
    up = np.triu(np.asarray(lu)[:k, :])
    np.testing.assert_allclose(np.asarray(a)[np.asarray(piv)],
                               low @ up, atol=1e-10)


@pytest.mark.parametrize("dtype", ("float32", "complex64"))
@pytest.mark.parametrize("tier", (memspace.HOST, memspace.DEVICE))
def test_getrf_factors_each_panel_on_the_factors_tier(monkeypatch, dtype,
                                                      tier):
    """The panel runs where the factor lives: a host factor's panels are
    host getf2 calls, a device factor's offloaded ones, each factored
    panel on the factor's tier; both give the same pivots and factors."""
    n, nb = 96, 32
    a = _rand((n, n), dtype)
    src = memspace.put(jnp.asarray(a), tier)
    assert memspace.tier_of(src) == tier
    panels = []

    def spy(panel, tier, factor=lapack._pivot_panel):
        fpanel, lpiv = factor(panel, tier)
        panels.append((memspace.tier_of(fpanel), lpiv))
        return fpanel, lpiv

    monkeypatch.setattr(lapack, "_pivot_panel", spy)
    with repro.session(OffloadConfig(threshold=1, lapack=True)) as s:
        lu, piv = drivers.getrf(src, nb=nb)
        st = s.stats.per_routine[f"{lapack._prec(dtype)}getf2"]
    count = n // nb
    assert [t for t, _ in panels] == [tier] * count
    # the matrix is random: every panel swaps rows
    assert all((lpiv != np.arange(nb)).any() for _, lpiv in panels)
    assert st.calls == count
    assert (st.offloaded, st.on_host) == (
        (count, 0) if tier == memspace.DEVICE else (0, count))
    piv, lu = np.asarray(piv), np.asarray(lu)
    low = np.tril(lu, -1) + np.eye(n)
    np.testing.assert_allclose(a[piv], low @ np.triu(lu),
                               rtol=1e-4, atol=1e-4)
    other = memspace.HOST if tier == memspace.DEVICE else memspace.DEVICE
    lu2, piv2 = lapack.getrf(memspace.put(jnp.asarray(a), other), nb=nb)
    np.testing.assert_array_equal(piv, np.asarray(piv2))
    np.testing.assert_allclose(lu, np.asarray(lu2), rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------- #
# drivers vs oracles (no runtime: plain blocked kernels)                 #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", DTYPES)
def test_gesv_oracle(dtype):
    a = _diag_dominant(96, dtype)
    b = _rand((96, 7), dtype)
    x = drivers.gesv(jnp.asarray(a), jnp.asarray(b), nb=32)
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(a, b),
                               atol=_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("uplo", ("L", "U"))
def test_potrf_potrs_oracle(dtype, uplo):
    a = _hpd(80, dtype)
    f = drivers.potrf(jnp.asarray(a), nb=32, uplo=uplo)
    fn = np.asarray(f)
    if uplo == "L":
        np.testing.assert_allclose(np.tril(fn) @ np.tril(fn).conj().T,
                                   a, atol=_tol(dtype))
    else:
        np.testing.assert_allclose(np.triu(fn).conj().T @ np.triu(fn),
                                   a, atol=_tol(dtype))
    b = _rand((80, 5), dtype)
    x = drivers.potrs(f, jnp.asarray(b), uplo=uplo)
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(a, b),
                               atol=_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
def test_syev_oracle(dtype):
    a = _hermitian(67, dtype)
    w, s = drivers.syev(jnp.asarray(a), nb=24)
    ww = sla.eigh(a, eigvals_only=True)
    np.testing.assert_allclose(np.asarray(w), ww, atol=_tol(dtype))
    # residual: A S == S diag(w), and S orthonormal
    sn, wn = np.asarray(s), np.asarray(w)
    np.testing.assert_allclose(a @ sn, sn * wn, atol=20 * _tol(dtype))
    np.testing.assert_allclose(sn.conj().T @ sn, np.eye(67),
                               atol=_tol(dtype))


def test_syev_uplo_u_ignores_lower_garbage():
    """uplo="U" reads only the upper triangle — LAPACK convention: the
    strictly-lower part may hold arbitrary values."""
    a = _hermitian(40, "complex128")
    dirty = np.array(a)
    dirty[np.tril_indices(40, -1)] = RNG.standard_normal(
        len(np.tril_indices(40, -1)[0])) * 1e3
    w, _ = drivers.syev(jnp.asarray(dirty), nb=16, uplo="U")
    np.testing.assert_allclose(np.asarray(w),
                               sla.eigh(a, eigvals_only=True), atol=1e-9)


# --------------------------------------------------------------------- #
# symbol interception (SCILIB_LAPACK)                                    #
# --------------------------------------------------------------------- #
def test_lapack_session_patches_and_restores_symbols():
    orig = (jsl.lu_factor, jsl.lu_solve, jnp.linalg.cholesky,
            jnp.linalg.solve, jsl.eigh)
    with repro.session(OffloadConfig(lapack=True, threshold=64.0)):
        assert jsl.lu_factor is not orig[0]
        assert jnp.linalg.solve is not orig[3]
    assert (jsl.lu_factor, jsl.lu_solve, jnp.linalg.cholesky,
            jnp.linalg.solve, jsl.eigh) == orig


def test_lapack_unset_touches_no_symbols():
    """The default-off guarantee starts here: SCILIB_LAPACK unset means
    these symbols are never even reassigned."""
    orig = (jsl.lu_factor, jsl.cho_solve, jnp.linalg.cholesky)
    with repro.session(OffloadConfig(threshold=64.0)):
        assert (jsl.lu_factor, jsl.cho_solve,
                jnp.linalg.cholesky) == orig


def test_reconfigure_flips_solver_patch():
    orig = jsl.lu_factor
    with repro.session(OffloadConfig(threshold=64.0)) as s:
        assert jsl.lu_factor is orig
        s.reconfigure(lapack=True)
        assert jsl.lu_factor is not orig
        s.reconfigure(lapack=False)
        assert jsl.lu_factor is orig


def test_intercepted_solve_records_span_and_is_correct():
    a = _diag_dominant(150, "complex128")
    b = _rand((150, 6), "complex128")
    with repro.session(OffloadConfig(lapack=True, threshold=32.0,
                                     lapack_nb=48)) as s:
        x = jnp.linalg.solve(host_array(jnp.asarray(a)),
                             host_array(jnp.asarray(b)))
        rt = s.runtime
        st = rt.stats.solvers["gesv"]
        assert st.spans == 1
        assert st.panel_calls == 4          # ceil(150/48) panels
        assert st.calls > st.panel_calls    # + trsms and gemms
        assert rt.trace.event_count("solver_begin") == 1
        assert rt.trace.event_count("solver_end") == 1
        assert all(c.solver == "gesv" for c in rt.trace
                   if c.solver_id)
        assert "solvers (LAPACK tier)" in rt.stats.report()
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(a, b),
                               atol=1e-9)


def test_subthreshold_solve_falls_through_native():
    a = _diag_dominant(48, "float64")
    with repro.session(OffloadConfig(lapack=True,
                                     threshold=1000.0)) as s:
        x = jnp.linalg.solve(host_array(jnp.asarray(a)),
                             host_array(jnp.asarray(_rand((48, 3),
                                                          "float64"))))
        assert not s.runtime.stats.solvers
        assert s.runtime.trace.event_count("solver_begin") == 0
    assert x.shape == (48, 3)


def test_intercepted_scipy_surface_matches_oracles():
    """cho_factor/cho_solve, solve_triangular and eigh all route
    through the tier and stay numerically faithful."""
    n = 72
    spd = _hpd(n, "float64")
    b = _rand((n, 4), "float64")
    herm = _hermitian(n, "float64")
    tri = np.tril(_rand((n, n), "float64")) + n * np.eye(n)
    with repro.session(OffloadConfig(lapack=True, threshold=32.0,
                                     lapack_nb=24)) as s:
        c = jsl.cho_factor(host_array(jnp.asarray(spd)))
        x = jsl.cho_solve(c, host_array(jnp.asarray(b)))
        y = jsl.solve_triangular(host_array(jnp.asarray(tri)),
                                 host_array(jnp.asarray(b)), lower=True)
        w = jsl.eigh(host_array(jnp.asarray(herm)), eigvals_only=True)
        names = set(s.runtime.stats.solvers)
        assert {"potrf", "potrs", "syev"} <= names
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(spd, b),
                               atol=1e-9)
    np.testing.assert_allclose(np.asarray(y), sla.solve_triangular(
        tri, b, lower=True), atol=1e-9)
    np.testing.assert_allclose(np.asarray(w),
                               sla.eigh(herm, eigvals_only=True),
                               atol=1e-8)


# --------------------------------------------------------------------- #
# spans: trace round-trip and simulator replay                           #
# --------------------------------------------------------------------- #
def _lapack_workload(sess) -> None:
    a = _diag_dominant(120, "float64")
    spd = _hpd(96, "float64")
    jnp.linalg.solve(host_array(jnp.asarray(a)),
                     host_array(jnp.asarray(_rand((120, 5), "float64"))))
    jnp.linalg.cholesky(host_array(jnp.asarray(spd)))
    jsl.eigh(host_array(jnp.asarray(_hermitian(48, "float64"))),
             eigvals_only=True)


def test_span_trace_roundtrip(tmp_path):
    path = tmp_path / "t.json"
    with repro.session(OffloadConfig(lapack=True, threshold=32.0,
                                     lapack_nb=32)) as s:
        _lapack_workload(s)
        trace = s.runtime.trace
        tagged = [(c.routine, c.solver_id) for c in trace if c.solver_id]
        begins = trace.event_count("solver_begin")
        trace.dump(str(path))
    loaded = Trace.load(str(path))
    assert [(c.routine, c.solver_id) for c in loaded
            if c.solver_id] == tagged
    assert loaded.event_count("solver_begin") == begins == 3
    assert loaded.event_count("solver_end") == 3


def test_live_equals_replay_per_solver(tmp_path):
    """The acceptance bar: simulator-replayed per-solver counters match
    the live session's exactly, span for span and call for call."""
    with repro.session(OffloadConfig(lapack=True, threshold=32.0,
                                     lapack_nb=32)) as s:
        _lapack_workload(s)
        live = {name: (st.spans, st.calls, st.panel_calls)
                for name, st in s.runtime.stats.solvers.items()}
        trace = s.runtime.trace
    sim = MemTierSimulator(SPECS["gh200"], policy="dfu", threshold=32.0)
    rep = sim.run(trace)
    replay = {name: (d["spans"], d["calls"], d["panel_calls"])
              for name, d in rep.per_solver.items()}
    assert replay == live
    assert rep.solver_spans == sum(v[0] for v in live.values()) == 3


# --------------------------------------------------------------------- #
# residency: the span pins its factor                                    #
# --------------------------------------------------------------------- #
def test_span_pins_factor_under_cap_pressure():
    n = 96
    el = 8
    rt = rtm.install("dfu", threshold=10, device_bytes=3 * n * n * el,
                     record_trace=False)
    try:
        factor = host_array(jnp.asarray(_diag_dominant(n, "float64")))
        span = rt.solver_begin("getrf", factor)
        ent = rt.placements.entry(id(factor))
        assert ent is not None and ent.pinned
        # stream a working set larger than the cap: evictions must
        # happen, but never to the pinned factor
        others = [host_array(jnp.asarray(_rand((n, n), "float64")))
                  for _ in range(6)]
        for x in others:
            blas.gemm(x, x)
        rt.sync()
        assert rt.stats.evictions > 0
        ent = rt.placements.entry(id(factor))
        assert ent is not None and ent.pinned
        rt.solver_end(span)
        ent = rt.placements.entry(id(factor))
        assert ent is not None and not ent.pinned
    finally:
        rtm.uninstall()


def test_cpu_policy_span_does_not_pin():
    rt = rtm.install(config=OffloadConfig(policy="cpu"),
                     record_trace=False)
    try:
        factor = host_array(jnp.asarray(_diag_dominant(32, "float64")))
        span = rt.solver_begin("getrf", factor)
        assert not span.pinned
        assert rt.placements.entry(id(factor)) is None
        rt.solver_end(span)
    finally:
        rtm.uninstall()


# --------------------------------------------------------------------- #
# default-off bit-identity                                               #
# --------------------------------------------------------------------- #
def test_lapack_off_golden_counters(monkeypatch):
    """SCILIB_LAPACK unset reproduces the pre-solver golden counters
    bit-for-bit on the capped eviction workload (same goldens the
    kernel-venue and precision stages preserve)."""
    monkeypatch.delenv("SCILIB_LAPACK", raising=False)
    rng = np.random.default_rng(42)
    rt = rtm.install("dfu", threshold=10, device_bytes=2 * 128 * 128 * 4,
                     record_trace=False)
    try:
        xs = [host_array(jnp.asarray(rng.standard_normal((128, 128)),
                                     jnp.float32)) for _ in range(5)]
        for _ in range(3):
            for x in xs:
                blas.gemm(x, x)
        rt.sync()
        assert rt.stats.evictions == 28
        assert rt.stats.evicted_bytes == 1835008
        st = rt.stats.per_routine["sgemm"]
        assert (st.offloaded, st.on_host) == (15, 0)
        assert (st.cache_hits, st.cache_misses) == (15, 15)
        assert not rt.stats.solvers
        assert "solvers (LAPACK tier)" not in rt.stats.report()
    finally:
        rtm.uninstall()


def test_lapack_off_trace_dump_has_no_solver_keys(tmp_path):
    """Default-off dumps carry no solver_id keys and no solver events —
    byte-stable against pre-solver readers and writers."""
    path = tmp_path / "t.json"
    with repro.session(OffloadConfig(threshold=1.0, sync=True)) as s:
        a = host_array(jnp.asarray(RNG.standard_normal((64, 64)),
                                   jnp.float32))
        blas.gemm(a, a)
        s.runtime.trace.dump(str(path))
    raw = json.loads(path.read_text())
    assert all("solver_id" not in c for c in raw["calls"])
    assert not any(e["kind"].startswith("solver")
                   for e in raw.get("events", ()))


def test_note_panel_is_noop_outside_spans():
    rt = rtm.install("dfu", threshold=10, record_trace=True)
    try:
        a = host_array(jnp.asarray(_rand((32, 32), "float64")))
        rt.note_panel("d", 32, 8, a)
        assert len(rt.trace) == 0
        assert "dgetf2" not in rt.stats.per_routine
        assert not rt.stats.solvers
    finally:
        rtm.uninstall()


# --------------------------------------------------------------------- #
# lsms mini-app through the tier                                         #
# --------------------------------------------------------------------- #
def test_run_mini_matches_host_under_lapack():
    from repro.apps.lsms import run_mini
    kw = dict(atoms=2, energies=2, scf=1, n=96, nb=32)
    ref = run_mini(**kw)
    with repro.session(OffloadConfig(lapack=True, threshold=48.0,
                                     lapack_nb=32)) as s:
        out = run_mini(**kw)
        assert {"getrf", "getrs"} <= set(s.runtime.stats.solvers)
        spans = s.runtime.trace.event_count("solver_begin")
        assert spans == 2 * kw["atoms"] * kw["energies"] * kw["scf"]
    assert out["n_solves"] == ref["n_solves"]
    assert out["max_resid"] < 1e-10
    np.testing.assert_allclose(out["energy"], ref["energy"], rtol=1e-9)


# --------------------------------------------------------------------- #
# autotuner: the lapack_nb grid dimension                                #
# --------------------------------------------------------------------- #
def _solver_trace(spans: int = 2, n: int = 512, nb: int = 64) -> Trace:
    t = Trace()
    el = 16
    tau = t.new_buffer(n * n * el, "tau")
    tm = t.new_buffer(n * 32 * el, "tmat")
    for s in range(spans):
        sid = f"gesv#{s}"
        t.record_event("solver_begin", sid, 0)
        for j0 in range(0, n, nb):
            jb = min(nb, n - j0)
            t.panel("z", n - j0, jb, tau, solver=sid)
            rem = n - j0 - jb
            if rem:
                t.trsm("z", jb, rem, tau, tau, solver=sid)
                t.gemm("z", rem, rem, jb, tau, tau, tau, solver=sid)
        t.trsm("z", n, 32, tau, tm, solver=sid)
        t.trsm("z", n, 32, tau, tm, solver=sid)
        t.record_event("solver_end", sid, 0)
    return t


def test_retile_lapack_regenerates_lu_spans():
    trace = _solver_trace(spans=2, n=512, nb=64)
    out = at.retile_lapack(trace, 128)
    assert at.retile_lapack(trace, 0) is trace
    per_span = 512 // 128
    panels = [c for c in out if c.routine.endswith("getf2")]
    assert len(panels) == 2 * per_span
    # solve-phase trsms (m == matrix n) survive verbatim
    solves = [c for c in out if c.routine.endswith("trsm")
              and c.m == 512]
    assert len(solves) == 4
    # buffers and span events are preserved
    assert out.buffer_sizes == trace.buffer_sizes
    assert out.event_count("solver_begin") == 2
    # the re-tiled stream stays span-tagged
    assert all(c.solver == "gesv" for c in out if c.solver_id)


def test_retile_leaves_spanfree_traces_alone():
    t = Trace()
    a = t.new_buffer(64 * 64 * 4, "A")
    t.gemm("s", 64, 64, 64, a, a, a)
    assert at.retile_lapack(t, 128) is t


def test_autotune_sweeps_nb_only_on_solver_traces():
    res = at.autotune(_solver_trace(), policies=("dfu",),
                      device_counts=(1,), device_bytes=None)
    assert {p.lapack_nb for p in res.points} == {0, 64, 128, 256}
    assert "nb" in at.format_grid(res).splitlines()[0]
    plain = Trace()
    a = plain.new_buffer(512 * 512 * 4, "A")
    for _ in range(4):
        plain.gemm("s", 512, 512, 512, a, a, a)
    res_off = at.autotune(plain, policies=("dfu",), device_counts=(1,),
                          device_bytes=None)
    assert all(p.lapack_nb == 0 for p in res_off.points)


def test_autotune_nb_point_env_and_config():
    res = at.autotune(_solver_trace(), policies=("dfu",),
                      device_counts=(1,), device_bytes=None,
                      lapack_nbs=(0, 128))
    p = next(p for p in res.points if p.lapack_nb == 128)
    assert p.env().get("SCILIB_LAPACK") == "1"
    assert p.env().get("SCILIB_LAPACK_NB") == "128"
    cfg = p.to_config()
    assert cfg.lapack is True and cfg.lapack_nb == 128
    base = next(p for p in res.points if p.lapack_nb == 0)
    assert "SCILIB_LAPACK" not in base.env()


# --------------------------------------------------------------------- #
# config plumbing                                                        #
# --------------------------------------------------------------------- #
def test_lapack_env_fields(monkeypatch):
    monkeypatch.setenv("SCILIB_LAPACK", "1")
    monkeypatch.setenv("SCILIB_LAPACK_NB", "96")
    cfg = OffloadConfig.from_env()
    assert cfg.lapack is True and cfg.lapack_nb == 96
    monkeypatch.delenv("SCILIB_LAPACK")
    monkeypatch.delenv("SCILIB_LAPACK_NB")
    cfg = OffloadConfig.from_env()
    assert cfg.lapack is False and cfg.lapack_nb == 0
    with pytest.raises(ValueError):
        OffloadConfig(lapack_nb=-1)
