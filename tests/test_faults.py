"""Fault tolerance: spec parsing, exception classification, the
deterministic injector, retry/backoff, the per-device circuit breaker
state machine, host fallback bit-identity, quarantine -> re-shard ->
recover on a multi-device layout, live == replay fault counters, and
the exception-safe sync() drain."""
import contextlib
import os
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import blas, memspace  # noqa: E402
from repro.core import faults as flt  # noqa: E402
from repro.core import runtime as rtm  # noqa: E402
from repro.core.config import OffloadConfig  # noqa: E402
from repro.core.policy import host_array  # noqa: E402
from repro.core.session import Session  # noqa: E402
from repro.memtier.simulator import MemTierSimulator  # noqa: E402

RNG = np.random.default_rng(23)


def _mat(n, m=None):
    return RNG.standard_normal((n if m is None else m, n)).astype(
        np.float32)


@contextlib.contextmanager
def _devices(n):
    old = os.environ.get("SCILIB_DEVICES")
    os.environ["SCILIB_DEVICES"] = str(n)
    memspace.install()
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("SCILIB_DEVICES", None)
        else:
            os.environ["SCILIB_DEVICES"] = old
        memspace.install()


# --------------------------------------------------------------------- #
# spec grammar                                                           #
# --------------------------------------------------------------------- #
def test_parse_spec_full_grammar():
    rules = flt.parse_spec("transfer:p=0.05,device=1,seed=7;kernel:nth=13")
    assert rules == (
        flt.FaultRule(kind="transfer", p=0.05, device=1, seed=7),
        flt.FaultRule(kind="kernel", nth=13))


def test_parse_spec_empty_is_no_rules():
    assert flt.parse_spec("") == ()
    assert flt.parse_spec("  ") == ()
    assert flt.FaultInjector.from_spec("") is None


@pytest.mark.parametrize("bad", [
    "bogus:p=1",           # unknown fault kind
    "transfer:q=1",        # unknown parameter
    "transfer:p=1.5",      # probability out of range
    "transfer:nth=0",      # nth counts from 1
    "transfer:device=-1",  # negative device index
    "transfer",            # non-latency rule with no trigger
    "kernel:p=x",          # unparseable value
])
def test_parse_spec_rejects(bad):
    with pytest.raises(ValueError):
        flt.parse_spec(bad)


def test_config_validates_fault_knobs():
    with pytest.raises(ValueError):
        OffloadConfig(faults="bogus:p=1")
    with pytest.raises(ValueError):
        OffloadConfig(retries=-1)
    with pytest.raises(ValueError):
        OffloadConfig(backoff_ms=-0.5)
    cfg = OffloadConfig(faults="transfer:p=0.5,seed=3", retries=4)
    assert cfg.retries == 4


# --------------------------------------------------------------------- #
# exception classification                                               #
# --------------------------------------------------------------------- #
def test_classify_maps_absorbable_errors():
    oom = flt.classify("transfer", MemoryError("boom"), device=1,
                       nbytes=64)
    assert isinstance(oom, flt.DeviceOOMError) and not oom.transient
    assert oom.device == 1 and oom.nbytes == 64
    oom2 = flt.classify("kernel", RuntimeError("RESOURCE_EXHAUSTED: hbm"))
    assert isinstance(oom2, flt.DeviceOOMError)
    tr = flt.classify("transfer", OSError("link reset"))
    assert isinstance(tr, flt.TransferError) and tr.transient
    kr = flt.classify("kernel", RuntimeError("launch failed"))
    assert isinstance(kr, flt.KernelError) and kr.transient


def test_classify_leaves_bugs_alone():
    # bugs in our own stack must keep their type and traceback
    assert flt.classify("kernel", TypeError("bad arg")) is None
    assert flt.classify("transfer", ValueError("shape")) is None
    # already-typed errors pass through unchanged
    e = flt.TransferError("x", device=2)
    assert flt.classify("transfer", e) is e


# --------------------------------------------------------------------- #
# the injector                                                           #
# --------------------------------------------------------------------- #
def _injected_pattern(spec, n=200, site="transfer", device=None):
    inj = flt.FaultInjector.from_spec(spec)
    out = []
    for _ in range(n):
        try:
            inj.check(site, device=device, nbytes=8)
            out.append(0)
        except flt.OffloadError:
            out.append(1)
    return out


def test_injector_is_deterministic():
    a = _injected_pattern("transfer:p=0.2,seed=11")
    b = _injected_pattern("transfer:p=0.2,seed=11")
    assert a == b and sum(a) > 0
    c = _injected_pattern("transfer:p=0.2,seed=12")
    assert a != c


def test_injector_nth_fires_periodically():
    hits = _injected_pattern("transfer:nth=5", n=20)
    assert hits == [0, 0, 0, 0, 1] * 4


def test_injector_device_filter():
    # device-filtered rule fires only on its device, never on device=None
    assert sum(_injected_pattern("transfer:p=1,device=1", device=0)) == 0
    assert sum(_injected_pattern("transfer:p=1,device=1", device=None)) == 0
    assert sum(_injected_pattern("transfer:p=1,device=1", device=1,
                                 n=5)) == 5


def test_injector_site_and_kind_mapping():
    with pytest.raises(flt.DeviceOOMError):
        flt.FaultInjector.from_spec("oom:p=1").check("transfer")
    with pytest.raises(flt.KernelError):
        flt.FaultInjector.from_spec("kernel:p=1").check("kernel")
    # kernel rules never fire at transfer sites and vice versa
    inj = flt.FaultInjector.from_spec("kernel:p=1")
    inj.check("transfer")
    inj = flt.FaultInjector.from_spec("transfer:p=1")
    inj.check("kernel")
    # latency injects a stall, not an error
    t0 = time.perf_counter()
    flt.FaultInjector.from_spec("latency:p=1,ms=5").check("transfer")
    assert time.perf_counter() - t0 >= 0.004


# --------------------------------------------------------------------- #
# retry policy + breaker state machine                                   #
# --------------------------------------------------------------------- #
def test_retry_backoff_is_exponential():
    rp = flt.RetryPolicy(attempts=3, backoff_ms=8.0)
    assert [rp.delay_s(a) for a in range(3)] == [0.008, 0.016, 0.032]


def test_breaker_state_machine_with_fake_clock():
    now = [0.0]
    events = []
    ht = flt.HealthTracker(
        2, threshold=3, cooldown_ms=100.0, clock=lambda: now[0],
        on_quarantine=lambda d: events.append(("q", d)),
        on_recover=lambda d: events.append(("r", d)))
    # two failures then a success: consecutive count resets, no trip
    assert not ht.failure(1) and not ht.failure(1)
    ht.ok(1)
    assert ht.device(1).state == flt.CLOSED
    # three consecutive failures trip the breaker
    assert [ht.failure(1) for _ in range(3)] == [False, False, True]
    assert ht.device(1).state == flt.OPEN
    assert not ht.usable(1) and ht.usable(0)
    assert ht.usable_count() == 1 and ht.usable_devices() == [0]
    assert events == [("q", 1)]
    # cooldown elapses -> half-open probe allowed
    now[0] = 0.2
    assert ht.usable(1) and ht.device(1).state == flt.HALF_OPEN
    # a failed probe re-opens immediately (no threshold accumulation)
    assert ht.failure(1)
    assert ht.device(1).state == flt.OPEN and events[-1] == ("q", 1)
    # next probe succeeds -> closed again, recover hook fires
    now[0] = 0.4
    assert ht.usable(1)
    ht.ok(1)
    assert ht.device(1).state == flt.CLOSED and events[-1] == ("r", 1)
    assert ht.usable_count() == 2


def test_breaker_disabled_never_trips():
    ht = flt.HealthTracker(1, threshold=0)
    for _ in range(50):
        ht.failure(0)
    assert ht.usable(0) and ht.device(0).quarantines == 0


# --------------------------------------------------------------------- #
# runtime integration                                                    #
# --------------------------------------------------------------------- #
def _workload(n_calls=6, n=96, seed=5):
    rng = np.random.default_rng(seed)
    mats = [(host_array(rng.standard_normal((n, n)).astype(np.float32)),
             host_array(rng.standard_normal((n, n)).astype(np.float32)))
            for _ in range(n_calls)]
    outs = [np.asarray(blas.gemm(a, b)) for a, b in mats]
    refs = [np.asarray(jnp.asarray(np.asarray(a))
                       @ jnp.asarray(np.asarray(b))) for a, b in mats]
    return outs, refs


def _run(cfg):
    with Session(cfg, record_trace=True, intercept=False) as s:
        outs, refs = _workload()
        st = s.runtime.stats
        sg = st.per_routine["sgemm"]
        snap = dict(faults=st.faults, retries=st.retries,
                    fallbacks=st.fallbacks, bytes_in=sg.bytes_in,
                    cache_hits=sg.cache_hits, offloaded=sg.offloaded,
                    on_host=sg.on_host)
        trace = s.runtime.trace
    return outs, refs, snap, trace


def test_retry_absorbs_transient_faults_exactly():
    """A retried fault is a perfect no-op: every byte/hit/offload
    counter matches the unfaulted run, and results are bit-identical."""
    base = dict(policy="dfu", threshold=10.0)
    o0, r0, clean, _ = _run(OffloadConfig(**base))
    o1, _, chaotic, _ = _run(OffloadConfig(
        **base, faults="transfer:nth=2", retries=2, backoff_ms=0.0))
    assert chaotic["faults"] > 0
    assert chaotic["retries"] == chaotic["faults"]
    assert chaotic["fallbacks"] == 0
    for key in ("bytes_in", "cache_hits", "offloaded", "on_host"):
        assert chaotic[key] == clean[key], key
    for a, b in zip(o0, o1):
        assert np.array_equal(a, b)


def test_kernel_fault_exhaustion_falls_back_bit_identically():
    outs, refs, snap, _ = _run(OffloadConfig(
        policy="dfu", threshold=10.0, faults="kernel:p=1,seed=5",
        retries=0, breaker=0))
    assert snap["fallbacks"] == 6 and snap["on_host"] == 6
    assert snap["offloaded"] == 0
    for got, ref in zip(outs, refs):
        assert np.array_equal(got, ref)     # same jit on same values


def test_oom_is_permanent_no_retries():
    _, _, snap, _ = _run(OffloadConfig(
        policy="dfu", threshold=10.0, faults="oom:p=1", retries=3,
        breaker=0, backoff_ms=0.0))
    assert snap["faults"] > 0 and snap["retries"] == 0
    assert snap["fallbacks"] == 6


def test_real_bugs_still_propagate():
    """classify() must not absorb caller errors into fallbacks."""
    with Session(OffloadConfig(policy="dfu", threshold=10.0, retries=3),
                 record_trace=False, intercept=False):
        with pytest.raises((TypeError, ValueError)):
            blas.gemm(host_array(_mat(8)), host_array(_mat(16)))


def test_degraded_mode_serves_from_host():
    """Breaker tripped on every device -> host-only degraded mode keeps
    serving with correct results (no exception escapes)."""
    cfg = OffloadConfig(policy="dfu", threshold=10.0,
                        faults="transfer:p=1,seed=2", retries=0,
                        breaker=2, breaker_cooldown_ms=60_000.0)
    with Session(cfg, record_trace=False, intercept=False) as s:
        outs, refs = _workload()
        st = s.runtime.stats
        assert st.quarantines == 1
        assert st.fallbacks == 6
        assert not s.runtime.health.any_usable()
    for got, ref in zip(outs, refs):
        assert np.array_equal(got, ref)


def test_report_shows_health_only_under_faults():
    with Session(OffloadConfig(policy="dfu", threshold=10.0),
                 record_trace=False, intercept=False) as s:
        _workload(n_calls=1)
        assert "health:" not in s.runtime.stats.report()
    with Session(OffloadConfig(policy="dfu", threshold=10.0,
                               faults="kernel:nth=1", retries=1,
                               backoff_ms=0.0),
                 record_trace=False, intercept=False) as s:
        _workload(n_calls=1)
        rep = s.runtime.stats.report()
        assert "health:" in rep and "dev0:" in rep


# --------------------------------------------------------------------- #
# quarantine -> re-shard -> recover (multi-device)                       #
# --------------------------------------------------------------------- #
def test_quarantine_reshard_recover():
    with _devices(3):
        cfg = OffloadConfig(policy="dfu", threshold=10.0, devices=3,
                            faults="transfer:p=1,device=1,seed=1",
                            retries=0, breaker=2,
                            breaker_cooldown_ms=50.0)
        # operands and host references made up front: the calls between
        # a trip and the next call must fit in the 50 ms cooldown, and
        # a host BLAS reference can take longer than that on its own
        pairs = [(_mat(384), _mat(384)) for _ in range(4)]
        refs = [a @ b for a, b in pairs]
        with Session(cfg, record_trace=False, intercept=False) as s:
            rt = s.runtime
            outs = []

            def call():
                a, b = pairs[len(outs)]
                outs.append(np.asarray(
                    blas.gemm(host_array(a), host_array(b))))

            # two sharded calls hit dev1 tiles -> 2 consecutive unit
            # failures -> quarantine (each call itself falls back)
            call()
            call()
            assert rt.stats.quarantines == 1
            assert rt.stats.fallbacks == 2
            assert not rt.health.usable(1)
            assert rt.block_stores[1].resident_bytes == 0  # invalidated
            # next call re-shards across the healthy pair
            call()
            assert rt.stats.per_routine["sgemm"].sharded >= 1
            assert rt.stats.fallbacks == 2                 # no new ones
            assert rt.stats.per_device[1].tiles == 0       # dev1 idle
            # clear the injector, wait out the cooldown: the half-open
            # probe succeeds and dev1 rejoins the fleet
            s.reconfigure(faults="")
            time.sleep(0.06)
            call()
            assert rt.health.usable(1)
            assert rt.stats.recoveries == 1
            assert rt.health.device(1).state == flt.CLOSED
            for got, ref in zip(outs, refs):
                np.testing.assert_allclose(got, ref, rtol=2e-3,
                                           atol=2e-3)


# --------------------------------------------------------------------- #
# live == replay                                                         #
# --------------------------------------------------------------------- #
def test_faulted_live_run_matches_replay_counters():
    # kernel faults get absorbed by the retry; oom faults are permanent
    # and fall back — the trace must carry both accurately
    cfg = OffloadConfig(policy="dfu", threshold=10.0,
                        faults="kernel:nth=3;oom:nth=5", retries=1,
                        backoff_ms=0.0, breaker=0)
    with Session(cfg, record_trace=True, intercept=False) as s:
        _workload(n_calls=8)
        st = s.runtime.stats
        trace = s.runtime.trace
        live = (st.faults, st.retries, st.fallbacks, st.quarantines,
                st.recoveries)
    assert st.retries > 0 and st.fallbacks > 0      # both paths exercised
    rep = MemTierSimulator.from_config(cfg).run(trace)
    assert (rep.faults, rep.retries, rep.fallbacks, rep.quarantines,
            rep.recoveries) == live
    # the forced-host set really moved calls off the device path
    assert rep.host_calls >= st.fallbacks


def test_fault_events_roundtrip_through_dump(tmp_path):
    from repro.core.trace import Trace
    path = str(tmp_path / "t.json")
    cfg = OffloadConfig(policy="dfu", threshold=10.0,
                        faults="kernel:nth=2", retries=0, breaker=0,
                        trace_path=path)
    with Session(cfg, record_trace=True, intercept=False):
        _workload(n_calls=4)
    loaded = Trace.load(path)
    assert loaded.event_count("fault") > 0
    assert loaded.event_count("fallback") == loaded.event_count("fault")
    rep = MemTierSimulator.from_config(cfg).run(loaded)
    assert rep.fallbacks == loaded.event_count("fallback")


def test_trace_dump_is_atomic(tmp_path):
    """A dump that cannot serialize leaves no partial file behind."""
    from repro.core.trace import Trace
    t = Trace()
    t.gemm("s", 8, 8, 8, t.new_buffer(256), t.new_buffer(256),
           t.new_buffer(256))
    target = tmp_path / "out.json"
    t.dump(str(target))
    good = target.read_bytes()
    t.calls.append(object())           # unserializable: dump must fail
    with pytest.raises(Exception):
        t.dump(str(target))
    assert target.read_bytes() == good          # old file intact
    assert list(tmp_path.iterdir()) == [target]  # no tmp litter


# --------------------------------------------------------------------- #
# exception-safe sync()                                                  #
# --------------------------------------------------------------------- #
def test_sync_drains_everything_and_reraises_first():
    class _Buf:
        def __init__(self, log, fail=None):
            self.log, self.fail = log, fail

        def block_until_ready(self):
            self.log.append(self)
            if self.fail is not None:
                raise self.fail

    with Session(OffloadConfig(policy="dfu"), record_trace=False,
                 intercept=False) as s:
        rt = s.runtime
        log = []
        first = RuntimeError("first failure")
        bufs = [_Buf(log), _Buf(log, first), _Buf(log),
                _Buf(log, RuntimeError("second failure")), _Buf(log)]
        rt._pending.extend(bufs)
        with pytest.raises(RuntimeError) as exc_info:
            rt.sync()
        assert exc_info.value is first
        assert log == bufs                  # every buffer was awaited
        assert not rt._pending              # queue fully drained
        if hasattr(first, "__notes__"):     # py3.11+
            assert any("second failure" in n for n in first.__notes__)
        rt._pending.append(_Buf(log))
        rt.sync()                           # clean sync still works


# --------------------------------------------------------------------- #
# property: any fault spec leaves results bit-identical                  #
# --------------------------------------------------------------------- #
def _check_bit_identity(spec, retries):
    """The robustness contract: under ANY injected fault pattern the
    numerical results equal the unfaulted host-path run bit for bit."""
    a = RNG.standard_normal((64, 64)).astype(np.float32)
    b = RNG.standard_normal((64, 64)).astype(np.float32)
    with Session(OffloadConfig(policy="cpu"), record_trace=False,
                 intercept=False):
        want = np.asarray(blas.gemm(host_array(a), host_array(b)))
    cfg = OffloadConfig(policy="dfu", threshold=10.0, faults=spec,
                        retries=retries, backoff_ms=0.0, breaker=2,
                        breaker_cooldown_ms=60_000.0)
    with Session(cfg, record_trace=False, intercept=False):
        got = np.asarray(blas.gemm(host_array(a), host_array(b)))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("spec,retries", [
    ("", 2),
    ("transfer:p=1,seed=0", 0),
    ("transfer:nth=1", 2),
    ("kernel:p=1,seed=9", 1),
    ("oom:p=1", 3),
    ("latency:p=1,ms=1", 0),
    ("transfer:p=0.62,seed=4;kernel:nth=2", 1),
])
def test_fault_specs_are_bit_identical_to_host(spec, retries):
    _check_bit_identity(spec, retries)


try:                                    # hypothesis widens the sweep
    from hypothesis import given, settings
    from hypothesis import strategies as st_
except ImportError:                     # pragma: no cover — CI has it
    given = None

if given is not None:
    _SPECS = st_.one_of(
        st_.just(""),
        st_.builds(lambda k, p, s: f"{k}:p={p:.2f},seed={s}",
                   st_.sampled_from(["transfer", "kernel", "oom"]),
                   st_.floats(0.0, 1.0), st_.integers(0, 99)),
        st_.builds(lambda k, n: f"{k}:nth={n}",
                   st_.sampled_from(["transfer", "kernel"]),
                   st_.integers(1, 5)),
        st_.builds(
            lambda p, s, n: f"transfer:p={p:.2f},seed={s};kernel:nth={n}",
            st_.floats(0.0, 1.0), st_.integers(0, 99),
            st_.integers(1, 5)),
    )

    @settings(max_examples=12, deadline=None)
    @given(spec=_SPECS, retries=st_.integers(0, 2))
    def test_any_fault_spec_is_bit_identical_to_host(spec, retries):
        _check_bit_identity(spec, retries)
