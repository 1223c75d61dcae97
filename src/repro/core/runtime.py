"""SCILIB-Accel offload runtime: the JAX re-implementation of paper §3.

Every runtime is configured by one typed
:class:`~repro.core.config.OffloadConfig` (the ``SCILIB_*`` env names
below remain supported spellings, ingested solely by
``OffloadConfig.from_env()``), and normally lives inside a
:class:`~repro.core.session.Session`; ``install()``/``uninstall()``
below are legacy shims over an implicit session.

One ``OffloadRuntime`` owns

* the **placement registry** — buffer identity -> device-tier placement.
  This is the JAX analogue of the remapped page table (Fig. 2): the caller
  keeps its handle, the physical home changes once, later uses are free.
  The registry is a byte-capped :class:`~repro.core.residency.
  ResidencyStore` (``SCILIB_DEVICE_BYTES``): when device residency
  exceeds the cap, the eviction policy (``SCILIB_EVICT`` — ``lru``
  default, ``lfu``, or cost-aware ``refetch``) pushes placements back
  to the host tier so DFU cannot grow HBM use unboundedly.  Pinned
  entries (``runtime.pin(x)``, or ``SCILIB_PIN=never-evict`` for
  everything) survive arbitrary pressure.  The same store class backs
  the per-device tile-block registries, the trace-id table, and the
  memtier simulator's replay, so live runs and simulation share one
  accounting implementation — residency events (place/hit/evict/
  refetch) are recorded into the trace and can be checked
  count-for-count against a replay.
* the **offload decision** (threshold logic of §3.3), memoized per call
  site in the **dispatch cache** — steady-state calls re-derive nothing,
* the **statistics** the paper's ``.fini_array`` hook prints (per-routine
  call/offload counts, bytes moved, wall time, reuse counts),
* a **BLAS trace** so any run can be replayed through the memtier
  simulator under calibrated GH200/TPU constants (Tables 3/5 methodology),
* the **multi-device tile scheduler**: with more than one device tier
  (``len(jax.devices()) > 1``, or ``SCILIB_DEVICES=n`` forcing a
  simulated N-tier layout), super-threshold calls are split into 2-D
  tiles scheduled round-robin-with-affinity across devices, BLASX-style
  — a tile runs on the device where its operand block is already
  resident, tracked in per-device block registries with per-device byte
  caps and eviction counters.  With one device the scheduler is inert
  and the single-device fast path is untouched.

Execution is **asynchronous by default**: the runtime manages *placement*
and hands XLA the jit-compiled arithmetic without blocking, exactly like
the paper's tool returns control to the host thread while cuBLAS runs.
``SCILIB_SYNC=1`` (or ``install(..., sync=True)``) restores the fully
synchronous seed behaviour — per-call ``block_until_ready`` with wall
time measured around the device work — and ``runtime.sync()`` drains
in-flight results explicitly (what benchmarks call before reading clocks).

**The dispatch pipeline.**  ``blas_call`` is a staged pipeline with
call-site identity threaded through every layer, mirroring the paper's
per-call-site DBI patching:

    canonicalize -> decide -> plan -> execute -> record

* *canonicalize* bundles the call into a :class:`CallContext` and
  fingerprints the call site (:mod:`repro.core.callsite`).
* *decide* runs the ordered ``decision_stages`` — adaptive per-site
  lock-in (``SCILIB_ADAPTIVE=1``), the memoized dispatch cache, then the
  threshold rule — until one yields a :class:`DispatchDecision`; the
  policy capability (``policy.offloads``) can veto offload afterwards.
  Stages are plain callables on the runtime: later policies plug in by
  inserting into ``decision_stages`` instead of editing branches.
* *plan* consults the multi-device tile planner only when the decision
  offloads and more than one device tier exists.
* *execute* runs the host path, the whole-call offload path, or the
  sharded tile schedule.
* *record* updates per-routine and per-site statistics and appends the
  :class:`~repro.core.trace.BlasCall` (with ``callsite_id`` and the
  measured per-call ``seconds``) to the trace.

**Adaptive per-site mode** (``SCILIB_ADAPTIVE=1``): the first
``SCILIB_ADAPTIVE_WARMUP`` calls at each site alternate deterministically
between the host and offload paths, timed synchronously, and the faster
path is then locked — exactly the paper's warmup-then-patch behaviour.
With ``SCILIB_ADAPTIVE=0`` (default) the pipeline is behaviour-identical
to the flat dispatch it replaced.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from contextvars import ContextVar
from typing import Callable, Dict, Hashable, Optional, Sequence, Tuple

import jax

from repro.core import callsite as cs
from repro.core import faults as flt
from repro.core import memspace
from repro.core import precision as prec
from repro.core import residency as res
from repro.core import threshold as thr
from repro.core import trace as trc
from repro.core.config import OffloadConfig
from repro.core.policy import CounterPolicy, PolicyBase, make_policy
from repro.core.trace import Trace

#: how many in-flight outputs the async mode keeps alive for ``sync()``;
#: XLA executes in submission order, so a bounded window is enough.
_PENDING_WINDOW = 32

#: dispatch-decision entries kept per runtime before a full reset
#: (long-lived servers over ragged shapes must not leak decisions).
_DECISION_CACHE_LIMIT = 65536

#: dtypes a device platform's compiler refuses for BLAS-3 arithmetic:
#: on a TPU complex128 dot_general and triangular_solve abort the
#: compiler, so such calls stay on the host path
_HOST_ONLY_DTYPES = {"tpu": frozenset({"complex128"})}


# --------------------------------------------------------------------- #
# tile plans (built by core.blas, executed by the scheduler below)       #
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class TileOp:
    """One operand block of one tile.

    ``coords`` is the (r0, r1, c0, c1) window on the *parent* array —
    the stable block identity the affinity registry keys on, so the same
    window of the same buffer lands on the same device call after call.
    ``shared`` marks blocks identical across every tile of the plan
    (e.g. the triangle of trsm): they replicate per device and must not
    steer affinity, or every tile would chase one device.
    """

    role: str
    parent: jax.Array
    coords: Tuple[int, int, int, int]
    shared: bool = False
    written: bool = False

    def key(self) -> Tuple:
        return (id(self.parent),) + self.coords

    @property
    def nbytes(self) -> int:
        r0, r1, c0, c1 = self.coords
        return (r1 - r0) * (c1 - c0) * self.parent.dtype.itemsize

    def materialize(self) -> jax.Array:
        r0, r1, c0, c1 = self.coords
        if (r0, c0) == (0, 0) and (r1, c1) == self.parent.shape[-2:]:
            return self.parent
        return self.parent[r0:r1, c0:c1]


@dataclasses.dataclass
class Tile:
    """One unit of scheduled work: placed operand blocks -> output block."""

    ops: Tuple[TileOp, ...]
    compute: Callable[..., jax.Array]
    out_coords: Tuple[int, int, int, int]


@dataclasses.dataclass
class TilePlan:
    """A 2-D decomposition of one level-3 call plus its gather."""

    grid: Tuple[int, int]
    tiles: Tuple[Tile, ...]
    gather: Callable[[Sequence[jax.Array]], jax.Array]


# --------------------------------------------------------------------- #
# dispatch-pipeline IR                                                   #
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class CallContext:
    """One canonicalized BLAS call flowing through the pipeline."""

    routine: str
    m: int
    n: int
    k: int
    batch: int
    operands: Sequence[Tuple[str, jax.Array, float, bool]]
    arrays: list
    compute: Callable[..., jax.Array]
    key: Optional[Hashable]
    shard: Optional[Callable[[int], Optional["TilePlan"]]]
    # the pallas-venue arithmetic for this call (same operand order as
    # ``compute``); None when the routine has no kernel — the venue
    # resolution then falls back to the generic XLA offload
    kernel_compute: Optional[Callable[..., jax.Array]] = None
    # split-precision factory ``(scheme, venue) -> compute`` (same
    # placed operand order); None when the call has no split
    # formulation (non-f64 dtype, unsupported base).  Built lazily by
    # core.blas only when SCILIB_PRECISION is configured, so the
    # default pipeline never pays for it.
    split_compute: Optional[Callable[[str, str],
                                     Callable[..., jax.Array]]] = None
    # sampled-residual estimator ``(out, *arrays) -> rel error`` for
    # the escalation check (repro.core.precision.gemm_residual et al.,
    # with the call's scalars/flags captured); None disables the check.
    split_check: Optional[Callable[..., jax.Array]] = None
    site: Optional[cs.CallSiteProfile] = None
    site_id: str = ""
    # the offload path's placed operands (set by _offload_whole), which
    # the split residual check and an escalated rerun reuse
    placed: Optional[list] = None
    # host seconds of the call's stages: canonicalize + decide, and
    # operand / output placement (a part of the call's ``seconds``)
    decide_s: float = 0.0
    place_s: float = 0.0


@dataclasses.dataclass
class DispatchDecision:
    """The small dispatch IR a decision stage emits: offload?  why?
    Later stages attach the tile plan (device? shard plan?)."""

    offload: bool
    n_avg: float = 0.0
    why: str = "threshold"      # "cache" | "threshold" | "adaptive:probe"
    #                           # | "adaptive:locked" | "policy:host-only"
    #                           # | "dtype:host-only"
    #                           # (+ "+kernel" suffix on the pallas venue)
    plan: Optional[TilePlan] = None
    timed: bool = False         # adaptive probe: block + bill path timing
    # execution venue ("host"/"xla"/"pallas"); "" with kernel_path off,
    # so the default pipeline is byte-identical to the two-venue one
    venue: str = ""
    # split-precision scheme ("split2"/"split3"); "" with
    # SCILIB_PRECISION off, keeping the default pipeline bit-identical.
    # An escalated call keeps the attempted scheme (why gains "+esc").
    precision: str = ""


@dataclasses.dataclass
class RoutineStats:
    calls: int = 0
    offloaded: int = 0
    on_host: int = 0
    # host seconds from the plan to the submitted result; the decision
    # before it is ``decide_seconds``, the placement in it
    # ``place_seconds``
    seconds: float = 0.0
    decide_seconds: float = 0.0
    place_seconds: float = 0.0
    flops: float = 0.0
    bytes_in: int = 0
    bytes_out: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    # dispatch fast path: calls whose offload decision came from the
    # per-call-site dispatch cache vs. calls that had to derive it
    dispatch_hits: int = 0
    dispatch_misses: int = 0
    # bytes streamed from the host tier without persisting (the coherent
    # remote-read path of GH200; a transient copy on this container)
    transient_bytes: int = 0
    # multi-device tile scheduler: calls split across devices / tiles run
    sharded: int = 0
    tiles: int = 0
    # failure paths: transient-fault retries and host fallbacks after
    # retry exhaustion / quarantine (the call still completed, on host)
    retries: int = 0
    fallbacks: int = 0
    # kernel_path: offloaded calls executed on the pallas venue (a
    # subset of ``offloaded``) and their wall time
    kernel_calls: int = 0
    kernel_seconds: float = 0.0
    # split precision (SCILIB_PRECISION): offloaded calls executed via
    # a split scheme (subset of ``offloaded``), their wall time, and
    # calls whose residual check escalated back to native fp64
    split_calls: int = 0
    split_seconds: float = 0.0
    escalations: int = 0


@dataclasses.dataclass
class DeviceStats:
    """Per-device-tier accounting of the multi-device tile scheduler."""

    tiles: int = 0               # tile kernels scheduled on this device
    moved_bytes: int = 0         # host -> this device block movement
    affinity_hits: int = 0       # blocks already resident here (free)
    evictions: int = 0           # per-device byte-cap LRU pressure
    evicted_bytes: int = 0


@dataclasses.dataclass
class SolverStats:
    """Per-solver accounting of the LAPACK tier (:mod:`repro.solvers`):
    one row per solver name, aggregated over that solver's spans."""

    spans: int = 0               # solver_begin/solver_end pairs
    calls: int = 0               # inner BLAS + panel calls in the spans
    panel_calls: int = 0         # unblocked getf2 panels, on the factor's tier
    moved_bytes: int = 0         # movement attributed to the spans
    seconds: float = 0.0         # wall time inside the spans


@dataclasses.dataclass
class RuntimeStats:
    per_routine: Dict[str, RoutineStats] = dataclasses.field(
        default_factory=dict)
    per_device: Dict[int, DeviceStats] = dataclasses.field(
        default_factory=dict)
    # LAPACK-tier solver spans (getrf/potrf/syev...), keyed by solver
    # name — empty (and invisible in the report) unless repro.solvers ran
    solvers: Dict[str, SolverStats] = dataclasses.field(
        default_factory=dict)
    uninstrumented_calls: int = 0
    # placement-registry cap pressure (mirrors the residency store)
    evictions: int = 0
    evicted_bytes: int = 0
    # evicted entries placed again later: the cap's real cost in link
    # traffic (summed over the placement and per-device block stores)
    refetches: int = 0
    refetched_bytes: int = 0
    # failure-path counters (each increments exactly when the matching
    # trace event is emitted, so a live run and its replay agree)
    faults: int = 0            # fault errors observed (injected + real)
    retries: int = 0           # transient-fault retries performed
    fallbacks: int = 0         # calls completed on host after a failure
    quarantines: int = 0       # breaker trips (incl. half-open re-trips)
    recoveries: int = 0        # quarantined devices re-admitted
    # moves over the host link, per direction, counted where memspace
    # makes them (BLAS operands and the LU's panel pivots alike):
    # bytes, and host seconds inside the transfer call
    link_h2d_bytes: int = 0
    link_h2d_seconds: float = 0.0
    link_d2h_bytes: int = 0
    link_d2h_seconds: float = 0.0
    # per-call-site profiles (shared with the owning runtime's registry)
    callsites: Optional[cs.CallSiteRegistry] = None
    # the owning runtime's per-device circuit breaker (health section)
    breaker: Optional[flt.HealthTracker] = None
    # the platforms each path's results were computed on ("host" /
    # "offload" -> {"cpu", "tpu", ...}), read off the output buffers
    platforms: Dict[str, set] = dataclasses.field(default_factory=dict)

    def routine(self, name: str) -> RoutineStats:
        return self.per_routine.setdefault(name, RoutineStats())

    def solver(self, name: str) -> SolverStats:
        return self.solvers.setdefault(name, SolverStats())

    def device(self, index: int) -> DeviceStats:
        return self.per_device.setdefault(index, DeviceStats())

    @property
    def total_moved_bytes(self) -> int:
        return sum(r.bytes_in + r.bytes_out
                   for r in self.per_routine.values())

    def flat(self) -> Dict[str, float]:
        """Every numeric counter in one flat dict, for taking window
        differences: top-level counters under their field names, rows
        as ``<routine>.<field>``, ``dev<i>.<field>`` and
        ``solver.<name>.<field>``."""
        def numbers(obj, prefix=""):
            return {prefix + f.name: getattr(obj, f.name)
                    for f in dataclasses.fields(obj)
                    if type(getattr(obj, f.name)) in (int, float)}
        out = numbers(self)
        for name, r in self.per_routine.items():
            out.update(numbers(r, f"{name}."))
        for dev, d in self.per_device.items():
            out.update(numbers(d, f"dev{dev}."))
        for name, sv in self.solvers.items():
            out.update(numbers(sv, f"solver.{name}."))
        return out

    def report(self) -> str:
        lines = ["scilib-accel runtime report",
                 f"{'routine':<10}{'calls':>8}{'offload':>9}{'host':>7}"
                 f"{'sec':>10}{'GB moved':>10}{'reuse':>8}{'dhit':>7}"]
        for name, r in sorted(self.per_routine.items()):
            gb = (r.bytes_in + r.bytes_out) / 1e9
            reuse = r.cache_hits / max(1, r.cache_misses)
            dhit = r.dispatch_hits / max(1, r.dispatch_hits
                                         + r.dispatch_misses)
            lines.append(f"{name:<10}{r.calls:>8}{r.offloaded:>9}"
                         f"{r.on_host:>7}{r.seconds:>10.3f}{gb:>10.3f}"
                         f"{reuse:>8.1f}{dhit:>7.2f}")
        lines.append(f"uninstrumented calls: {self.uninstrumented_calls}")
        lines.append(f"link: h2d {self.link_h2d_bytes / 1e9:.3f} GB in "
                     f"{self.link_h2d_seconds:.3f} s, d2h "
                     f"{self.link_d2h_bytes / 1e9:.3f} GB in "
                     f"{self.link_d2h_seconds:.3f} s")
        if self.platforms:
            lines.append("platforms: " + " ".join(
                f"{path}={','.join(sorted(p))}"
                for path, p in sorted(self.platforms.items())))
        if self.evictions:
            lines.append(f"evictions: {self.evictions} "
                         f"({self.evicted_bytes / 1e9:.3f} GB)")
        if self.refetches:
            lines.append(f"refetches: {self.refetches} "
                         f"({self.refetched_bytes / 1e9:.3f} GB)")
        if self.per_device:
            lines.append(f"{'device':<10}{'tiles':>8}{'GB moved':>10}"
                         f"{'affinity':>10}{'evict':>7}")
            for dev, d in sorted(self.per_device.items()):
                lines.append(f"{'dev' + str(dev):<10}{d.tiles:>8}"
                             f"{d.moved_bytes / 1e9:>10.3f}"
                             f"{d.affinity_hits:>10}{d.evictions:>7}")
        kernel_calls = sum(r.kernel_calls
                           for r in self.per_routine.values())
        if kernel_calls:
            # the venue section appears only once the pallas venue ran,
            # so kernel_path=0 reports are byte-identical to before
            ksec = sum(r.kernel_seconds
                       for r in self.per_routine.values())
            lines.append(f"pallas venue: {kernel_calls} calls "
                         f"({ksec:.3f} s)")
        split_calls = sum(r.split_calls
                          for r in self.per_routine.values())
        if split_calls:
            # precision section appears only once a split scheme ran,
            # so SCILIB_PRECISION-off reports are byte-identical
            ssec = sum(r.split_seconds
                       for r in self.per_routine.values())
            esc = sum(r.escalations for r in self.per_routine.values())
            lines.append(f"split precision: {split_calls} calls "
                         f"({ssec:.3f} s, {esc} escalations)")
        if self.solvers:
            # the solver section appears only once a LAPACK-tier span
            # ran, so solver-free reports are byte-identical to before
            lines.append("solvers (LAPACK tier)")
            lines.append(f"{'solver':<10}{'spans':>7}{'calls':>8}"
                         f"{'panel%':>8}{'GB moved':>10}{'sec':>9}")
            for name, s in sorted(self.solvers.items()):
                pct = 100.0 * s.panel_calls / max(1, s.calls)
                lines.append(f"{name:<10}{s.spans:>7}{s.calls:>8}"
                             f"{pct:>8.0f}{s.moved_bytes / 1e9:>10.3f}"
                             f"{s.seconds:>9.3f}")
        fault_activity = (self.faults + self.retries + self.fallbacks
                          + self.quarantines + self.recoveries)
        if fault_activity:
            # the health section appears only once failure paths ran, so
            # fault-free reports are byte-identical to older releases
            lines.append(f"health: faults={self.faults} "
                         f"retries={self.retries} "
                         f"fallbacks={self.fallbacks} "
                         f"quarantines={self.quarantines} "
                         f"recoveries={self.recoveries}")
            if self.breaker is not None:
                for d, h in enumerate(self.breaker.devices()):
                    lines.append(f"  dev{d}: {h.state} "
                                 f"consecutive={h.consecutive} "
                                 f"failures={h.failures} "
                                 f"quarantines={h.quarantines}")
        if self.callsites is not None and len(self.callsites):
            lines.append("call sites (top by flops; * = adaptive lock)")
            lines.append(f"{'site':<44}{'calls':>7}{'GFLOP':>9}"
                         f"{'decision':>10}{'hit%':>6}{'sec':>9}")
            for p in self.callsites.top_by_flops():
                site = (p.site if len(p.site) <= 43
                        else p.site[:40] + "...")
                lines.append(f"{site:<44}{p.calls:>7}"
                             f"{p.flops / 1e9:>9.2f}"
                             f"{p.decision_label():>10}"
                             f"{100 * p.hit_rate:>6.0f}{p.seconds:>9.3f}")
        return "\n".join(lines)


#: real-FLOP factors per base routine (shared by the access-counter
#: arithmetic-intensity input and the per-site flops accounting)
_FLOP_FACTORS = {
    "gemm": lambda m, n, k: 2.0 * m * n * k,
    "gemv": lambda m, n, k: 2.0 * m * n,
    "trsm": lambda m, n, k: 1.0 * m * m * n,
    "trmm": lambda m, n, k: 1.0 * m * m * n,
    "syrk": lambda m, n, k: 1.0 * n * n * k,
    "herk": lambda m, n, k: 1.0 * n * n * k,
    "symm": lambda m, n, k: 2.0 * m * m * n,
    "hemm": lambda m, n, k: 2.0 * m * m * n,
    "syr2k": lambda m, n, k: 2.0 * n * n * k,
    "her2k": lambda m, n, k: 2.0 * n * n * k,
}


def _flops_of(routine: str, m: int, n: int, k: int, batch: int = 1) -> float:
    """Real-FLOP count, matching :meth:`repro.core.trace.BlasCall.flops`:
    complex multiply-adds cost 4x their real counterparts."""
    fn = _FLOP_FACTORS.get(thr.base_routine(routine))
    if fn is None:
        return 0.0
    mult = 4.0 if routine[:1] in ("c", "z") else 1.0
    return mult * batch * fn(m, n, k)


class SolverSpan:
    """A live LAPACK-tier solver span (``solver_begin`` ..
    ``solver_end``).  While it is the innermost open span, every BLAS
    call the runtime records is stamped with its ``span_id``
    (``"<solver>#<seq>"``), and the factor buffer handed to
    :meth:`OffloadRuntime.solver_begin` stays pinned on the device tier
    for the span's lifetime — the ~780x-reuse pattern of the LSMS
    workload (``apps/lsms.py``) made explicit."""

    __slots__ = ("name", "span_id", "factor", "pinned", "t0", "moved0")

    def __init__(self, name: str, span_id: str, factor, pinned: bool,
                 t0: float, moved0: int):
        self.name = name
        self.span_id = span_id
        self.factor = factor
        self.pinned = pinned
        self.t0 = t0
        self.moved0 = moved0

    def __repr__(self) -> str:
        return f"SolverSpan({self.span_id})"


class OffloadRuntime:
    """Placement + dispatch brain behind the intercepted BLAS surface."""

    def __init__(self, config: Optional[OffloadConfig] = None, *,
                 policy: Optional[str] = None,
                 threshold: Optional[float] = None,
                 record_trace: bool = True,
                 sync: Optional[bool] = None,
                 device_bytes: Optional[int] = None,
                 session_id: str = "",
                 pool: Optional[res.SharedDevicePool] = None):
        # the legacy keyword surface resolves to a config with the
        # historical precedence (env SCILIB_POLICY/THRESHOLD over args,
        # explicit sync/device_bytes args over env); an explicit config
        # is taken as-is — no environment is read after this line.
        if config is None:
            config = OffloadConfig.legacy(policy=policy,
                                          threshold=threshold, sync=sync,
                                          device_bytes=device_bytes)
        self.config = config
        # thread safety (PR 7): the dispatch lock serializes whole
        # calls when several threads adopt one session (Session.scope);
        # the stats lock is a leaf guarding counter updates that can
        # arrive on *another* tenant's thread (shared-pool evictions
        # reach this runtime's stores from whichever thread overflowed
        # the pool).  Order: runtime lock -> health -> store -> pool,
        # with the stats lock a leaf acquired under any of them.
        self._lock = threading.RLock()
        self._stats_lock = threading.Lock()
        self.policy: PolicyBase = make_policy(config.policy)
        self.memspace = memspace.install(
            n_devices=config.resolved_devices())
        self.threshold = config.resolved_threshold()
        self.stats = RuntimeStats()
        self.trace: Optional[Trace] = Trace() if record_trace else None
        self.debug = config.debug
        self.sync_mode = bool(config.sync)
        self.dispatch_cache_enabled = config.dispatch_cache
        # per-call-site profiling (cheap fingerprint; callsite=False
        # turns the whole site layer off) and the adaptive per-site mode
        self.callsite_enabled = config.callsite
        self.adaptive = config.adaptive
        self.adaptive_warmup = config.adaptive_warmup
        # the pallas execution venue (SCILIB_KERNELS): off by default so
        # the two-venue pipeline below stays bit-identical
        self.kernel_path = bool(config.kernel_path)
        self.kernel_block = int(config.kernel_block)
        # split-precision emulation (SCILIB_PRECISION): off by default,
        # keeping the dispatch pipeline bit-identical to native fp64
        self.precision = str(config.precision)
        self.precision_rtol = float(config.precision_rtol)
        self.callsites = cs.CallSiteRegistry()
        self.stats.callsites = self.callsites
        # ordered decision stages: first stage to return a decision wins.
        # Later policy PRs extend dispatch by inserting here, not by
        # editing branches inside blas_call.
        self.decision_stages = [self._stage_adaptive,
                                self._stage_cached,
                                self._stage_threshold]
        # keep the blas-level scalar/kernel caches on the same flag even
        # when a runtime is constructed directly (not via install())
        from repro.core import blas
        blas.refresh_cache_flag(config.dispatch_cache)
        self.device_bytes_cap: Optional[int] = config.device_bytes
        # the residency engine: every registry below is one ResidencyStore
        # (repro.core.residency) — the same class the memtier simulator
        # replays, so live and simulated eviction accounting agree.
        self.evict_policy = config.evict
        self.pin_all = config.pin
        # per-call-site dispatch cache: key -> (offload, n_avg)
        self._decisions: Dict[Hashable, Tuple[bool, float]] = {}
        # placement registry: id(src) -> placed device-tier buffer
        self.placements = res.ResidencyStore(
            "placements", cap=self.device_bytes_cap,
            policy=self.evict_policy, pin_new=self.pin_all,
            on_evict=self._on_placement_evict, emit=self._emit_event)
        # multi-device tile scheduler: one block store per device tier,
        # block key -> placed block, plus the round-robin cursor for
        # blocks with no residency anywhere.
        self.n_devices = int(self.memspace.n_devices)
        self.block_stores = [
            res.ResidencyStore(
                f"dev{d}", cap=self.device_bytes_cap,
                policy=self.evict_policy, pin_new=self.pin_all,
                on_evict=self._block_evict_hook(d), emit=self._emit_event)
            for d in range(self.n_devices)]
        self._rr_cursor = 0
        # tiles assigned to each device within the call being scheduled
        # (tie-breaker: replicated blocks score several devices equally)
        self._sched_load: list = [0] * self.n_devices
        # fault tolerance: deterministic injector (SCILIB_FAULTS), the
        # transient-fault retry policy, and the per-device breaker whose
        # trips invalidate block stores and steer the tile scheduler
        self.faults = flt.FaultInjector.from_spec(config.faults)
        self.retry = flt.RetryPolicy(attempts=config.retries,
                                     backoff_ms=config.backoff_ms)
        self.health = flt.HealthTracker(
            self.n_devices, threshold=config.breaker,
            cooldown_ms=config.breaker_cooldown_ms,
            on_quarantine=self._on_quarantine,
            on_recover=self._on_recover)
        self.stats.breaker = self.health
        # transfer faults inject inside memspace (the real movement call
        # sites); the hook is installed by activate(), never here — a
        # merely-constructed runtime must not clobber the active one's
        # async mode: recent in-flight outputs, drained by sync()
        self._pending: "collections.deque[jax.Array]" = collections.deque(
            maxlen=_PENDING_WINDOW)
        # LAPACK-tier solver spans (repro.solvers): innermost-last stack
        # of open spans; the top span stamps every recorded BLAS call
        self._solver_stack: list = []
        self._solver_seq = 0
        # trace-buffer ids: id(arr) -> trace buffer id (uncapped store:
        # entries live exactly as long as their anchor array)
        self._trace_ids = res.ResidencyStore("traceids")
        self._reuse_by_buffer: Dict[int, int] = {}
        # multi-tenancy: join the shared pool (quota from the config),
        # binding the placement + block stores so their residency charges
        # the pool's per-tenant ledger.  An unnamed pooled session gets
        # an auto-assigned tenant id; unpooled unnamed runtimes keep ""
        # (their trace events serialize exactly as before).
        self.pool = pool
        if pool is not None:
            self.session_id = pool.register(session_id,
                                            quota=config.pool_quota)
            pool.attach(self.session_id, self.placements,
                        *self.block_stores)
        else:
            self.session_id = session_id

    def detach_pool(self) -> None:
        """Leave the shared pool (session close): the tenant's usage is
        forgotten, its lifetime counters stay in the pool totals."""
        if self.pool is not None:
            self.pool.unregister(self.session_id)
            self.pool = None

    # ------------------------------------------------------------------ #
    # safe mid-run reconfiguration (Session.reconfigure lands here)       #
    # ------------------------------------------------------------------ #
    def apply_config(self, new: OffloadConfig) -> None:
        """Apply a new (already validated) config to the live runtime.

        Everything that can change safely changes in place; state the
        change invalidates is flushed rather than left stale:

        * the memoized dispatch cache is always cleared (its entries
          encode threshold decisions),
        * a policy / threshold / adaptive change resets adaptive
          per-site locks (and a policy change also discards the probe
          timings, which were measured under the old policy),
        * residency caps, eviction policy and pinning update on every
          store, with an immediate eviction sweep under a tightened cap.

        The device-tier count is topology, fixed at construction:
        changing it raises ``ValueError`` (open a new session instead).
        """
        with self._lock:
            self._apply_config_locked(new)

    def _apply_config_locked(self, new: OffloadConfig) -> None:
        old = self.config
        if new.resolved_devices() != self.n_devices:
            raise ValueError(
                f"devices cannot change on a live runtime "
                f"({self.n_devices} -> {new.resolved_devices()}); "
                f"open a new session")
        old_threshold = self.threshold
        self.config = new
        self.threshold = new.resolved_threshold()
        self.sync_mode = bool(new.sync)
        self.debug = new.debug
        self.dispatch_cache_enabled = new.dispatch_cache
        self.callsite_enabled = new.callsite
        self.adaptive = new.adaptive
        self.adaptive_warmup = new.adaptive_warmup
        from repro.core import blas
        blas.refresh_cache_flag(new.dispatch_cache)
        self._decisions.clear()
        policy_changed = new.policy != old.policy
        if policy_changed:
            self.policy = make_policy(new.policy)
        kernel_changed = new.kernel_path != old.kernel_path
        precision_changed = (new.precision != old.precision
                             or new.precision_rtol != old.precision_rtol)
        if (policy_changed or self.threshold != old_threshold
                or new.adaptive != old.adaptive or kernel_changed
                or precision_changed):
            for prof in self.callsites:
                prof.locked = None
                prof.locked_why = ""
                prof.locked_venue = ""
                prof.locked_precision = ""
                if policy_changed:     # old timings measured a dead path
                    prof.host_timed = prof.device_timed = 0
                    prof.host_seconds = prof.device_seconds = 0.0
                    prof.host_best = prof.device_best = float("inf")
                if policy_changed or kernel_changed:
                    # kernel-venue samples are only comparable within
                    # one (policy, kernel_path) regime
                    prof.kernel_timed = 0
                    prof.kernel_seconds = 0.0
                    prof.kernel_best = float("inf")
                if policy_changed or precision_changed:
                    # split samples timed one (scheme, rtol) regime
                    prof.split_timed = 0
                    prof.split_seconds = 0.0
                    prof.split_best = float("inf")
                    prof.split_scheme = ""
                    prof.split_venue = ""
                    prof.split_bad = False
        self.kernel_path = bool(new.kernel_path)
        self.kernel_block = int(new.kernel_block)
        self.precision = str(new.precision)
        self.precision_rtol = float(new.precision_rtol)
        self.device_bytes_cap = new.device_bytes
        self.evict_policy = new.evict
        pin_changed = new.pin != self.pin_all
        self.pin_all = new.pin
        for store in (self.placements, *self.block_stores):
            store.cap = new.device_bytes
            store.policy = res.make_eviction_policy(new.evict)
            store.pin_new = new.pin
            if pin_changed:
                # pin=True pins existing residents too; pin=False makes
                # them evictable again (entries pinned under pin-all are
                # indistinguishable from explicit pins, and leaving them
                # pinned would render a newly-set cap unenforceable)
                for key in list(store.keys()):
                    (store.pin if new.pin else store.unpin)(key)
            store.evict_over_cap()
        # fault tolerance: a new spec gets a fresh injector (counters and
        # RNG restart — the spec defines the sequence); the breaker keeps
        # per-device state so reconfiguring knobs cannot un-quarantine a
        # sick device (disabling the breaker does re-admit everything)
        self.faults = flt.FaultInjector.from_spec(new.faults)
        self.retry = flt.RetryPolicy(attempts=new.retries,
                                     backoff_ms=new.backoff_ms)
        self.health.reconfigure(threshold=new.breaker,
                                cooldown_ms=new.breaker_cooldown_ms)
        if active() is self:
            memspace.set_fault_hook(self._transfer_fault_hook())
            memspace.set_debug(new.debug)

    # ------------------------------------------------------------------ #
    # the residency engine: event + eviction hooks, pinning               #
    # ------------------------------------------------------------------ #
    def _emit_event(self, kind: str, store: str, nbytes: int) -> None:
        """Mirror one residency transition into the trace and the
        refetch statistics (place/hit/evict/refetch) — and, through the
        same channel, the fault-tolerance transitions
        (fault/retry/fallback/quarantine/recover).  Shared-pool
        pressure can deliver these on another tenant's thread, so the
        counter updates take the leaf stats lock."""
        if kind == "refetch":
            with self._stats_lock:
                self.stats.refetches += 1
                self.stats.refetched_bytes += nbytes
        if self.trace is not None:
            self.trace.record_event(kind, store, nbytes,
                                    session=self.session_id)

    def _count_link(self, direction: str, nbytes: int,
                    seconds: float) -> None:
        """memspace's link hook: one move over the host link."""
        with self._stats_lock:
            st = self.stats
            if direction == "h2d":
                st.link_h2d_bytes += nbytes
                st.link_h2d_seconds += seconds
            else:
                st.link_d2h_bytes += nbytes
                st.link_d2h_seconds += seconds

    def _on_placement_evict(self, key, placed, nbytes: int) -> None:
        """Cap pressure pushed a placement out: the registry forgets it, so
        the source's next use re-migrates (and is counted again).  JAX
        arrays are immutable: the HBM itself is released once the
        application's own references die — the registry cannot forcibly
        move a borrowed handle."""
        with self._stats_lock:
            self.stats.evictions += 1
            self.stats.evicted_bytes += nbytes
        if self.debug >= 1:
            print(f"[scilib] evict {nbytes} B "
                  f"(resident {self.placements.resident_bytes} B)")

    def _block_evict_hook(self, device: int):
        """Per-device eviction callback for the tile-block stores."""
        def _on_evict(key, placed, nbytes, device=device, self=self):
            with self._stats_lock:
                dst = self.stats.device(device)
                dst.evictions += 1
                dst.evicted_bytes += nbytes
            if self.debug >= 1:
                print(f"[scilib] dev{device} evict block {nbytes} B "
                      f"(resident "
                      f"{self.block_stores[device].resident_bytes} B)")
        return _on_evict

    # ------------------------------------------------------------------ #
    # fault tolerance: guard, retry, fallback, per-device breaker         #
    # ------------------------------------------------------------------ #
    def _transfer_fault_hook(self):
        """The injector's transfer check, as memspace's hook (None when
        injection is off — the hook test stays one pointer compare)."""
        if self.faults is None:
            return None
        inj = self.faults

        def _hook(device, nbytes):
            inj.check("transfer", device=device, nbytes=nbytes)
        return _hook

    def _guarded(self, site: str, fn, *, device: int, nbytes: int,
                 st: RoutineStats):
        """Run one transfer or kernel *unit* under the fault guard.

        The unit is the smallest retryable operation (one block
        movement, one tile kernel): injection happens at its entry,
        before any state mutates, so a fault absorbed by a retry leaves
        every counter and residency structure bit-identical to an
        unfaulted run.  Transient faults retry with exponential backoff
        (``SCILIB_RETRIES`` / ``SCILIB_BACKOFF_MS``); exhaustion or a
        permanent fault records one breaker failure against ``device``
        and raises — :meth:`_execute` turns that into a host fallback.
        """
        attempt = 0
        while True:
            try:
                if site == "kernel" and self.faults is not None:
                    self.faults.check("kernel", device=device,
                                      nbytes=nbytes)
                out = fn()
            except Exception as raw:
                err = flt.classify(site, raw, device=device,
                                   nbytes=nbytes)
                if err is None:       # a bug, not a device fault
                    raise
                self.stats.faults += 1
                self._emit_event("fault", f"{err.kind}@dev{device}",
                                 nbytes)
                if self.debug >= 1:
                    print(f"[scilib] {site} fault on dev{device} "
                          f"(attempt {attempt}): {err}")
                if err.transient and attempt < self.retry.attempts:
                    self.stats.retries += 1
                    st.retries += 1
                    self._emit_event("retry", f"{site}@dev{device}",
                                     nbytes)
                    self.retry.sleep(attempt)
                    attempt += 1
                    continue
                self.health.failure(device)
                if err is raw:
                    raise
                raise err from raw
            else:
                self.health.ok(device)
                return out

    def _on_quarantine(self, device: int) -> None:
        """Breaker trip: invalidate everything resident on the device
        (evict-style events — the next use re-places on a healthy tier)
        and record the transition.  The tile scheduler and the plan
        stage consult ``health.usable`` and re-shard around it."""
        self.stats.quarantines += 1
        invalidated = self.block_stores[device].evict_all()
        if device == 0:
            # the whole-call placement registry is homed on tier 0
            invalidated += self.placements.evict_all()
        self._emit_event("quarantine", f"dev{device}", 0)
        if self.debug >= 1:
            print(f"[scilib] dev{device} quarantined "
                  f"({invalidated} residents invalidated)")

    def _on_recover(self, device: int) -> None:
        """Half-open probe succeeded: the device is healthy again."""
        self.stats.recoveries += 1
        self._emit_event("recover", f"dev{device}", 0)
        if self.debug >= 1:
            print(f"[scilib] dev{device} recovered")

    def device_usable(self, device: int) -> bool:
        """May the scheduler route work to this device tier now?"""
        return self.health.usable(device)

    def _whole_device(self) -> int:
        """Device-tier index the whole-call (unsharded) offload path is
        attributed to: tier 0, or the first usable tier when 0 is
        quarantined (the logical DEVICE put has no index of its own)."""
        if self.n_devices == 1 or self.health.usable(0):
            return 0
        for d in range(1, self.n_devices):
            if self.health.usable(d):
                return d
        return 0

    def _fallback_host(self, call: CallContext,
                       decision: DispatchDecision, st: RoutineStats,
                       exc: flt.OffloadError) -> jax.Array:
        """Retry exhausted (or a permanent fault): run the call on the
        host path — the same jitted arithmetic on the same operand
        values, so the result is bit-identical to an unoffloaded run —
        and surface the decision as ``fallback:<kind>`` in the IR."""
        decision.offload = False
        decision.plan = None
        decision.why = f"fallback:{exc.kind}"
        decision.precision = ""        # the host rerun is native fp64
        self.stats.fallbacks += 1
        st.fallbacks += 1
        st.on_host += 1
        dev = exc.device if exc.device is not None else 0
        self._emit_event("fallback", f"{exc.kind}@dev{dev}", exc.nbytes)
        if self.debug >= 1:
            print(f"[scilib] {call.routine} falling back to host: {exc}")
        return self._run_host(call, st)

    def pin(self, x: jax.Array) -> jax.Array:
        """Pin a buffer on the device tier: place it now if needed and
        mark it never-evictable — it survives arbitrary cap pressure
        until :meth:`unpin` or the buffer dies.  Returns the placed
        device-tier buffer (the pinned residency the next calls hit).
        Pinning is a user-level movement with no fallback path, so it
        opts out of fault injection."""
        with self._lock:
            placed = self.placements.get(id(x))
            if placed is None:
                placed = (x if memspace.tier_of(x) == memspace.DEVICE
                          else memspace.put(x, memspace.DEVICE,
                                            check=False))
                self.placements.put(id(x), placed, placed.nbytes,
                                    anchor=x)
                self.alias_trace_id(x, placed)
            self.placements.pin(id(x))
            return placed

    def unpin(self, x: jax.Array) -> None:
        """Make a pinned buffer evictable again (it stays resident until
        cap pressure actually selects it)."""
        with self._lock:
            self.placements.unpin(id(x))

    def note_uninstrumented(self) -> None:
        """Count one BLAS-shaped call the interceptors saw but could not
        canonicalize (thread-safe: trampolines fire on any thread)."""
        with self._stats_lock:
            self.stats.uninstrumented_calls += 1

    # ------------------------------------------------------------------ #
    # LAPACK-tier solver spans (repro.solvers drives these)               #
    # ------------------------------------------------------------------ #
    def solver_begin(self, name: str, factor=None) -> SolverSpan:
        """Open a solver span: emit the ``solver_begin`` trace event,
        pin the in-place factor buffer for the span's lifetime (the
        factorization re-reads it once per inner BLAS call — the LSMS
        ~780x-reuse pattern), and make the span the stamp for every
        BLAS call recorded until :meth:`solver_end`."""
        with self._lock:
            span_id = f"{name}#{self._solver_seq}"
            self._solver_seq += 1
            nbytes = int(getattr(factor, "nbytes", 0) or 0)
            pinned = False
            if (factor is not None and self.config.policy != "cpu"
                    and isinstance(factor, jax.Array)
                    and not isinstance(factor, jax.core.Tracer)):
                self.pin(factor)
                pinned = True
            self.stats.solver(name).spans += 1
            span = SolverSpan(name, span_id, factor, pinned,
                              time.perf_counter(),
                              self.stats.total_moved_bytes)
            self._solver_stack.append(span)
            self._emit_event("solver_begin", span_id, nbytes)
            return span

    def solver_end(self, span: SolverSpan) -> None:
        """Close a solver span: unpin the factor (it stays resident
        until cap pressure selects it), fold the span's wall time and
        movement delta into the per-solver statistics, and emit the
        ``solver_end`` trace event."""
        with self._lock:
            try:
                self._solver_stack.remove(span)
            except ValueError:
                return                    # already closed (idempotent)
            if span.pinned and span.factor is not None:
                self.unpin(span.factor)
            st = self.stats.solver(span.name)
            st.seconds += time.perf_counter() - span.t0
            st.moved_bytes += max(
                0, self.stats.total_moved_bytes - span.moved0)
            self._emit_event("solver_end", span.span_id, 0)

    def note_panel(self, prec: str, m: int, nb: int, a) -> None:
        """Record one unblocked panel factorization (``getf2`` inside a
        blocked driver), run on the tier of the factor it came from:
        ``offloaded`` when the factored panel ``a`` is on a device tier,
        ``on_host`` when it is on the host.  Panels are recorded only
        inside a solver span: outside the LAPACK tier the drivers emit
        exactly the BLAS stream they always did, keeping pre-solver
        traces and counters byte-identical."""
        with self._lock:
            if not self._solver_stack:
                return
            span = self._solver_stack[-1]
            sst = self.stats.solver(span.name)
            sst.calls += 1
            sst.panel_calls += 1
            rst = self.stats.routine(f"{prec}getf2")
            rst.calls += 1
            on_device = memspace.tier_of(a) == memspace.DEVICE
            if on_device:
                rst.offloaded += 1
            else:
                rst.on_host += 1
            if self.trace is not None:
                bid = self._trace_id(a, "P")
                el = a.dtype.itemsize
                from repro.core.trace import BlasCall
                self.trace.calls.append(BlasCall(
                    routine=f"{prec}getf2", m=m, n=nb, k=0,
                    operands=(("P", bid, m * nb * el, float(nb), True),),
                    venue="xla" if on_device else "host",
                    solver_id=span.span_id))

    def resident_bytes(self) -> int:
        return self.placements.resident_bytes

    # ------------------------------------------------------------------ #
    # multi-device block stores + tile scheduler                          #
    # ------------------------------------------------------------------ #
    def next_device(self) -> int:
        """Round-robin cursor for blocks with no residency anywhere.
        Quarantined devices are skipped; with every device quarantined
        the cursor value is returned anyway (callers only reach here
        when the degraded-mode check has already allowed offload)."""
        for _ in range(self.n_devices):
            dev = self._rr_cursor % self.n_devices
            self._rr_cursor += 1
            if self.health.usable(dev):
                return dev
        return dev

    def scheduled_load(self, device: int) -> int:
        """Tiles already assigned to a device in the call being
        scheduled (the affinity tie-breaker)."""
        return self._sched_load[device]

    def device_resident_bytes(self, device: int) -> int:
        return self.block_stores[device].resident_bytes

    def _place_block(self, device: int, op: TileOp,
                     st: RoutineStats) -> Tuple[jax.Array, int, bool]:
        """Materialize one operand block on one device tier.

        Returns (placed block, bytes moved, affinity hit).  Persistent
        policies (DFU/counter/pinned) register the block so later calls
        find it resident; Mem-Copy stages fresh every call.  The actual
        movement runs under the fault guard — a retried block put is a
        perfect no-op (cache hits return above and never see it)."""
        key = op.key()
        store = self.block_stores[device]
        persistent = self.policy.persistent
        if persistent:
            cached = store.get(key)
            if cached is not None:
                return cached, 0, True
        block = op.materialize()
        placed = self._guarded(
            "transfer", lambda: memspace.put_block(block, device),
            device=device, nbytes=op.nbytes, st=st)
        # a no-op put (block already home on this device, e.g. a chained
        # output reused whole) moved nothing — keep the stats honest
        moved = 0 if placed is block else op.nbytes
        if persistent:
            store.put(key, placed, placed.nbytes, anchor=op.parent)
        return placed, moved, False

    def _sharded_call(self, call: CallContext, st: RoutineStats,
                      plan: TilePlan,
                      ) -> Tuple[jax.Array, Tuple[int, ...]]:
        """Execute one call as scheduled tiles and gather the output.

        Device choice is the policy's (:meth:`PolicyBase.select_device`):
        affinity first — the device already holding the most operand-block
        bytes — then round-robin.  Output blocks are registered on their
        device so the next call slicing the gathered result at the same
        coordinates reuses them for free (the BLASX chained-call path)."""
        # Phase 1 — schedule every tile against the residency state at
        # call entry, so blocks placed by the first tiles of this call
        # cannot gravitationally pull the rest onto one device.
        self._sched_load = [0] * self.n_devices
        devices = []
        for tile in plan.tiles:
            dev = self.policy.select_device(
                self, [(op.key(), op.nbytes, op.shared) for op in tile.ops])
            self._sched_load[dev] += 1
            devices.append(dev)
        # Phase 2 — place blocks and run the tile kernels.
        site = call.site
        outs = []
        for tile, dev in zip(plan.tiles, devices):
            dst = self.stats.device(dev)
            placed = []
            with self._placing(call):
                for op in tile.ops:
                    arr, moved, hit = self._place_block(dev, op, st)
                    st.bytes_in += moved
                    dst.moved_bytes += moved
                    st.cache_hits += int(hit)
                    st.cache_misses += int(not hit)
                    dst.affinity_hits += int(hit)
                    if site is not None:
                        site.observe_residency(hit)
                    placed.append(arr)
            with trc.span(trc.SUBMIT):
                outs.append(self._guarded(
                    "kernel", lambda t=tile, p=placed: t.compute(*p),
                    device=dev, nbytes=0, st=st))
            dst.tiles += 1
        # tiles computed on several real devices meet on the whole-call
        # device before the gather (one op never spans devices)
        with trc.span(trc.SUBMIT):
            out = memspace.uncommitted(
                plan.gather([memspace.gather_home(o) for o in outs]))
        if self.policy.persistent:
            for tile, dev, block in zip(plan.tiles, devices, outs):
                self.block_stores[dev].put(
                    (id(out),) + tile.out_coords, block, block.nbytes,
                    anchor=out)
        if self.policy.copy_back:
            st.bytes_out += out.nbytes
            with self._placing(call):
                out = memspace.put(out, memspace.HOST)
        st.offloaded += 1
        st.sharded += 1
        st.tiles += len(plan.tiles)
        return out, tuple(devices)

    # ------------------------------------------------------------------ #
    # async mode                                                          #
    # ------------------------------------------------------------------ #
    def sync(self) -> "OffloadRuntime":
        """Block until every tracked in-flight result is materialized
        (XLA executes in submission order, so draining the recent window
        fences everything submitted before it).

        Exception-safe: a failed buffer never leaves later buffers
        undrained.  Every pending result is awaited; the first error is
        re-raised with later ones attached as ``__notes__`` (and logged
        under ``SCILIB_DEBUG``) rather than silently dropped."""
        first: Optional[BaseException] = None
        extras: list = []
        with self._lock:
            pending = list(self._pending)
            self._pending.clear()
        for buf in pending:
            try:
                buf.block_until_ready()
            except Exception as exc:
                if first is None:
                    first = exc
                else:
                    extras.append(exc)
        if first is not None:
            for i, exc in enumerate(extras):
                note = f"sync: also failed ({i + 2}/{len(extras) + 1}): " \
                       f"{type(exc).__name__}: {exc}"
                if hasattr(first, "add_note"):   # py3.11+
                    first.add_note(note)
                if self.debug >= 1:
                    print(f"[scilib] {note}")
            raise first
        return self

    # ------------------------------------------------------------------ #
    # trace buffer identity                                               #
    # ------------------------------------------------------------------ #
    def _trace_id(self, x: jax.Array, name: str = "") -> int:
        if self.trace is None:
            return -1
        bid = self._trace_ids.get(id(x))
        if bid is not None:
            return bid
        bid = self.trace.new_buffer(x.nbytes, name)
        self._trace_ids.put(id(x), bid, x.nbytes, anchor=x)
        return bid

    def alias_trace_id(self, src: jax.Array, dst: jax.Array) -> None:
        """Source and its device placement are the same logical buffer."""
        if self.trace is None or id(dst) in self._trace_ids:
            return
        bid = self._trace_ids.get(id(src))
        if bid is None:
            return
        self._trace_ids.put(id(dst), bid, dst.nbytes, anchor=dst)

    # ------------------------------------------------------------------ #
    # the intercepted-call entry point: the staged dispatch pipeline      #
    # ------------------------------------------------------------------ #
    def blas_call(self, routine: str, m: int, n: int, k: int,
                  operands: Sequence[Tuple[str, jax.Array, float, bool]],
                  compute: Callable[..., jax.Array],
                  batch: int = 1,
                  key: Optional[Hashable] = None,
                  shard: Optional[Callable[[int], Optional[TilePlan]]] = None,
                  kernel_compute: Optional[Callable[..., jax.Array]] = None,
                  split_compute: Optional[Callable] = None,
                  split_check: Optional[Callable] = None,
                  ) -> jax.Array:
        """Run one level-3 BLAS call through the dispatch pipeline:

            canonicalize -> decide -> plan -> execute -> record

        ``operands``: (role, array, device_reads_per_elem, written) — the
        same metadata the memtier access-counter model consumes.
        ``compute``: jit-compiled arithmetic taking the placed operand
        arrays in order.
        ``key``: hashable call-shape identity ``(routine, m, n, k, batch,
        dtype, flags)``; when given, the offload decision is memoized in
        the dispatch cache.
        ``shard``: optional tile-plan builder ``n_devices -> TilePlan``;
        consulted only when the call offloads and more than one device
        tier exists, so the single-device fast path never pays for it.
        ``kernel_compute``: the pallas-venue arithmetic (same placed
        operand order as ``compute``); consulted only under
        ``kernel_path`` — None means "no kernel for this routine" and
        the venue resolution falls back to the generic XLA offload.
        ``split_compute``: split-precision factory ``(scheme, venue) ->
        compute`` and ``split_check``: sampled-residual estimator
        ``(out, *arrays) -> rel error``; both consulted only under
        ``SCILIB_PRECISION`` — None means the call has no split
        formulation and always runs native.

        Thread-safe: the whole pipeline runs under the runtime lock, so
        several threads adopting one session (``Session.scope``) issue
        calls atomically — counters never lose updates and the decision
        cache never observes a half-written entry.  The single-threaded
        cost is one uncontended reentrant acquire per call.
        """
        with self._lock:
            return self._blas_call_locked(routine, m, n, k, operands,
                                          compute, batch, key, shard,
                                          kernel_compute, split_compute,
                                          split_check)

    def _blas_call_locked(self, routine, m, n, k, operands, compute,
                          batch, key, shard, kernel_compute,
                          split_compute=None,
                          split_check=None) -> jax.Array:
        st = self.stats.routine(routine)
        st.calls += 1
        arrays = [op[1] for op in operands]

        if any(isinstance(a, jax.core.Tracer) for a in arrays):
            # Inside jit/grad tracing there is no runtime placement to do;
            # the offload decision is static and the compute fn embeds it.
            return compute(*arrays)

        t = time.perf_counter()
        with trc.span(trc.DECIDE):
            call = self._canonicalize(routine, m, n, k, operands, arrays,
                                      compute, batch, key, shard,
                                      kernel_compute, split_compute,
                                      split_check)
            decision = self._decide(call, st)
        t0 = time.perf_counter()
        call.decide_s = t0 - t
        self._stage_plan(call, decision)
        out, devices = self._execute(call, decision, st)
        if decision.precision and decision.offload:
            # sampled-residual check + escalation; inside the timed
            # window so probe samples bill what the scheme really costs
            with trc.span(trc.SUBMIT):
                out = self._verify_split(call, decision, st, out)
        if self.sync_mode or decision.timed:
            # adaptive probes always block: path timing needs wall time
            out.block_until_ready()
        else:
            # retire finished results first so the window never pins
            # buffers the application has already dropped
            pend = self._pending
            while pend and pend[0].is_ready():
                pend.popleft()
            pend.append(out)
        dt = time.perf_counter() - t0
        self._record(call, decision, out, devices, dt, st)
        return out

    # ------------------------------------------------------------------ #
    # stage 1 — canonicalize: bundle the call, fingerprint the site       #
    # ------------------------------------------------------------------ #
    def _canonicalize(self, routine, m, n, k, operands, arrays, compute,
                      batch, key, shard, kernel_compute=None,
                      split_compute=None, split_check=None) -> CallContext:
        call = CallContext(routine=routine, m=m, n=n, k=k, batch=batch,
                           operands=operands, arrays=arrays,
                           compute=compute, key=key, shard=shard,
                           kernel_compute=kernel_compute,
                           split_compute=split_compute,
                           split_check=split_check)
        if self.callsite_enabled:
            call.site_id = cs.fingerprint(routine)
            call.site = self.callsites.profile(call.site_id)
        return call

    # ------------------------------------------------------------------ #
    # stage 2 — decide: ordered stages emit the DispatchDecision IR       #
    # ------------------------------------------------------------------ #
    def _decide(self, call: CallContext, st: RoutineStats) -> DispatchDecision:
        decision = None
        for stage in self.decision_stages:
            decision = stage(call, st)
            if decision is not None:
                break
        if decision.offload and not self.policy.offloads:
            decision.offload = False
            decision.why = "policy:host-only"
        if decision.offload and call.arrays[0].dtype.name in (
                _HOST_ONLY_DTYPES.get(self.memspace.device_platform, ())):
            decision.offload = False
            decision.why = "dtype:host-only"
        if decision.offload and not self.health.any_usable():
            # degraded mode: every device tier quarantined — keep
            # serving on the host path until a half-open probe readmits
            decision.offload = False
            decision.why = "fallback:quarantined"
            self.stats.fallbacks += 1
            st.fallbacks += 1
            self._emit_event("fallback", "quarantined", 0)
        self._resolve_precision(call, decision)
        self._resolve_venue(call, decision)
        return decision

    def _resolve_precision(self, call: CallContext,
                           decision: DispatchDecision) -> None:
        """Stage 2a — precision: which numeric formulation runs the
        decided path.  A no-op with ``SCILIB_PRECISION`` off
        (``precision`` stays ``""``, keeping the classic pipeline
        bit-identical).  Runs before the venue resolution because a
        split call is pallas-eligible where a native fp64 call is not.
        Adaptive decisions arrive with their precision already chosen
        by the probe schedule / lock and are left alone (a site that
        locked native must not be re-split here)."""
        if not self.precision:
            return
        if not decision.offload or call.split_compute is None:
            # host path (incl. policy/health vetoes of a split probe)
            # always runs native fp64
            decision.precision = ""
            return
        if decision.precision or decision.why.startswith("adaptive"):
            return
        scheme = prec.choose(self.precision,
                             thr.base_routine(call.routine),
                             call.k or call.m, self.precision_rtol)
        if scheme:
            decision.precision = scheme
            decision.why += f"+{scheme}"

    def _resolve_venue(self, call: CallContext,
                       decision: DispatchDecision) -> None:
        """Stage 2b — venue: which execution engine runs the decided
        path.  A no-op with ``kernel_path`` off (``venue`` stays ``""``,
        keeping the classic pipeline bit-identical).  Runs after the
        policy/health vetoes so a vetoed call is always ``host``; an
        adaptive decision arrives with its venue already chosen by the
        probe schedule / lock and is left alone."""
        if not self.kernel_path:
            return
        if not decision.offload:
            decision.venue = "host"
            return
        if decision.venue:
            return                      # adaptive stage already chose
        if (call.kernel_compute is not None
                or (decision.precision
                    and call.split_compute is not None)):
            # a split fp64 call is pallas-eligible even though native
            # fp64 has no kernel: its slice passes run the fp32 kernel
            decision.venue = "pallas"
            decision.why += "+kernel"
        else:
            decision.venue = "xla"

    def _stage_adaptive(self, call: CallContext,
                        st: RoutineStats) -> Optional[DispatchDecision]:
        """Per-site adaptive mode (``SCILIB_ADAPTIVE=1``): probe the
        first N calls at each site on both paths, then lock the faster
        decision — the paper's warmup-then-patch behaviour."""
        if not self.adaptive or call.site is None:
            return None
        site = call.site
        # with kernel_path on and a kernel for this routine, the warmup
        # rotates over three venues instead of two; the decision carries
        # the venue so execute/record stay stage-agnostic
        racing = self.kernel_path and call.kernel_compute is not None
        if site.locked is not None:
            # locked fast path: no threshold math, no N_avg derivation —
            # the warmup already captured the site's size distribution
            st.dispatch_hits += 1
            return DispatchDecision(
                site.locked, n_avg=0.0, why="adaptive:locked",
                venue=site.locked_venue if self.kernel_path else "",
                precision=site.locked_precision)
        nav = (thr.n_avg(call.routine, call.m, call.n, call.k)
               * (max(1, call.batch) ** (1.0 / 3.0)))
        if site.probes_done >= self.adaptive_warmup:
            locked = site.lock()
            if self.debug >= 1:
                label = (site.locked_venue if self.kernel_path
                         else ("offload" if locked else "host"))
                if site.locked_precision:
                    label += f"~{site.locked_precision}"
                print(f"[scilib] adaptive lock {site.site}: "
                      f"{label} ({site.locked_why})")
            if self.kernel_path:
                self._emit_event("venue",
                                 f"{site.locked_venue}:{site.site}", 0)
            st.dispatch_hits += 1
            return DispatchDecision(
                locked, n_avg=nav, why="adaptive:locked",
                venue=site.locked_venue if self.kernel_path else "",
                precision=site.locked_precision)
        st.dispatch_misses += 1
        # with SCILIB_PRECISION set and a split formulation available,
        # the warmup additionally races the split variant like a venue
        split_scheme = ""
        if self.precision and call.split_compute is not None:
            split_scheme = prec.choose(
                self.precision, thr.base_routine(call.routine),
                call.k or call.m, self.precision_rtol)
        venue = site.probe_venue(3 if racing else 2,
                                 split=bool(split_scheme))
        if venue == "split":
            # venue stays "" here; _resolve_venue picks xla or pallas
            return DispatchDecision(True, n_avg=nav,
                                    why="adaptive:probe", timed=True,
                                    precision=split_scheme)
        return DispatchDecision(venue != "host", n_avg=nav,
                                why="adaptive:probe", timed=True,
                                venue=venue if self.kernel_path else "")

    def _stage_cached(self, call: CallContext,
                      st: RoutineStats) -> Optional[DispatchDecision]:
        """The memoized dispatch cache (fast path): one threshold
        derivation per call shape, two dict lookups thereafter."""
        if call.key is None or not self.dispatch_cache_enabled:
            return None
        dec = self._decisions.get(call.key)
        if dec is None:
            dec = thr.should_offload(call.routine, call.m, call.n, call.k,
                                     threshold=self.threshold,
                                     batch=call.batch)
            if len(self._decisions) > _DECISION_CACHE_LIMIT:
                self._decisions.clear()   # dynamic-shape churn guard
            self._decisions[call.key] = dec
            st.dispatch_misses += 1
            return DispatchDecision(dec[0], n_avg=dec[1], why="threshold")
        st.dispatch_hits += 1
        return DispatchDecision(dec[0], n_avg=dec[1], why="cache")

    def _stage_threshold(self, call: CallContext,
                         st: RoutineStats) -> DispatchDecision:
        """Terminal stage: derive the threshold rule per call (paper
        §3.3); reached when the key is unhashable or caching is off."""
        st.dispatch_misses += 1
        offload, nav = thr.should_offload(call.routine, call.m, call.n,
                                          call.k, threshold=self.threshold,
                                          batch=call.batch)
        return DispatchDecision(offload, n_avg=nav, why="threshold")

    # ------------------------------------------------------------------ #
    # stage 3 — plan: consult the multi-device tile planner               #
    # ------------------------------------------------------------------ #
    def _stage_plan(self, call: CallContext,
                    decision: DispatchDecision) -> DispatchDecision:
        n_avail = self.health.usable_count()
        if (decision.offload and call.shard is not None
                and n_avail > 1 and self.policy.shardable):
            kw = {}
            if self.kernel_path and decision.venue == "pallas":
                # sharded tiles follow the venue selection too: the tile
                # kernels run the pallas path, under the same _guarded
                # fault units as any tile
                kw["venue"] = "pallas"
            if decision.precision and call.split_compute is not None:
                # split tiles: the same tile geometry, the tile kernels
                # run the split passes (precision-aware shard builders
                # exist exactly when split_compute does)
                kw["precision"] = decision.precision
            decision.plan = (call.shard(n_avail, **kw) if kw
                             else call.shard(n_avail))
        return decision

    # ------------------------------------------------------------------ #
    # stage 4 — execute: host, whole-call offload, or sharded tiles       #
    # ------------------------------------------------------------------ #
    def _execute(self, call: CallContext, decision: DispatchDecision,
                 st: RoutineStats) -> Tuple[jax.Array, Tuple[int, ...]]:
        if not decision.offload:
            st.on_host += 1
            return self._run_host(call, st), ()
        try:
            if decision.plan is not None:
                return self._sharded_call(call, st, decision.plan)
            return self._offload_whole(call, decision, st), ()
        except flt.OffloadError as exc:
            return self._fallback_host(call, decision, st, exc), ()

    def _offload_whole(self, call: CallContext,
                       decision: DispatchDecision,
                       st: RoutineStats) -> jax.Array:
        """Single-device offload: the policy places every operand.
        Each operand movement and the kernel launch are separate
        guarded units, attributed to the whole-call device tier."""
        dev = self._whole_device()
        with self._placing(call):
            args = call.placed = self._place_operands(call, st, dev)
        # venue selection: the pallas-venue arithmetic replaces the
        # generic jitted compute inside the *same* guarded kernel unit,
        # so injection, retries and breaker trips cover it identically.
        # A split decision swaps in the split formulation the same way
        # (bound to the decided scheme and venue).
        if decision.precision and call.split_compute is not None:
            compute = call.split_compute(decision.precision,
                                         decision.venue)
        elif (decision.venue == "pallas"
                and call.kernel_compute is not None):
            compute = call.kernel_compute
        else:
            compute = call.compute
        with trc.span(trc.SUBMIT):
            out = self._guarded("kernel", lambda: compute(*args),
                                device=dev, nbytes=0, st=st)
        with self._placing(call):
            out_p = self._guarded(
                "transfer", lambda: self.policy.place_output(self, out),
                device=dev, nbytes=out.nbytes, st=st)
        st.bytes_out += out_p.moved_bytes
        st.offloaded += 1
        return out_p.array

    @staticmethod
    @contextlib.contextmanager
    def _placing(call: CallContext):
        """A placement stage of ``call``: under its span, its host
        seconds added to the call's ``place_s``."""
        t = time.perf_counter()
        try:
            with trc.span(trc.PLACE):
                yield
        finally:
            call.place_s += time.perf_counter() - t

    def _place_operands(self, call: CallContext, st: RoutineStats,
                        dev: int) -> list:
        """The policy places every operand of a whole-call offload,
        each movement a guarded unit attributed to device tier ``dev``;
        returns them harmonized onto the device tier."""
        site = call.site
        placed, budget_used = [], 0
        ai = self._arith_intensity(call.routine, call.m, call.n, call.k,
                                   call.arrays, call.batch)
        for (role, x, reads, written) in call.operands:
            if isinstance(self.policy, CounterPolicy):
                p = self._guarded(
                    "transfer",
                    lambda x=x, r=reads, w=written, b=budget_used:
                        self.policy.place_operand(
                            self, x, reads_per_elem=r, written=w,
                            ai=ai, budget_used=b),
                    device=dev, nbytes=x.nbytes, st=st)
            else:
                p = self._guarded(
                    "transfer",
                    lambda x=x: self.policy.place_operand(self, x),
                    device=dev, nbytes=x.nbytes, st=st)
            budget_used += p.moved_bytes
            st.bytes_in += p.moved_bytes
            st.cache_hits += int(p.cache_hit)
            st.cache_misses += int(not p.cache_hit)
            if site is not None:
                site.observe_residency(p.cache_hit)
            if p.cache_hit:
                self._count_reuse(x)
            if p.moved_bytes or p.cache_hit:
                self.alias_trace_id(x, p.array)
            placed.append(p.array)
        # harmonize outside the kernel guard: a retried kernel must not
        # re-bill transient streaming bytes
        return self._harmonize(placed, st)

    def _verify_split(self, call: CallContext,
                      decision: DispatchDecision, st: RoutineStats,
                      out: jax.Array) -> jax.Array:
        """Post-execution escalation check of a split result.

        The sampled residual (one O(n^2) fp64 matvec chain against the
        O(n^3) call) estimates the *forward* relative error; a result
        exceeding ``precision_rtol`` — catastrophic cancellation, an
        ill-conditioned triangle — is discarded and the call reruns
        native fp64, so accuracy degradation is bounded, never silent.
        The check materializes the result (the split path trades the
        async window for the guarantee).  Without ``split_check`` the
        a-priori bound already fit ``rtol`` at resolve time and the
        result stands."""
        if call.split_check is None:
            return out
        placed = call.placed or self._harmonize(call.arrays)
        rel = float(call.split_check(out, *placed))
        if rel <= self.precision_rtol:
            return out
        st.escalations += 1
        decision.why += "+esc"
        self._emit_event("escalate",
                         f"{decision.precision}:{call.routine}", 0)
        if call.site is not None:
            # a site whose scheme misses its bound must never lock it
            call.site.split_bad = True
        if self.debug >= 1:
            print(f"[scilib] {call.routine} {decision.precision} "
                  f"residual {rel:.2e} > rtol {self.precision_rtol:.2e}"
                  f" -> native fp64")
        return call.compute(*(call.placed or self._harmonize(call.arrays)))

    # ------------------------------------------------------------------ #
    # stage 5 — record: statistics, site profile, trace                   #
    # ------------------------------------------------------------------ #
    def _record(self, call: CallContext, decision: DispatchDecision,
                out: jax.Array, devices: Tuple[int, ...], dt: float,
                st: RoutineStats) -> None:
        st.seconds += dt
        st.decide_seconds += call.decide_s
        st.place_seconds += call.place_s
        path = "offload" if decision.offload else "host"
        plats = self.stats.platforms.setdefault(path, set())
        plats.update(d.platform for d in out.devices())
        if decision.offload and decision.venue == "pallas":
            st.kernel_calls += 1
            st.kernel_seconds += dt
        if decision.offload and decision.precision:
            st.split_calls += 1
            st.split_seconds += dt
        site = call.site
        if site is not None:
            if decision.timed:
                site.observe_probe(decision.offload, dt,
                                   venue=decision.venue,
                                   precision=decision.precision)
            site.observe(decision.n_avg,
                         _flops_of(call.routine, call.m, call.n, call.k,
                                   call.batch),
                         dt, decision.offload, venue=decision.venue,
                         precision=decision.precision)
        solver_id = ""
        if self._solver_stack:
            span = self._solver_stack[-1]
            solver_id = span.span_id
            self.stats.solver(span.name).calls += 1
        self._record_trace(call.routine, call.m, call.n, call.k,
                           call.operands, out, call.batch, devices,
                           site_id=call.site_id, seconds=dt,
                           decide_seconds=call.decide_s,
                           place_seconds=call.place_s,
                           venue=decision.venue,
                           precision=decision.precision,
                           solver_id=solver_id)
        if self.debug >= 2:
            where = "host" if not decision.offload else (
                f"shard[{len(devices)} tiles]" if devices else
                (decision.venue or "offload"))
            print(f"[scilib] {call.routine} m={call.m} n={call.n} "
                  f"k={call.k} navg={decision.n_avg:.0f} {where} "
                  f"({decision.why})")

    # ------------------------------------------------------------------ #
    def _run_host(self, call: CallContext, st: RoutineStats) -> jax.Array:
        """The host path: every operand on the HOST tier, the arithmetic
        on the host CPU, the result left there (the paper's CPU BLAS)."""
        with trc.span(trc.HOST_CALL):
            out = call.compute(*self._harmonize(call.arrays, st,
                                                memspace.HOST))
            return memspace.put(out, memspace.HOST, check=False)

    def _harmonize(self, arrays, st: Optional[RoutineStats] = None,
                   tier: str = memspace.DEVICE) -> list:
        """Execution-space harmonization: one op never mixes placements,
        so operands outside ``tier`` are streamed in transiently (GH200's
        coherent remote read, made explicit) and billed to ``st`` when
        given.  The placement registry is untouched.  Transient
        streaming is not a placement decision (and the host paths run
        through here): it never injects faults."""
        out = []
        for a in arrays:
            if memspace.tier_of(a) != tier:
                if st is not None:
                    st.transient_bytes += a.nbytes
                a = memspace.put(a, tier, check=False)
            out.append(a)
        return out

    # ------------------------------------------------------------------ #
    def _count_reuse(self, x: jax.Array) -> None:
        bid = self._trace_ids.get(id(x))
        if bid is not None:
            self._reuse_by_buffer[bid] = self._reuse_by_buffer.get(bid, 0) + 1

    def mean_buffer_reuse(self) -> float:
        if not self._reuse_by_buffer:
            return 0.0
        return sum(self._reuse_by_buffer.values()) / len(self._reuse_by_buffer)

    @staticmethod
    def _arith_intensity(routine, m, n, k, arrays, batch) -> float:
        nbytes = sum(a.nbytes for a in arrays)
        return _flops_of(routine, m, n, k, batch) / max(1, nbytes)

    def _record_trace(self, routine, m, n, k, operands, out, batch,
                      devices=(), site_id: str = "",
                      seconds: float = 0.0, decide_seconds: float = 0.0,
                      place_seconds: float = 0.0, venue: str = "",
                      precision: str = "", solver_id: str = "") -> None:
        if self.trace is None:
            return
        ops = []
        for (role, x, reads, written) in operands:
            bid = self._trace_id(x, role)
            ops.append((role, bid, x.nbytes // max(1, batch), reads, written))
        # the output aliases the written operand's logical buffer; a
        # fresh output gets its own buffer and is recorded on the call,
        # so replay can account its device-born residency like the live
        # placement store does
        out_buf, out_nbytes = -1, 0
        for (role, x, reads, written) in operands:
            if written:
                self.alias_trace_id(x, out)
                break
        else:
            out_buf = self._trace_id(out, "OUT")
            out_nbytes = out.nbytes
        from repro.core.trace import BlasCall
        self.trace.calls.append(BlasCall(
            routine=routine, m=m, n=n, k=k, batch=batch,
            operands=tuple(ops), devices=tuple(devices),
            callsite_id=site_id, seconds=seconds,
            decide_seconds=decide_seconds, place_seconds=place_seconds,
            out_buf=out_buf, out_nbytes=out_nbytes, venue=venue,
            precision=precision, solver_id=solver_id))


# --------------------------------------------------------------------- #
# context-local active runtime (what LD_PRELOAD init/fini manage in C;   #
# context-local so concurrent sessions in different threads each see     #
# their own dispatch target, never a neighbour's)                        #
# --------------------------------------------------------------------- #
_ACTIVE: ContextVar[Optional[OffloadRuntime]] = (
    ContextVar("scilib_active_runtime", default=None))


def activate(runtime: Optional[OffloadRuntime]) -> None:
    """Make ``runtime`` the dispatch target of the *current* context
    (None deactivates).  The session layer drives this; application
    code opens sessions instead.  The memspace fault hook follows the
    active runtime, so a nested session's injector never outlives its
    activation."""
    _ACTIVE.set(runtime)
    if runtime is None:
        memspace.set_fault_hook(None)
        memspace.set_link_hook(None)
        memspace.set_debug(0)
    else:
        memspace.set_fault_hook(runtime._transfer_fault_hook())
        memspace.set_link_hook(runtime._count_link)
        memspace.set_debug(runtime.debug)


def install(policy: Optional[str] = None,
            threshold: Optional[float] = None,
            record_trace: bool = True, sync: Optional[bool] = None,
            device_bytes: Optional[int] = None,
            config: Optional[OffloadConfig] = None) -> OffloadRuntime:
    """`.init_array` analogue, now a shim over an implicit
    :class:`~repro.core.session.Session` (without symbol interception —
    the dlsym-mode surface).  Behavior-identical to the pre-session
    global: env knobs are honored through
    :meth:`OffloadConfig.legacy`, and the created runtime becomes the
    active dispatch target.  An explicit ``config`` bypasses the legacy
    resolution (and the environment) entirely."""
    from repro.core import session as ses
    if config is None:
        config = OffloadConfig.legacy(policy=policy, threshold=threshold,
                                      sync=sync, device_bytes=device_bytes)
    return ses.open_legacy(config, record_trace=record_trace,
                           intercept=False).runtime


def uninstall() -> Optional[RuntimeStats]:
    """`.fini_array` analogue: drain in-flight work, deactivate, and
    return final statistics.  With ``SCILIB_TRACE=/path.json`` set (or
    ``config.trace_path``), the recorded trace is dumped — traces for
    the autotuner need no code changes, mirroring the paper tool's
    no-recompile ethos."""
    from repro.core import session as ses
    return ses.close_legacy()


def active() -> Optional[OffloadRuntime]:
    return _ACTIVE.get()


def pin(x: jax.Array) -> jax.Array:
    """Pin a buffer on the active runtime's device tier (no-op when no
    runtime is installed).  See :meth:`OffloadRuntime.pin`."""
    rt = _ACTIVE.get()
    return x if rt is None else rt.pin(x)


def unpin(x: jax.Array) -> None:
    """Release a :func:`pin` (no-op when no runtime is installed)."""
    rt = _ACTIVE.get()
    if rt is not None:
        rt.unpin(x)
