"""First-class offload sessions: a runtime you hold, not a process global.

The paper's tool is necessarily process-global — an ``LD_PRELOAD``
interposer has exactly one ``.init_array``/``.fini_array`` lifecycle.
The reproduction inherited that shape (``install()``/``uninstall()``
flipping one module-level runtime configured by ambient env vars), and
it is the main obstacle to the ROADMAP's serve-many-workloads goal:
two workloads in one process cannot hold different thresholds, caps, or
policies, and nothing isolates their statistics.

A :class:`Session` owns the full offload stack for one workload:

* its :class:`~repro.core.runtime.OffloadRuntime` (placement registry,
  dispatch pipeline, statistics, trace),
* the installed interceptors (``jnp.dot``/``matmul``/``einsum``/
  ``tensordot`` trampolines — patched while at least one intercepting
  session is open, refcounted),
* its :class:`~repro.core.config.OffloadConfig` — typed, validated,
  serializable; no env vars read after construction.

Sessions **nest via a stack**: the innermost open session's runtime is
the active dispatch target (its config wins), and closing it restores
the outer session — so a library can open a scoped session with its own
tuned config inside an application's long-lived one:

    import repro

    with repro.session(OffloadConfig.load("tuned.json")) as s:
        ...                      # dispatched under the tuned config
        print(s.report())

Long-lived use is the same object without ``with``: ``s =
repro.session(cfg)`` ... ``s.close()``.  Mid-run changes go through
:meth:`Session.reconfigure`, which flushes the dispatch cache and the
adaptive locks the change invalidates instead of leaving stale
decisions behind.

The legacy surface (``repro.core.install``/``uninstall``/``offload``)
is now a thin shim over an implicit default session — behavior-identical
(the parity tests assert decisions, counters and report output match),
but everything it did is expressible, and testable, as objects.

An ``atexit`` hook dumps the recorded trace of any session still open
at interpreter shutdown to its ``config.trace_path`` — traces are no
longer lost when a process exits without ``uninstall()``/``close()``.
"""
from __future__ import annotations

import atexit
import contextlib
import threading
from contextvars import ContextVar
from typing import List, Optional, Tuple

from repro.core import trace as trc
from repro.core.config import OffloadConfig

__all__ = ["Session", "session", "active_session"]

#: innermost-last stack of open sessions (the nesting discipline).
#: Context-local (PR 7): each thread nests its own sessions; one
#: thread's open/close can never corrupt another thread's restore
#: order.  The stack is an immutable tuple — push/pop replace it
#: wholesale, so a reader never observes a half-mutated stack.
_STACK: ContextVar[Tuple["Session", ...]] = (
    ContextVar("scilib_session_stack", default=()))

#: all open sessions process-wide, for the atexit trace-dump fallback
#: (context-local stacks are invisible across threads; shutdown isn't).
_OPEN: List["Session"] = []
_OPEN_LOCK = threading.Lock()

_ATEXIT_REGISTERED = False


def _ensure_atexit() -> None:
    global _ATEXIT_REGISTERED
    if not _ATEXIT_REGISTERED:
        atexit.register(_atexit_dump)
        _ATEXIT_REGISTERED = True


def _atexit_dump() -> None:
    """Fallback trace dump: a process exiting with sessions still open
    (crash path, forgotten ``uninstall()``) keeps its recorded traces —
    each open session with a ``trace_path`` dumps before teardown."""
    with _OPEN_LOCK:
        pending = list(_OPEN)
    for s in pending:
        try:
            s._dump_trace(reason="atexit")
        except Exception:   # never let shutdown raise   # noqa: BLE001
            pass


class Session:
    """One workload's offload stack: config + runtime + interceptors.

    ``intercept=False`` activates the runtime without patching the
    public ``jnp`` symbols (the dlsym-mode analogue: callers invoke
    ``repro.core.blas`` directly).

    ``name`` is the session's tenant id for multi-tenant runs: trace
    events are stamped with it and per-tenant pool statistics report
    under it.  Unnamed sessions stamp nothing — their traces serialize
    byte-identically to the single-tenant format.  ``pool`` joins the
    session to a :class:`~repro.core.residency.SharedDevicePool`
    (quota from ``config.pool_quota``); with no explicit pool, setting
    ``config.pool_bytes``/``pool_quota`` joins the process-default
    pool.
    """

    def __init__(self, config: Optional[OffloadConfig] = None, *,
                 record_trace: bool = True, intercept: bool = True,
                 name: str = "", pool=None):
        self.config = (OffloadConfig.from_env() if config is None
                       else config)
        self.record_trace = record_trace
        self.intercept = intercept
        self.name = name
        self.pool = pool
        self.runtime = None      # type: Optional[object]
        self._traced_dumped = False
        # whether this session holds a LAPACK-tier patch reference
        # (config.lapack + intercept): jnp.linalg / jax.scipy.linalg
        # factorizations routed onto the repro.solvers drivers
        self._lapack_patched = False

    # ------------------------------------------------------------------ #
    # lifecycle                                                           #
    # ------------------------------------------------------------------ #
    def open(self) -> "Session":
        """Create this session's runtime and make it the active dispatch
        target (pushing any currently-active session one level out)."""
        if self.runtime is not None:
            raise RuntimeError("session is already open")
        self._traced_dumped = False     # a reopened session dumps again
        from repro.core import intercept as icp
        from repro.core import residency as res
        from repro.core import runtime as rt
        pool = self.pool
        if pool is None and (self.config.pool_bytes is not None
                             or self.config.pool_quota is not None):
            pool = res.default_pool(self.config.pool_bytes)
        self.runtime = rt.OffloadRuntime(config=self.config,
                                         record_trace=self.record_trace,
                                         session_id=self.name,
                                         pool=pool)
        self.name = self.runtime.session_id   # pool may auto-assign one
        _STACK.set(_STACK.get() + (self,))
        with _OPEN_LOCK:
            _OPEN.append(self)
        trc.gc_spans(True)
        rt.activate(self.runtime)
        if self.intercept:
            icp.patch_symbols()
            if self.config.lapack:
                from repro.solvers import intercept as slv
                slv.patch_symbols()
                self._lapack_patched = True
        _ensure_atexit()
        return self

    def close(self):
        """Drain in-flight work, dump the trace (``config.trace_path``),
        deactivate, and return final :class:`RuntimeStats`.  The outer
        session (if any) becomes active again.  Idempotent."""
        if self.runtime is None:
            return None
        from repro.core import intercept as icp
        from repro.core import runtime as rt
        runtime, self.runtime = self.runtime, None
        runtime.sync()
        self._dump_trace(runtime=runtime)
        runtime.detach_pool()
        stack = _STACK.get()
        if self in stack:
            _STACK.set(tuple(s for s in stack if s is not self))
        with _OPEN_LOCK:
            if self in _OPEN:
                _OPEN.remove(self)
        trc.gc_spans(False)
        if self.intercept:
            if self._lapack_patched:
                from repro.solvers import intercept as slv
                slv.unpatch_symbols()
                self._lapack_patched = False
            icp.unpatch_symbols()
        # the innermost remaining session's runtime is the dispatch
        # target again; with none left, dispatch deactivates entirely.
        # Module-level state this runtime set (the blas-layer cache
        # flag, the resolved memspace mapping) is restored to the outer
        # session's values too — "outer restored on exit" must hold for
        # everything the inner config touched, not just the runtime.
        from repro.core import blas, memspace
        stack = _STACK.get()
        prev = stack[-1] if stack else None
        rt.activate(prev.runtime if prev is not None else None)
        if prev is not None and prev.runtime is not None:
            blas.refresh_cache_flag(prev.config.dispatch_cache)
            memspace.install(space=prev.runtime.memspace)
        else:
            blas.refresh_cache_flag()    # env-derived default again
        return runtime.stats

    def __enter__(self) -> "Session":
        if self.runtime is None:
            self.open()
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @property
    def closed(self) -> bool:
        return self.runtime is None

    @contextlib.contextmanager
    def scope(self):
        """Adopt this open session in the *current* thread/context.

        Sessions are context-local: a worker thread does not inherit
        the thread that opened them.  ``with s.scope():`` makes ``s``
        the active dispatch target here without reopening it — several
        workers may scope one session concurrently (its runtime
        serializes their calls).  The previous context state is
        restored on exit."""
        self._require_open()
        from repro.core import blas, memspace
        from repro.core import runtime as rt
        token = _STACK.set(_STACK.get() + (self,))
        rt.activate(self.runtime)
        blas.refresh_cache_flag(self.config.dispatch_cache)
        memspace.install(space=self.runtime.memspace)
        try:
            yield self
        finally:
            _STACK.reset(token)
            stack = _STACK.get()
            prev = stack[-1] if stack else None
            rt.activate(prev.runtime if prev is not None else None)
            if prev is not None and prev.runtime is not None:
                blas.refresh_cache_flag(prev.config.dispatch_cache)
                memspace.install(space=prev.runtime.memspace)
            else:
                blas.refresh_cache_flag()

    # ------------------------------------------------------------------ #
    # what a workload reads off its session                               #
    # ------------------------------------------------------------------ #
    @property
    def stats(self):
        self._require_open()
        return self.runtime.stats

    @property
    def trace(self):
        """The recorded BLAS trace (None with ``record_trace=False``)."""
        self._require_open()
        return self.runtime.trace

    def report(self) -> str:
        """The runtime's statistics report, scoped to this session."""
        self._require_open()
        return self.runtime.stats.report()

    def sync(self) -> "Session":
        self._require_open()
        self.runtime.sync()
        return self

    def pin(self, x):
        """Pin a buffer on this session's device tier (survives cap
        pressure until :meth:`unpin` or buffer death)."""
        self._require_open()
        return self.runtime.pin(x)

    def unpin(self, x) -> None:
        self._require_open()
        self.runtime.unpin(x)

    # ------------------------------------------------------------------ #
    # safe mid-run reconfiguration                                        #
    # ------------------------------------------------------------------ #
    def reconfigure(self, **kw) -> OffloadConfig:
        """Apply config changes to the live runtime.

        Builds the new config with :meth:`OffloadConfig.replace` (so it
        is validated as a whole), then applies it: the memoized dispatch
        cache and any adaptive per-site locks invalidated by the change
        are flushed, residency caps and eviction policies are updated in
        place.  ``devices`` cannot change mid-run (the block-store
        topology is fixed at open); use a new session.  Returns the new
        config.
        """
        self._require_open()
        was_lapack = self.config.lapack
        new = self.config.replace(**kw)
        self.runtime.apply_config(new)
        self.config = new
        # the LAPACK-tier patch follows the flag: flipping it mid-run
        # (re)patches or releases this session's reference
        if self.intercept and new.lapack != was_lapack:
            from repro.solvers import intercept as slv
            if new.lapack and not self._lapack_patched:
                slv.patch_symbols()
                self._lapack_patched = True
            elif not new.lapack and self._lapack_patched:
                slv.unpatch_symbols()
                self._lapack_patched = False
        return new

    # ------------------------------------------------------------------ #
    def _dump_trace(self, runtime=None, reason: str = "close") -> None:
        runtime = self.runtime if runtime is None else runtime
        if runtime is None or self._traced_dumped:
            return
        path = self.config.trace_path
        if not path or runtime.trace is None:
            return
        self._traced_dumped = True
        try:
            runtime.trace.dump(path)
            if self.config.debug >= 1:
                print(f"[scilib] trace ({len(runtime.trace)} calls) "
                      f"-> {path} ({reason})")
        except Exception as exc:   # noqa: BLE001 — teardown must finish:
            # a failed dump (bad path, full disk, serialization bug) is
            # reported, never allowed to mask the process exit status or
            # leave a half-closed session.  trace.dump writes through a
            # temp file + rename, so `path` is never left truncated.
            print(f"[scilib] trace dump to {path!r} failed: "
                  f"{type(exc).__name__}: {exc}")

    def _require_open(self) -> None:
        if self.runtime is None:
            raise RuntimeError("session is closed")

    def __repr__(self) -> str:
        state = "open" if self.runtime is not None else "closed"
        return f"Session({self.config!r}, {state})"


# --------------------------------------------------------------------- #
# module-level helpers                                                   #
# --------------------------------------------------------------------- #
def session(config: Optional[OffloadConfig] = None, *,
            record_trace: bool = True,
            intercept: bool = True,
            name: str = "", pool=None, **kw) -> Session:
    """Open a session (the primary public entry point).

    ``repro.session(cfg)`` returns an **open** session: use it as a
    context manager for scoped offload, or keep it long-lived and call
    ``close()`` yourself.  Extra keyword arguments are config fields
    applied on top (``repro.session(threshold=800)``), so quick
    one-off overrides need no explicit config object.  ``name`` and
    ``pool`` are the multi-tenant knobs (see :class:`Session`).
    """
    if config is None:
        config = OffloadConfig.from_env()
    if kw:
        config = config.replace(**kw)
    return Session(config, record_trace=record_trace,
                   intercept=intercept, name=name, pool=pool).open()


def active_session() -> Optional[Session]:
    """The innermost open session of the current context, or None."""
    stack = _STACK.get()
    return stack[-1] if stack else None


# --------------------------------------------------------------------- #
# the implicit default-session stack behind the legacy shims             #
# --------------------------------------------------------------------- #
#: sessions opened by install() (both the runtime- and intercept-level
#: shims), closed LIFO by uninstall().  One shared stack — exactly like
#: the one module global the shims used to flip — so a runtime-level
#: uninstall() after an intercept-level install() (or vice versa)
#: cannot leave a stale closed session behind.  Context-local like the
#: session stack: each thread's install()/uninstall() pairs are its own.
_LEGACY: ContextVar[Tuple[Session, ...]] = (
    ContextVar("scilib_legacy_stack", default=()))


def open_legacy(config: OffloadConfig, *, record_trace: bool = True,
                intercept: bool = False) -> Session:
    """Open the implicit session behind a legacy ``install()`` call.

    One deliberate divergence from the pre-session globals: repeated
    ``install()`` calls **nest** (each ``uninstall()`` closes the most
    recent and restores the previous one).  The old code silently
    orphaned the previous runtime on a second ``install()`` and one
    ``uninstall()`` tore everything down — nesting is strictly more
    useful and is what the session stack already guarantees."""
    s = Session(config, record_trace=record_trace,
                intercept=intercept).open()
    _LEGACY.set(_LEGACY.get() + (s,))
    return s


def close_legacy():
    """Close the most recent legacy session (the ``uninstall()`` shim);
    falls back to the innermost open session, then to a no-op."""
    legacy = _LEGACY.get()
    if legacy:
        _LEGACY.set(legacy[:-1])
        return legacy[-1].close()
    s = active_session()
    return s.close() if s is not None else None
