"""Logical memory tiers over the backend's real placements.

The paper's runtime moves buffers between two physical homes: host DRAM
and device HBM, coherently addressable from both sides (GH200 NVLink-C2C).
This module maps two *logical* tiers onto two real JAX placements:

* :data:`HOST`   — where CPU-first-touched data lives (the malloc side),
* :data:`DEVICE` — where offloaded BLAS wants its operands (the HBM side).

``probe()`` resolves the pair once per backend:

* **Accelerator backend (TPU).**  HOST is the CPU backend's device and
  DEVICE is the accelerator's own memory.  Code computing on a HOST
  array runs on the host CPU, exactly like the paper's CPU code touching
  malloc'd pages.  (The TPU's ``pinned_host`` kind cannot be an operand
  of ordinary device ops, so it cannot back a tier user code computes
  on.)  DEVICE placements, and the gathered results of sharded calls,
  are *uncommitted* arrays on the default device, so user code that
  combines one with a HOST array computes on the host, as a CPU thread
  reading a device-resident page would.
* **CPU backend.**  There is one device; HOST is its ``unpinned_host``
  memory kind and DEVICE its ``device`` kind.  Both are ordinary
  operands to every op, and a ``put`` between them is a real transfer.

A backend that cannot express both placements is an error: there is no
simulated fallback.

The DEVICE tier additionally carries a **device index**: a node with N
local accelerators has N device tiers, one per HBM.  ``probe()``
enumerates them from ``len(jax.devices())``; ``SCILIB_DEVICES=n`` may
ask for more tiers than there are devices, and then the extra tiers are
logical (a copy tagged with its index) so the multi-device tile
scheduler can run on one device.  ``put_block`` re-homes a buffer to one
specific device tier; ``device_of`` reads the index back.
"""
from __future__ import annotations

import dataclasses
import threading
import time
import weakref
from contextvars import ContextVar
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Sharding, SingleDeviceSharding

from repro.core import faults as flt
from repro.core import trace as trc

#: logical tier names (stable across backends)
HOST = "host"
DEVICE = "device"

# --------------------------------------------------------------------- #
# failure semantics (PR 6): a real cross-tier movement toward a DEVICE   #
# tier is the transfer guard site.  The active runtime installs its      #
# fault injector here (set_fault_hook) so injected transfer faults fire  #
# at the genuine call site, *before* any copy or tag mutates state; and  #
# real backend failures during the move are wrapped into the typed       #
# hierarchy of repro.core.faults instead of escaping as bare             #
# XlaRuntimeError — the runtime's retry/fallback guard catches them.     #
#                                                                        #
# The hook, the debug level, and the active tier mapping are all         #
# *context-local* (PR 7): concurrent sessions in different threads       #
# each see their own runtime's hook and mapping, never a neighbour's.    #
# --------------------------------------------------------------------- #
#: (device_index_or_None, nbytes) -> None; raises to inject a fault
_FAULT_HOOK: ContextVar[Optional[Callable[[Optional[int], int], None]]] = (
    ContextVar("scilib_fault_hook", default=None))

#: (direction "h2d"/"d2h", nbytes, host seconds) -> None: the active
#: runtime's link counters, fed by every real move between the host and
#: a device tier (routed like the fault hook)
_LINK_HOOK: ContextVar[Optional[Callable[[str, int, float], None]]] = (
    ContextVar("scilib_link_hook", default=None))

#: SCILIB_DEBUG level, plumbed in by the owning runtime (config boundary)
_DEBUG: ContextVar[int] = ContextVar("scilib_debug", default=0)

#: exception types a data movement may legitimately raise (XlaRuntimeError
#: subclasses RuntimeError); anything else is a bug and propagates as-is
_MOVE_ERRORS = (RuntimeError, MemoryError, OSError)


def set_fault_hook(hook: Optional[Callable[[Optional[int], int], None]],
                   ) -> None:
    """Install (or clear, with None) the transfer-fault injection hook.

    The runtime layer owns this: it points the hook at the active
    runtime's :class:`repro.core.faults.FaultInjector` on activation and
    reconfiguration.  The hook runs immediately before every *real*
    movement toward a DEVICE tier (never on no-op puts or cache hits),
    except movements explicitly opted out with ``check=False`` — the
    host execution path and user-level ``pin()`` must not inherit
    offload-path faults they cannot fall back from.  Context-local:
    one thread's injector never fires in another thread's transfers."""
    _FAULT_HOOK.set(hook)


def set_link_hook(hook: Optional[Callable[[str, int, float], None]],
                  ) -> None:
    """Install (or clear, with None) the link counter hook; the runtime
    layer points it at the active runtime.  Context-local."""
    _LINK_HOOK.set(hook)


def _link(direction: str, nbytes: int, move: Callable[[], jax.Array]
          ) -> jax.Array:
    """Run one move over the host link under its span, and count its
    bytes and the host seconds the transfer call takes.  Nothing here
    waits for the copy: a transfer that returns before its data has
    landed is waited for by whoever reads the result."""
    t0 = time.perf_counter()
    with trc.span(trc.LINK_H2D if direction == "h2d" else trc.LINK_D2H):
        out = move()
    hook = _LINK_HOOK.get()
    if hook is not None:
        hook(direction, nbytes, time.perf_counter() - t0)
    return out


def set_debug(level: int) -> None:
    """Plumb the config's ``debug`` level in (``SCILIB_DEBUG`` stays
    behind the config boundary; this module never reads the env)."""
    _DEBUG.set(int(level))


def _debug_log(msg: str, level: int = 1) -> None:
    if _DEBUG.get() >= level:
        print(f"[scilib] {msg}")


def _wrap_move_error(exc: BaseException, *, device: Optional[int],
                     nbytes: int) -> flt.OffloadError:
    """Classify a raw movement failure into the typed hierarchy."""
    err = flt.classify("transfer", exc, device=device, nbytes=nbytes)
    assert err is not None    # _MOVE_ERRORS are always classifiable
    return err


@dataclasses.dataclass(frozen=True)
class MemSpace:
    """Resolved mapping of the logical tiers onto one backend."""

    host: Sharding      # placement backing the HOST tier
    device: Sharding    # placement backing the (whole-call) DEVICE tier
    backend: str        # jax.default_backend() at probe time
    n_devices: int = 1  # number of logical DEVICE tiers (accelerators)

    @property
    def host_kind(self) -> str:
        return self.host.memory_kind

    @property
    def device_kind(self) -> str:
        return self.device.memory_kind

    @property
    def host_platform(self) -> str:
        return _device(self.host).platform

    @property
    def device_platform(self) -> str:
        return _device(self.device).platform

    def sharding(self, tier: str) -> Sharding:
        return self.host if tier == HOST else self.device

    def is_host(self, sharding: Sharding) -> bool:
        """Does an array with this sharding live on the HOST tier?"""
        device = _device(sharding)
        kind = sharding.memory_kind or device.default_memory().kind
        return device == _device(self.host) and kind == self.host_kind

    def describe(self) -> str:
        return (f"host={self.host_platform}:{self.host_kind} "
                f"device={self.device_platform}:{self.device_kind} "
                f"backend={self.backend} n_devices={self.n_devices}")


def _device(sharding: Sharding) -> jax.Device:
    return next(iter(sharding.device_set))


def device_bytes_from_env() -> Optional[int]:
    """Back-compat wrapper: the per-device-tier byte cap, read through
    the config boundary (:meth:`repro.core.config.OffloadConfig.
    from_env`).  The runtime itself is plumbed from its config; this
    exists for callers that inspect the env-derived cap directly."""
    from repro.core.config import OffloadConfig
    return OffloadConfig.from_env().device_bytes


def probe(device: Optional[jax.Device] = None,
          n_devices: Optional[int] = None) -> MemSpace:
    """Inspect the backend once and resolve the tier mapping.

    ``n_devices`` is the logical device-tier count; the runtime passes
    its config's resolved value.  When omitted (a bare re-probe outside
    any runtime), it comes from the env-derived config — the single
    ``SCILIB_*`` ingestion boundary — falling back to
    ``len(jax.devices())``.

    Raises ``RuntimeError`` when the backend cannot hold the two tiers
    apart: an accelerator without a CPU backend beside it, or a CPU
    device without an ``unpinned_host`` memory kind.
    """
    d = device if device is not None else jax.devices()[0]
    backend = jax.default_backend()
    if n_devices is None:
        from repro.core.config import OffloadConfig
        n_devices = OffloadConfig.from_env().devices
    if n_devices is None:
        n_devices = len(jax.devices())
    if d.platform == "cpu":
        kinds = [m.kind for m in d.addressable_memories()]
        if "unpinned_host" not in kinds:
            raise RuntimeError(
                f"CPU device {d} has memory kinds {kinds}; the HOST tier "
                f"needs 'unpinned_host'")
        host = SingleDeviceSharding(d, memory_kind="unpinned_host")
    else:
        try:
            cpu = jax.devices("cpu")[0]
        except RuntimeError as exc:
            raise RuntimeError(
                f"{d.platform} backend has no CPU backend beside it for "
                f"the HOST tier ({exc})") from exc
        host = SingleDeviceSharding(cpu, memory_kind=cpu.default_memory().kind)
    dev = SingleDeviceSharding(d, memory_kind=d.default_memory().kind)
    return MemSpace(host=host, device=dev, backend=backend,
                    n_devices=n_devices)


# --------------------------------------------------------------------- #
# module state: active mapping + logical device-tier tags               #
# --------------------------------------------------------------------- #
# The *installed* mapping is context-local (a session's devices layout
# must not leak into a neighbouring thread); the lazily-probed fallback
# for sessionless threads is process-wide and built once under a lock.
_ACTIVE: ContextVar[Optional[MemSpace]] = (
    ContextVar("scilib_memspace", default=None))
_PROBED: Optional[MemSpace] = None
_PROBE_LOCK = threading.Lock()

# id(arr) -> (weakref(arr), logical device index): the copies put_block
# makes for logical device tiers that share one real device.  The table
# is process-wide (a tier is a property of the buffer, not of the
# observing session) and its dict operations are GIL-atomic.
_LOGICAL: Dict[int, Tuple[weakref.ref, int]] = {}


def active() -> MemSpace:
    """The resolved tier mapping: the context's installed mapping when a
    session owns this thread, else the lazily-probed process default."""
    space = _ACTIVE.get()
    if space is not None:
        return space
    global _PROBED
    with _PROBE_LOCK:
        if _PROBED is None:
            _PROBED = probe()
        return _PROBED


def install(space: Optional[MemSpace] = None,
            n_devices: Optional[int] = None) -> MemSpace:
    """Re-probe (or inject, for tests) the mapping; runtime.install hook.
    ``n_devices`` plumbs the owning config's device-tier count through.
    The installed mapping is context-local."""
    space = probe(n_devices=n_devices) if space is None else space
    _ACTIVE.set(space)
    return space


def reset() -> None:
    global _PROBED
    _ACTIVE.set(None)
    with _PROBE_LOCK:
        _PROBED = None
    _LOGICAL.clear()


def n_devices() -> int:
    """Number of logical device tiers (accelerators) of the active space."""
    return active().n_devices


def _tag(x: jax.Array, device: int) -> None:
    key = id(x)

    def _drop(_ref, key=key):
        _LOGICAL.pop(key, None)

    _LOGICAL[key] = (weakref.ref(x, _drop), device)


def _logical_device(x) -> Optional[int]:
    ent = _LOGICAL.get(id(x))
    if ent is not None and ent[0]() is not None:
        return ent[1]
    return None


def tier_of(x) -> str:
    """Logical tier of a buffer (HOST or DEVICE), read from where the
    buffer physically is.  Anything that is not on the HOST placement —
    including fresh arrays born on the default device and non-array
    leaves — is DEVICE.  Data that is semantically CPU-first-touched
    comes in through :func:`host_array` / ``put(x, HOST)``, exactly like
    the paper's malloc'd inputs."""
    if _logical_device(x) is not None:
        return DEVICE
    try:
        sharding = x.sharding
    except AttributeError as exc:  # non-array leaves
        _debug_log(f"tier_of: no sharding on {type(x).__name__} "
                   f"({exc!r}); assuming DEVICE", level=2)
        return DEVICE
    return HOST if active().is_host(sharding) else DEVICE


def device_of(x) -> Optional[int]:
    """Device-tier index of a buffer, or None when it has no explicit
    device placement (host-resident or never routed by the scheduler)."""
    logical = _logical_device(x)
    if logical is not None:
        return logical
    if tier_of(x) != DEVICE:
        return None
    try:
        devs = list(x.devices())
    except AttributeError as exc:  # non-array leaves
        _debug_log(f"device_of: no devices() on {type(x).__name__} "
                   f"({exc!r})", level=2)
        return None
    if len(devs) != 1:
        return None
    try:
        return jax.devices().index(devs[0])
    except ValueError:
        return None


def _move(x: jax.Array, tier: str) -> jax.Array:
    """The physical transfer behind :func:`put`.  On an accelerator a
    DEVICE placement is left *uncommitted* on the default device (see the
    module docstring); everything else is a plain ``device_put``."""
    ms = active()
    if tier == DEVICE and ms.device_platform != "cpu":
        return jax.device_put(np.asarray(x))
    return jax.device_put(x, ms.sharding(tier))


def put(x: jax.Array, tier: str, *, check: bool = True) -> jax.Array:
    """Re-home a buffer to a logical tier (the ``move_pages()`` analogue):
    a real transfer to the tier's placement, or ``x`` itself when it is
    already there.

    A movement toward DEVICE is a transfer guard site: the fault hook
    runs first (injection point — before any state changes), and a
    failure of the movement itself raises a typed
    :class:`repro.core.faults.TransferError` / ``DeviceOOMError`` the
    runtime's retry/fallback guard can absorb.  ``check=False`` opts a
    call site out of injection (host-path streaming, explicit pins).

    A move of an array between the tiers is link traffic, counted by
    direction; host data (numpy) first placed on HOST is not.
    """
    if tier_of(x) == tier:
        return x
    hook = _FAULT_HOOK.get()
    if check and tier == DEVICE and hook is not None:
        hook(None, x.nbytes)
    try:
        if not isinstance(x, jax.Array):
            return _move(x, tier)
        return _link("h2d" if tier == DEVICE else "d2h", x.nbytes,
                     lambda: _move(x, tier))
    except _MOVE_ERRORS as exc:
        raise _wrap_move_error(exc, device=None, nbytes=x.nbytes) from exc


def pull(x) -> np.ndarray:
    """``np.asarray(x)``, with the pull of a device-tier array to host
    memory counted as a d2h link move.  The wait for whatever computes
    ``x`` falls before the move, outside its span and its seconds."""
    if not isinstance(x, jax.Array) or tier_of(x) == HOST:
        return np.asarray(x)
    x.block_until_ready()
    return _link("d2h", x.nbytes, lambda: np.asarray(x))


def put_block(x: jax.Array, device: int) -> jax.Array:
    """Re-home a buffer onto one specific DEVICE tier (tile scheduling).

    With several real devices the block is ``device_put`` onto that
    accelerator (tier ``i`` on device ``i % len(jax.devices())``).  When
    the tiers outnumber the real devices the copy is also tagged with its
    tier index, so tiers sharing a device stay apart — the
    same first-touch cost model as :func:`put`, so per-device movement
    statistics stay honest on a one-device ``SCILIB_DEVICES=n`` layout.

    Like :func:`put`, a movement is a transfer guard site — the fault
    hook fires first (with the device index, so ``device=`` rules in
    ``SCILIB_FAULTS`` target one tier), and movement failures raise the
    typed hierarchy.  A block that comes from the host is link traffic;
    one copied from another device tier is not.
    """
    real = jax.devices()
    ms = active()
    logical = ms.n_devices > len(real)
    if (_logical_device(x) if logical else device_of(x)) == device:
        return x
    hook = _FAULT_HOOK.get()
    if hook is not None:
        hook(device, x.nbytes)
    target = real[device % len(real)] if len(real) > 1 else ms.device
    try:
        if isinstance(x, jax.Array) and tier_of(x) == DEVICE:
            moved = jax.device_put(x, target, may_alias=False)
        else:
            moved = _link("h2d", x.nbytes, lambda: jax.device_put(
                x, target, may_alias=False))
    except _MOVE_ERRORS as exc:
        raise _wrap_move_error(exc, device=device,
                               nbytes=x.nbytes) from exc
    if logical:
        _tag(moved, device)
    return moved


def uncommitted(x: jax.Array) -> jax.Array:
    """``x`` released from its device, its buffer left where it is.

    The tiles of a sharded call are committed to the devices they ran on,
    and so is their gathered result; an op combining a committed device
    array with a HOST array (committed to the CPU) is refused.  Handed
    back uncommitted, the result behaves like a whole-call DEVICE
    placement: user code mixing it with host data computes on the host.
    JAX has no public call that drops the commitment without a copy, so
    this re-wraps the same device buffers."""
    if not x.committed:
        return x
    from jax._src import array as jax_array
    return jax_array.ArrayImpl(x.aval, x.sharding, x._arrays,
                               committed=False)


def gather_home(x: jax.Array) -> jax.Array:
    """``x`` on the device of the whole-call DEVICE placement: tiles a
    sharded call computed on several real devices meet there before
    they are assembled (one op never spans devices).  A device-to-device
    copy over the interconnect, not host-link traffic."""
    home = _device(active().device)
    if x.devices() == {home}:
        return x
    return jax.device_put(x, home)
