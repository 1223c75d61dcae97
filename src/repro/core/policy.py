"""Data-movement policies (paper §3.2) over logical memory tiers.

The JAX adaptation of the paper's three strategies plus two controls.
Tiers are the *logical* HOST/DEVICE pair of :mod:`repro.core.memspace`,
resolved to real placements per backend (the CPU device vs the
accelerator on a TPU; the ``unpinned_host`` vs ``device`` memory kinds
on the CPU backend), so every ``put`` is a physical transfer.

Buffer identity follows the source array object (the JAX analogue of the
paper's virtual-address identity): placement is cached per buffer, so a
matrix moved by Device First-Use stays device-resident for all later calls
that pass the same array — that cache *is* the page table remap of Fig. 2.

Placement state lives in the runtime's residency stores
(:mod:`repro.core.residency`): ``runtime.placements`` for whole-buffer
placements and ``runtime.block_stores[d]`` per device tier for tile
blocks.  Policies read and write those stores directly — the stores own
byte caps, eviction, pinning and event accounting, so every policy gets
them for free and none keeps private residency state.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.core import memspace

#: logical tier names, re-exported for the runtime and tests.  These were
#: once hard-coded physical memory kinds ("pinned_host"/"device"); the
#: mem-space now resolves the physical kind per backend.
HOST_KIND = memspace.HOST
DEVICE_KIND = memspace.DEVICE


def _put(x: jax.Array, tier: str) -> jax.Array:
    """Re-home a buffer to a logical tier (the move_pages() analogue)."""
    return memspace.put(x, tier)


def memory_kind_of(x: jax.Array) -> str:
    """Logical tier of a buffer (kept under its historical name)."""
    return memspace.tier_of(x)


def host_array(x) -> jax.Array:
    """The malloc() analogue: materialize an array on the HOST tier.

    Application inputs in the paper are CPU-first-touched; use this for
    inputs so the offload policies have real movement to manage.  Host
    data (numpy, lists) goes straight to the HOST placement, never
    through the device."""
    if isinstance(x, jax.Array):
        return _put(x, HOST_KIND)
    return jax.device_put(np.asarray(x), memspace.active().host)


@dataclasses.dataclass
class Placement:
    """Outcome of placing one operand for one call."""

    array: jax.Array
    moved_bytes: int = 0
    cache_hit: bool = False
    #: which device tier the buffer landed on (multi-device scheduling);
    #: 0 is the only tier on single-accelerator systems.
    device: int = 0


class PolicyBase:
    """Interface: how operands reach the device tier and results return."""

    name = "base"
    #: whether outputs of offloaded calls are copied back to the host tier
    copy_back = False
    #: whether placements persist across calls (the reuse mechanism)
    persistent = True
    #: whether this policy executes on the device tier at all — the
    #: dispatch pipeline's decide stage vetoes offload when False
    #: (host-only baselines), instead of string-matching policy names
    offloads = True
    #: whether the multi-device tile scheduler may shard calls under this
    #: policy.  Only policies that migrate every operand on (first) use
    #: keep their semantics when the runtime moves blocks itself; the
    #: access-counter model decides per-operand and must stay
    #: single-device or it would silently degenerate to DFU.
    shardable = True

    def place_operand(self, runtime, x: jax.Array) -> Placement:
        raise NotImplementedError

    def place_output(self, runtime, y: jax.Array) -> Placement:
        """Offloaded compute produces device-tier outputs; policies decide
        whether they stay (DFU) or bounce back to host (Mem-Copy)."""
        if self.copy_back:
            nbytes = y.nbytes
            return Placement(_put(y, HOST_KIND), moved_bytes=nbytes)
        return Placement(y)

    def select_device(self, runtime, blocks) -> int:
        """Which device tier runs one tile of a sharded call (BLASX-style
        round-robin with affinity).

        ``blocks``: (key, nbytes, shared) per tile operand.  Persistent
        policies prefer the device already holding the most operand-block
        bytes — first use moved the block there, every later tile on the
        same device is free, the exact multi-device generalization of
        first-touch.  Blocks shared by every tile (trsm's triangle) are
        replicated and never steer the choice.  With no residency
        anywhere, tiles deal round-robin so work spreads evenly.
        Score ties — a block replicated onto several devices by an
        earlier grid layout — break toward the device with the fewest
        tiles scheduled this call, so replication cannot funnel a whole
        grid onto one device and idle the rest.  A quarantined device
        (circuit breaker open) is never selected, even by affinity —
        its residents were invalidated at trip time anyway."""
        if self.persistent:
            scores: dict = {}
            for key, nbytes, shared in blocks:
                if shared:
                    continue
                for home, store in enumerate(runtime.block_stores):
                    if key in store and runtime.device_usable(home):
                        scores[home] = scores.get(home, 0) + nbytes
            if scores:
                return min(scores, key=lambda d: (-scores[d],
                                                  runtime.scheduled_load(d),
                                                  d))
        return runtime.next_device()


class MemCopyPolicy(PolicyBase):
    """Strategy 1 (§3.2.1): stage in and out around *every* call."""

    name = "memcopy"
    copy_back = True
    persistent = False

    def place_operand(self, runtime, x):
        if memspace.tier_of(x) == DEVICE_KIND:
            # even Mem-Copy tools skip the copy when data is already there
            return Placement(x, cache_hit=True)
        return Placement(_put(x, DEVICE_KIND), moved_bytes=x.nbytes)


class DeviceFirstUsePolicy(PolicyBase):
    """Strategy 3 (§3.2.3): the paper's contribution.

    First device use migrates the buffer to the device tier and registers
    the placement; every later use of the same buffer is a cache hit with
    zero movement. Outputs are born device-resident and registered, so
    chained calls (``C = A·B`` then ``E = D·C``) never touch the link.
    """

    name = "dfu"
    copy_back = False
    persistent = True

    def place_operand(self, runtime, x):
        store = runtime.placements
        cached = store.get(id(x))
        if cached is not None:
            return Placement(cached, cache_hit=True)
        if memspace.tier_of(x) == DEVICE_KIND:
            store.put(id(x), x, x.nbytes, anchor=x)
            return Placement(x, cache_hit=False)
        moved = _put(x, DEVICE_KIND)
        store.put(id(x), moved, moved.nbytes, anchor=x)
        return Placement(moved, moved_bytes=x.nbytes)

    def place_output(self, runtime, y):
        runtime.placements.put(id(y), y, y.nbytes, anchor=y)
        return Placement(y)


class CounterPolicy(PolicyBase):
    """Strategy 2 (§3.2.2): model of the hardware access-counter migration.

    Reproduces the size- and reuse-biased behaviour measured in Table 6
    (rules R1-R4 of ``repro.memtier.simulator``): some operands never
    migrate and are streamed from the host tier on every call — which is
    why this policy loses to DFU in the paper's application tests.
    """

    name = "counter"
    copy_back = False
    persistent = True
    shardable = False     # R1-R4 are per-operand host-vs-device rules

    reuse_min = 100.0
    byte_budget = 3.4e9
    c_small = 16e6

    def place_operand(self, runtime, x, *, reads_per_elem: float = 1.0,
                      written: bool = False, ai: float = 0.0,
                      budget_used: int = 0) -> Placement:
        store = runtime.placements
        cached = store.get(id(x))
        if cached is not None:
            return Placement(cached, cache_hit=True)
        if memspace.tier_of(x) == DEVICE_KIND:
            store.put(id(x), x, x.nbytes, anchor=x)
            return Placement(x)
        if written:
            ok = x.nbytes <= self.c_small and ai >= 30.0
        else:
            ok = (reads_per_elem >= self.reuse_min
                  and budget_used + x.nbytes <= self.byte_budget)
        if not ok:
            return Placement(x)         # stays host: remote-streamed reads
        moved = _put(x, DEVICE_KIND)
        store.put(id(x), moved, moved.nbytes, anchor=x)
        return Placement(moved, moved_bytes=x.nbytes)


class PinnedDevicePolicy(PolicyBase):
    """``numactl -m 1`` control: everything lives on the device tier."""

    name = "pinned"
    copy_back = False

    def place_operand(self, runtime, x):
        store = runtime.placements
        cached = store.get(id(x))
        if cached is not None:
            return Placement(cached, cache_hit=True)
        if memspace.tier_of(x) == DEVICE_KIND:
            store.put(id(x), x, x.nbytes, anchor=x)
            return Placement(x)
        moved = _put(x, DEVICE_KIND)
        store.put(id(x), moved, moved.nbytes, anchor=x)
        return Placement(moved, moved_bytes=x.nbytes)


class CpuOnlyPolicy(PolicyBase):
    """Baseline: never offload (the paper's NVPL CPU runs)."""

    name = "cpu"
    copy_back = False
    persistent = False
    offloads = False

    def place_operand(self, runtime, x):
        return Placement(x)


POLICY_CLASSES = {
    p.name: p for p in (MemCopyPolicy, CounterPolicy, DeviceFirstUsePolicy,
                        PinnedDevicePolicy, CpuOnlyPolicy)
}


def make_policy(name: str) -> PolicyBase:
    try:
        return POLICY_CLASSES[name]()
    except KeyError:
        raise ValueError(
            f"unknown policy {name!r}; choose from {sorted(POLICY_CLASSES)}")
