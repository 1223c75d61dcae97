"""SCILIB-Accel core: the paper's contribution as a composable JAX module.

Public surface:

* :mod:`repro.core.blas` — level-3 BLAS routines (dlsym-mode API).
* :mod:`repro.core.intercept` — ``install``/``uninstall``/``offload``:
  automatic interception of ``jnp.dot/matmul/einsum`` (DBI-mode).
* :mod:`repro.core.lapack` — blocked LU/Cholesky drivers on that BLAS.
* :mod:`repro.core.runtime` — the placement runtime + statistics
  (async by default; ``SCILIB_SYNC=1`` or ``runtime.sync()`` to fence).
* :mod:`repro.core.policy` — Mem-Copy / counter / Device-First-Use /
  pinned / cpu data-movement policies.
* :mod:`repro.core.memspace` — logical HOST/DEVICE memory tiers mapped
  onto the backend's real placements (host CPU vs HBM on a TPU).
* :mod:`repro.core.callsite` — per-call-site fingerprints and profiles
  (the paper's patched call sites; drives ``SCILIB_ADAPTIVE=1``).
* :mod:`repro.core.residency` — the residency engine: the one byte-
  capped, policy-evicting, pinnable block store behind the runtime's
  registries and the memtier simulator (``SCILIB_EVICT``,
  ``SCILIB_PIN``; :func:`pin`/:func:`unpin` pin live buffers).
* :mod:`repro.core.config` — :class:`OffloadConfig`: every knob as a
  typed, validated, serializable field; ``from_env()`` is the single
  ``SCILIB_*`` ingestion boundary.
* :mod:`repro.core.session` — :class:`Session`: a first-class offload
  stack (runtime + interceptors + trace) per workload; sessions nest,
  and ``install``/``uninstall``/``offload`` above are shims over an
  implicit default session.
* :mod:`repro.core.faults` — fault tolerance: the typed offload error
  hierarchy, the deterministic fault injector (``SCILIB_FAULTS``), the
  transient-fault retry policy (``SCILIB_RETRIES``/
  ``SCILIB_BACKOFF_MS``) and the per-device circuit breaker
  (``SCILIB_BREAKER``); exhausted faults fall back to the host path
  bit-identically.
"""
from repro.core import blas, callsite, faults, lapack, memspace, residency
from repro.core.config import OffloadConfig
from repro.core.intercept import install, offload, uninstall
from repro.core.policy import host_array
from repro.core.residency import ResidencyStore
from repro.core.runtime import OffloadRuntime, active, pin, unpin
# NOTE: the session() helper is NOT re-exported here — that name is the
# repro.core.session submodule; the helper lives at the top level as
# repro.session().
from repro.core.session import Session, active_session
from repro.core.trace import BlasCall, Trace

__all__ = ["blas", "callsite", "faults", "lapack", "memspace",
           "residency", "install", "offload", "uninstall",
           "OffloadRuntime", "active", "BlasCall", "Trace", "host_array",
           "ResidencyStore", "pin", "unpin", "OffloadConfig", "Session",
           "active_session"]
