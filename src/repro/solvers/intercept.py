"""Solver-symbol interception: the LAPACK half of the DBI analogue.

The paper's tool patches LAPACK entry points (``zgetrf_``, ``zpotrf_``,
``zheev_`` ...) exactly like BLAS ones; the JAX equivalents are the
public factorization/solve symbols application code actually calls:
``jnp.linalg.cholesky``/``solve`` and ``jax.scipy.linalg.lu_factor``/
``lu_solve``/``cho_factor``/``cho_solve``/``solve_triangular``/``eigh``.  The trampolines route
eager, super-threshold, float/complex square systems onto the
span-wrapped blocked drivers (:mod:`repro.solvers.drivers`) — same
gating discipline as the matmul trampolines in
:mod:`repro.core.intercept` — and fall through to the originals for
everything else (sub-threshold sizes, tracers, batched inputs, kwargs
the drivers do not model).

Patching is refcounted and owned per session: ``OffloadConfig.lapack``
(``SCILIB_LAPACK=1``) makes an intercepting session take a reference on
open and release it on close, so with the flag unset these symbols are
never touched and behavior is bit-identical to the BLAS-only runtime.

Pivot convention: the patched ``lu_factor`` returns the *absolute row
permutation* (``A[piv] == L @ U``, the composed form of LAPACK's
sequential ipiv swaps), and the patched ``lu_solve`` consumes the same
— the pair is self-consistent, but a ``lu_factor`` result produced
while patched must not be fed to an unpatched ``lu_solve``.
"""
from __future__ import annotations

import threading
from typing import Dict

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from repro.core import blas
from repro.core import callsite
from repro.core import runtime as rt
from repro.solvers import drivers

callsite.register_machinery(__file__)

_ORIG: Dict[str, callable] = {}
_PATCHED = 0
_PATCH_LOCK = threading.Lock()

_TRANS = {0: "N", 1: "T", 2: "C", "N": "N", "T": "T", "C": "C"}


def _is_eager_array(x) -> bool:
    return isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer)


def _solvable(*arrays) -> bool:
    """The solver-tier gate: an active runtime, eager float/complex
    2-D operands, and a leading square system at or above the
    threshold (sub-threshold factorizations stay on the native path —
    the blocked Python drivers only pay off where offload does)."""
    r = rt.active()
    if r is None:
        return False
    for x in arrays:
        if not _is_eager_array(x):
            return False
        if not (jnp.issubdtype(x.dtype, jnp.floating)
                or jnp.issubdtype(x.dtype, jnp.complexfloating)):
            return False
    a = arrays[0]
    if a.ndim != 2 or a.shape[0] != a.shape[1]:
        return False
    return a.shape[0] >= r.config.resolved_threshold()


def _fall(name, *args, **kw):
    r = rt.active()
    if r is not None:
        r.note_uninstrumented()
    return _ORIG[name](*args, **kw)


# --------------------------------------------------------------------- #
# trampolines                                                            #
# --------------------------------------------------------------------- #
def _cholesky(a, *, upper=False):
    if _solvable(a):
        f = drivers.potrf(a, uplo="U" if upper else "L")
        return f
    return _fall("cholesky", a, upper=upper)


def _solve(a, b):
    if (_solvable(a) and _is_eager_array(b)
            and b.ndim in (1, 2) and b.shape[0] == a.shape[0]):
        return drivers.gesv(a, b)
    return _fall("solve", a, b)


def _lu_factor(a, overwrite_a=False, check_finite=True):
    if _solvable(a):
        return drivers.getrf(a)
    return _fall("lu_factor", a, overwrite_a=overwrite_a,
                 check_finite=check_finite)


def _lu_solve(lu_and_piv, b, trans=0, overwrite_b=False,
              check_finite=True):
    lu, piv = lu_and_piv
    if (trans == 0 and _solvable(lu) and _is_eager_array(b)
            and b.ndim in (1, 2) and b.shape[0] == lu.shape[0]):
        return drivers.getrs(lu, piv, b)
    return _fall("lu_solve", lu_and_piv, b, trans,
                 overwrite_b=overwrite_b, check_finite=check_finite)


def _cho_factor(a, lower=False, overwrite_a=False, check_finite=True):
    if _solvable(a):
        return drivers.potrf(a, uplo="L" if lower else "U"), lower
    return _fall("cho_factor", a, lower=lower, overwrite_a=overwrite_a,
                 check_finite=check_finite)


def _cho_solve(c_and_lower, b, overwrite_b=False, check_finite=True):
    c, lower = c_and_lower
    if (_solvable(c) and _is_eager_array(b)
            and b.ndim in (1, 2) and b.shape[0] == c.shape[0]):
        return drivers.potrs(c, b, uplo="L" if lower else "U")
    return _fall("cho_solve", c_and_lower, b, overwrite_b=overwrite_b,
                 check_finite=check_finite)


def _solve_triangular(a, b, trans=0, lower=False, unit_diagonal=False,
                      overwrite_b=False, debug=None, check_finite=True):
    if (trans in _TRANS and _solvable(a) and _is_eager_array(b)
            and b.ndim in (1, 2) and b.shape[0] == a.shape[0]):
        b2 = b[:, None] if b.ndim == 1 else b
        x = blas.trsm(a, b2, side="L", uplo="L" if lower else "U",
                      trans=_TRANS[trans],
                      diag="U" if unit_diagonal else "N")
        return x[:, 0] if b.ndim == 1 else x
    return _fall("solve_triangular", a, b, trans, lower=lower,
                 unit_diagonal=unit_diagonal, overwrite_b=overwrite_b,
                 debug=debug, check_finite=check_finite)


def _eigh(a, b=None, lower=True, eigvals_only=False, overwrite_a=False,
          overwrite_b=False, turbo=True, eigvals=None, type=1,
          check_finite=True):
    if (b is None and eigvals is None and type == 1 and _solvable(a)):
        w, v = drivers.syev(a, uplo="L" if lower else "U")
        return w if eigvals_only else (w, v)
    return _fall("eigh", a, b, lower=lower, eigvals_only=eigvals_only,
                 overwrite_a=overwrite_a, overwrite_b=overwrite_b,
                 turbo=turbo, eigvals=eigvals, type=type,
                 check_finite=check_finite)


# --------------------------------------------------------------------- #
# symbol patching (refcounted, same discipline as core.intercept)        #
# --------------------------------------------------------------------- #
_SYMBOLS = (
    (jnp.linalg, "cholesky", _cholesky),
    (jnp.linalg, "solve", _solve),
    (jsl, "lu_factor", _lu_factor),
    (jsl, "lu_solve", _lu_solve),
    (jsl, "cho_factor", _cho_factor),
    (jsl, "cho_solve", _cho_solve),
    (jsl, "solve_triangular", _solve_triangular),
    (jsl, "eigh", _eigh),
)


def patch_symbols() -> None:
    """Install the solver trampolines (refcounted: nested
    ``SCILIB_LAPACK`` sessions share one patch)."""
    global _PATCHED
    with _PATCH_LOCK:
        _PATCHED += 1
        if not _ORIG:
            for mod, name, wrapper in _SYMBOLS:
                _ORIG[name] = getattr(mod, name)
                setattr(mod, name, wrapper)


def unpatch_symbols() -> None:
    """Release one patch reference; restore the originals at zero."""
    global _PATCHED
    with _PATCH_LOCK:
        _PATCHED = max(0, _PATCHED - 1)
        if _PATCHED == 0 and _ORIG:
            for mod, name, _ in _SYMBOLS:
                setattr(mod, name, _ORIG.pop(name))
