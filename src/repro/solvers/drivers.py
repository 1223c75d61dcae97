"""Span-wrapped LAPACK drivers: each call is one solver *span*.

A span is the runtime's unit of solver work: ``solver_begin`` pins the
in-place factor buffer on the device tier (it is re-read by every panel
update — the ~780x-reuse pattern ``apps/lsms.py`` documents), stamps
every inner BLAS call with the span's ``solver_id``, and emits a
``solver_begin``/``solver_end`` event pair into the trace so the
memtier simulator can replay per-solver counters count-for-count.
Without an active runtime the drivers degrade to plain
:mod:`repro.core.lapack` / :mod:`repro.solvers.eigen` calls.
"""
from __future__ import annotations

import contextlib
from typing import Tuple

import jax

from repro.core import lapack
from repro.core import runtime as rtm
from repro.core import trace as trc
from repro.solvers import eigen as _eigen


def _resolve_nb(nb: int) -> int:
    """Explicit ``nb`` wins; else the active session's ``lapack_nb``
    (``SCILIB_LAPACK_NB``); else the driver default."""
    if nb:
        return nb
    rt = rtm.active()
    if rt is not None and rt.config.lapack_nb:
        return rt.config.lapack_nb
    return lapack.DEFAULT_NB


@contextlib.contextmanager
def _span(name: str, factor=None):
    with trc.span(trc.SOLVER_SPANS[name]):
        rt = rtm.active()
        if rt is None:
            yield None
            return
        span = rt.solver_begin(name, factor)
        try:
            yield span
        finally:
            rt.solver_end(span)


# --------------------------------------------------------------------- #
# LU tier                                                                #
# --------------------------------------------------------------------- #
def getrf(a: jax.Array, nb: int = 0) -> Tuple[jax.Array, jax.Array]:
    """Blocked LU with partial pivoting, as one solver span."""
    with _span("getrf", a):
        return lapack.getrf(a, nb=_resolve_nb(nb))


def getrs(lu: jax.Array, piv: jax.Array, b: jax.Array) -> jax.Array:
    """Solve from getrf output (laswp + two trsms), as one span."""
    with _span("getrs", lu):
        return lapack.getrs(lu, piv, b)


def gesv(a: jax.Array, b: jax.Array, nb: int = 0) -> jax.Array:
    """Factor-and-solve (the zgetrf+zgetrs pair MuST calls) — one span
    covering both phases, so the LU factor stays pinned through the
    triangular solves that re-read it."""
    with _span("gesv", a):
        nbv = _resolve_nb(nb)
        lu, piv = lapack.getrf(a, nb=nbv)
        return lapack.getrs(lu, piv, b)


# --------------------------------------------------------------------- #
# Cholesky tier                                                          #
# --------------------------------------------------------------------- #
def potrf(a: jax.Array, nb: int = 0, *, uplo: str = "L") -> jax.Array:
    """Blocked Cholesky (real-symmetric or complex-Hermitian)."""
    with _span("potrf", a):
        return lapack.potrf(a, _resolve_nb(nb), uplo=uplo)


def potrs(f: jax.Array, b: jax.Array, *, uplo: str = "L") -> jax.Array:
    """Solve from potrf output (two triangular solves)."""
    with _span("potrs", f):
        return lapack.potrs(f, b, uplo=uplo)


# --------------------------------------------------------------------- #
# eigensolver tier                                                       #
# --------------------------------------------------------------------- #
def syev(a: jax.Array, nb: int = 0, *,
         uplo: str = "L") -> Tuple[jax.Array, jax.Array]:
    """Hermitian eigensolve: blocked tridiagonalization + host
    tridiagonal solve + blocked back-transform, as one span."""
    with _span("syev", a):
        return _eigen.syev(a, nb=_resolve_nb(nb), uplo=uplo)
