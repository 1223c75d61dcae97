"""Blocked LAPACK routines built on the intercepted BLAS (paper §4.2).

MuST's hot path is LU factorization/solve (``zgetrf``/``zgetrs``) whose
inner loops are the very ``zgemm``/``ztrsm`` calls SCILIB-Accel offloads.
This module reproduces that call structure: right-looking blocked LU with
partial pivoting, triangular solves, and blocked Cholesky — every panel
update flows through :mod:`repro.core.blas`, so an installed offload
runtime sees exactly the BLAS stream a LAPACK-linked binary would emit.

These are eager, host-orchestrated drivers (like LAPACK itself: Python
plays the role of the Fortran driver; the FLOPs are in the BLAS calls).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import blas
from repro.core import memspace
from repro.core import runtime as rtm
from repro.core import trace as trc

DEFAULT_NB = 128

_PREC = {"float32": "s", "float64": "d",
         "complex64": "c", "complex128": "z"}


def _prec(dtype) -> str:
    return _PREC.get(jnp.dtype(dtype).name, "d")


def _note_panel(prec: str, m: int, nb: int, panel: jax.Array) -> None:
    """Report an unblocked panel factorization to the active runtime.

    Panels are getf2 work on the factor's tier — inside a solver span
    (repro.solvers) they count toward the span's panel fraction, as
    offloaded or host calls by the factored panel's tier, and appear as
    ``getf2`` trace calls.  Outside a span this is a no-op, keeping
    direct driver calls byte-identical to before."""
    rt = rtm.active()
    if rt is not None:
        rt.note_panel(prec, m, nb, panel)


def _store(factor: jax.Array, idx, block: jax.Array) -> jax.Array:
    """``factor.at[idx].set(block)`` with the block first brought to the
    factor's tier: an update the runtime kept on the host must not drag
    the whole factor off the device."""
    with trc.span(trc.LU_STORE):
        return factor.at[idx].set(
            memspace.put(block, memspace.tier_of(factor), check=False))


@jax.jit
def _getf2(p: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Unblocked LU with partial pivoting on a (m x nb) panel; returns
    the factored panel and its local pivot rows."""
    m, nb = p.shape

    def body(j, carry):
        p, piv = carry
        col = p[:, j]
        mag = jnp.abs(col)
        mask = jnp.arange(m) < j
        mag = jnp.where(mask, -jnp.inf, mag)
        r = jnp.argmax(mag)
        piv = piv.at[j].set(r.astype(piv.dtype))
        # swap rows j <-> r
        rowj, rowr = p[j], p[r]
        p = p.at[j].set(rowr).at[r].set(rowj)
        pivval = p[j, j]
        scale = jnp.where(pivval != 0, 1.0 / pivval, 0.0)
        below = jnp.arange(m) > j
        l = jnp.where(below, p[:, j] * scale, p[:, j])
        p = p.at[:, j].set(l)
        # rank-1 update of the trailing panel columns
        trail = jnp.arange(nb) > j
        lcol = jnp.where(below, l, 0.0)[:, None]
        urow = jnp.where(trail, p[j], 0.0)[None, :]
        p = p - lcol * urow
        return p, piv

    piv0 = jnp.zeros(nb, dtype=jnp.int32)
    return jax.lax.fori_loop(0, nb, body, (p, piv0))


def _pivot_panel(panel: jax.Array, tier: str
                 ) -> Tuple[jax.Array, np.ndarray]:
    """getf2 on the tier that holds the factor: a device-resident
    factor's panel is factored on the device and stays there, and only
    its ``nb`` local pivot rows come to the host; a host-resident
    factor's panel is factored on the host.  Returns the factored panel,
    on ``tier``, and the local pivot rows as numpy."""
    fpanel, lpiv = _getf2(panel)
    # the CPU backend hands a program's output back in its device memory
    # kind whatever the input's; elsewhere the panel is already on tier
    return memspace.put(fpanel, tier, check=False), memspace.pull(lpiv)


def getrf(a: jax.Array, nb: int = DEFAULT_NB
          ) -> Tuple[jax.Array, jax.Array]:
    """Blocked right-looking LU with partial pivoting (general m x n).

    Returns (LU, piv): ``piv`` is the absolute row permutation (length
    ``m``) such that ``A[piv] == L @ U`` — the composed form of LAPACK's
    sequential ipiv swaps. The trailing-matrix updates are the trsm+gemm
    pairs that dominate MuST's runtime.
    """
    m, n = a.shape
    prec = _prec(a.dtype)
    k_max = min(m, n)
    # every panel is factored where the caller's factor lives
    tier = memspace.tier_of(a)
    lu = a
    piv = np.arange(m, dtype=np.int32)
    for j0 in range(0, k_max, nb):
        jb = min(nb, k_max - j0)
        panel = lu[j0:, j0:j0 + jb]
        with trc.span(trc.LU_PANEL):
            fpanel, lpiv = _pivot_panel(panel, tier)
        _note_panel(prec, m - j0, jb, fpanel)
        # apply local pivots to the whole rows (left + right of panel)
        with trc.span(trc.LU_PERMUTE):
            perm = np.arange(m - j0)
            for jj in range(jb):       # compose swaps (host loop, nb small)
                r = lpiv[jj]
                perm[jj], perm[r] = perm[r], perm[jj]
            abs_perm = np.concatenate([np.arange(j0), perm + j0])
            lu = lu[abs_perm]
            piv = piv[abs_perm]
        lu = _store(lu, (slice(j0, None), slice(j0, j0 + jb)), fpanel)
        if j0 + jb < n:
            # U12 = L11^{-1} A12           (trsm, unit lower)
            a12 = lu[j0:j0 + jb, j0 + jb:]
            l11 = lu[j0:j0 + jb, j0:j0 + jb]
            u12 = blas.trsm(l11, a12, side="L", uplo="L", trans="N",
                            diag="U")
            lu = _store(lu, (slice(j0, j0 + jb), slice(j0 + jb, None)), u12)
            if j0 + jb < m:
                # A22 -= L21 U12           (gemm: the hot spot)
                l21 = lu[j0 + jb:, j0:j0 + jb]
                a22 = lu[j0 + jb:, j0 + jb:]
                upd = blas.gemm(l21, u12, a22, alpha=-1.0, beta=1.0)
                lu = _store(lu, (slice(j0 + jb, None),
                                 slice(j0 + jb, None)), upd)
    return lu, jnp.asarray(piv)


def getrs(lu: jax.Array, piv: jax.Array, b: jax.Array) -> jax.Array:
    """Solve A X = B from getrf output (laswp + two trsm calls)."""
    if b.ndim == 1:
        b = b[:, None]
        squeeze = True
    else:
        squeeze = False
    # getrf returned LU = (P A) with piv the absolute row permutation:
    # A x = b  <=>  LU x = (P b)
    x = b[piv]
    y = blas.trsm(lu, x, side="L", uplo="L", trans="N", diag="U")
    z = blas.trsm(lu, y, side="L", uplo="U", trans="N", diag="N")
    return z[:, 0] if squeeze else z


def gesv(a: jax.Array, b: jax.Array, nb: int = DEFAULT_NB) -> jax.Array:
    """Driver: solve A X = B (the zgetrf+zgetrs pair MuST calls)."""
    lu, piv = getrf(a, nb=nb)
    return getrs(lu, piv, b)


@jax.jit
def _chol_block(blk: jax.Array) -> jax.Array:
    # jnp.linalg.cholesky symmetrizes its input, so feed full blocks
    return jnp.linalg.cholesky(blk)


def potrf(a: jax.Array, nb: int = DEFAULT_NB, *,
          uplo: str = "L") -> jax.Array:
    """Blocked Cholesky (syrk-shaped gemm + trsm + small unblocked factor).

    Handles real-symmetric and complex-Hermitian inputs (the updates use
    conjugate transposes, which reduce to plain transposes for real
    dtypes).  ``uplo="U"`` factors the conjugate-transposed matrix and
    returns ``U`` with ``A = U^H U``.
    """
    if uplo == "U":
        l = potrf(jnp.conj(a.T), nb, uplo="L")
        return jnp.conj(l.T)
    n = a.shape[0]
    l = jnp.zeros(a.shape, a.dtype)

    for j0 in range(0, n, nb):
        jb = min(nb, n - j0)
        # diagonal block: A11 - L10 L10^H
        l10 = l[j0:j0 + jb, :j0]
        a11 = a[j0:j0 + jb, j0:j0 + jb]
        if j0 > 0:
            a11 = blas.gemm(l10, l10, a11, alpha=-1.0, beta=1.0,
                            trans_b="C")
        l11 = _chol_block(a11)
        l = _store(l, (slice(j0, j0 + jb), slice(j0, j0 + jb)), l11)
        if j0 + jb < n:
            l20 = l[j0 + jb:, :j0]
            a21 = a[j0 + jb:, j0:j0 + jb]
            if j0 > 0:
                a21 = blas.gemm(l20, l10, a21, alpha=-1.0, beta=1.0,
                                trans_b="C")
            # L21 = A21 L11^{-H}    (right-side trsm)
            l21 = blas.trsm(l11, a21, side="R", uplo="L", trans="C",
                            diag="N")
            l = _store(l, (slice(j0 + jb, None), slice(j0, j0 + jb)), l21)
    return l


def potrs(f: jax.Array, b: jax.Array, *, uplo: str = "L") -> jax.Array:
    """Solve A X = B from potrf output (two triangular solves)."""
    if b.ndim == 1:
        b = b[:, None]
        squeeze = True
    else:
        squeeze = False
    if uplo == "L":
        # A = L L^H: solve L y = b, then L^H x = y
        y = blas.trsm(f, b, side="L", uplo="L", trans="N", diag="N")
        x = blas.trsm(f, y, side="L", uplo="L", trans="C", diag="N")
    else:
        # A = U^H U: solve U^H y = b, then U x = y
        y = blas.trsm(f, b, side="L", uplo="U", trans="C", diag="N")
        x = blas.trsm(f, y, side="L", uplo="U", trans="N", diag="N")
    return x[:, 0] if squeeze else x
