"""Blocked Hermitian eigensolver (syev/heev) on the intercepted BLAS.

LAPACK's one-stage ``?sytrd``/``?hetrd`` structure: latrd panels build
``kb`` Householder reflectors at a time (each column costs one big
symmetric/Hermitian matvec through :mod:`repro.core.blas` plus small
V/W corrections), the trailing submatrix is updated with one rank-2k
``syr2k``/``her2k`` per panel — the level-3 call the offload runtime
feeds on — the resulting real tridiagonal system is solved on the host
(it is O(n) data, far below any offload threshold), and eigenvectors
are back-transformed panel-by-panel with compact-WY gemms.

Only the lower triangle of the working matrix is referenced and
updated throughout (``uplo="U"`` inputs are mirrored up front), exactly
the storage discipline of the LAPACK routines this reproduces.
"""
from __future__ import annotations

import math
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from scipy.linalg import eigh_tridiagonal

from repro.core import blas
from repro.core.lapack import DEFAULT_NB


def _hermitize(a: jax.Array, uplo: str) -> jax.Array:
    """Full Hermitian matrix from the referenced triangle (the other
    triangle of a LAPACK-convention input may hold garbage)."""
    tri = jnp.triu(a, 1) if uplo == "U" else jnp.tril(a, -1)
    dg = jnp.real(jnp.diagonal(a)).astype(a.dtype)
    return tri + jnp.conj(tri.T) + jnp.diag(dg)


def _larfg(alpha, x: jax.Array, dtype) -> Tuple[float, jax.Array, complex]:
    """Elementary reflector (zlarfg): returns ``(beta, v, tau)`` with
    ``beta`` real, ``v[0] == 1``, and
    ``(I - tau v v^H)^H [alpha; x] = [beta; 0]``."""
    iscomplex = jnp.issubdtype(dtype, jnp.complexfloating)
    a = complex(alpha)
    xnorm = float(jnp.linalg.norm(x)) if x.size else 0.0
    one = jnp.ones((1,), dtype=dtype)
    if xnorm == 0.0 and a.imag == 0.0:
        # already tridiagonal-real here: H = I
        return a.real, jnp.concatenate([one, x]), 0j if iscomplex else 0.0
    beta = -math.copysign(
        math.sqrt(a.real * a.real + a.imag * a.imag + xnorm * xnorm),
        a.real)
    tau = (beta - a) / beta
    scale = 1.0 / (a - beta)
    if not iscomplex:             # exact: a.imag == 0 on the real path
        tau, scale = tau.real, scale.real
    v = jnp.concatenate([one, x * scale])
    return beta, v, tau


def _sytrd(a: jax.Array, nb: int
           ) -> Tuple[np.ndarray, np.ndarray, List[tuple]]:
    """Blocked lower tridiagonalization ``A = Q T Q^H``.

    Returns ``(d, e, panels)``: the real tridiagonal (host numpy), and
    per-panel ``(k0, V, taus)`` reflector storage for the
    back-transform.  ``A`` is consumed lower-triangle-only: the latrd
    matvec reads the (not yet updated) trailing block through
    ``symm``/``hemm`` and the deferred rank-2k update writes the lower
    triangle via ``syr2k``/``her2k`` — one level-3 call per panel.
    """
    n = a.shape[0]
    dtype = a.dtype
    iscomplex = jnp.issubdtype(dtype, jnp.complexfloating)
    matvec = blas.hemm if iscomplex else blas.symm
    rank2 = blas.her2k if iscomplex else blas.syr2k
    d = np.zeros(n)
    e = np.zeros(max(0, n - 1))
    panels: List[tuple] = []
    A = a
    k0 = 0
    while n - k0 > 1:
        m = n - k0
        kb = min(nb, m - 1)
        A2 = A[k0:, k0:]
        V = jnp.zeros((m, kb), dtype=dtype)
        W = jnp.zeros((m, kb), dtype=dtype)
        taus: List[complex] = []
        for j in range(kb):
            # column j under the panel's previous reflectors (deferred
            # update: A - V W^H - W V^H); rows < j are never read
            col = (A2[:, j] - V @ jnp.conj(W[j, :])
                   - W @ jnp.conj(V[j, :]))
            d[k0 + j] = float(jnp.real(col[j]))
            beta, v, tau = _larfg(col[j + 1], col[j + 2:], dtype)
            e[k0 + j] = beta
            taus.append(tau)
            V = V.at[j + 1:, j].set(v)
            vfull = jnp.zeros(m, dtype=dtype).at[j + 1:].set(v)
            # w = tau (A v - V(W^H v) - W(V^H v)) - (tau/2)(w^H v) v:
            # the big matvec runs on the pre-panel trailing block (rows
            # <= j of the product are discarded by the masking below)
            p = matvec(A2, vfull[:, None], side="L", uplo="L")[:, 0]
            p = (p - V @ (jnp.conj(W.T) @ vfull)
                 - W @ (jnp.conj(V.T) @ vfull))
            w = (tau * p).at[:j + 1].set(0)
            w = w + (-0.5 * tau * (jnp.conj(w) @ vfull)) * vfull
            W = W.at[:, j].set(w)
        panels.append((k0, V, taus))
        if k0 + kb < n:
            # the deferred rank-2k trailing update: the panel's one
            # level-3 call, and the offload runtime's hot spot here
            upd = rank2(V[kb:, :], W[kb:, :], A[k0 + kb:, k0 + kb:],
                        uplo="L", trans="N", alpha=-1.0, beta=1.0)
            A = A.at[k0 + kb:, k0 + kb:].set(upd)
        k0 += kb
    if k0 < n:
        d[n - 1] = float(jnp.real(A[n - 1, n - 1]))
    return d, e, panels


def _tridiag_eigh(d: np.ndarray, e: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Host eigensolve of the real tridiagonal (O(n) data: far below
    any offload threshold, exactly where LAPACK keeps it too)."""
    return eigh_tridiagonal(d, e)


def _larft(V: jax.Array, taus: List[complex]) -> np.ndarray:
    """Compact-WY triangular factor for the forward product
    ``H_0 H_1 ... H_{kb-1} = I - V T V^H`` (larft, forward/columnwise;
    kb x kb — built on the host)."""
    kb = len(taus)
    Vn = np.asarray(V)
    T = np.zeros((kb, kb), dtype=Vn.dtype)
    for j, tau in enumerate(taus):
        if j > 0:
            T[:j, j] = -tau * (T[:j, :j]
                               @ (Vn[:, :j].conj().T @ Vn[:, j]))
        T[j, j] = tau
    return T


def _apply_q(panels: List[tuple], z: np.ndarray, dtype) -> jax.Array:
    """Back-transform ``S = Q Z``: apply the panel products in reverse
    order, each as two big gemms around a small T application."""
    s = jnp.asarray(z, dtype=dtype)
    for k0, V, taus in reversed(panels):
        T = jnp.asarray(_larft(V, taus), dtype=dtype)
        s2 = s[k0:, :]
        x = blas.gemm(V, s2, trans_a="C")       # V^H S
        x = T @ x                               # small kb x kb apply
        s2 = blas.gemm(V, x, s2, alpha=-1.0, beta=1.0)
        s = s.at[k0:, :].set(s2)
    return s


def syev(a: jax.Array, nb: int = DEFAULT_NB, *,
         uplo: str = "L") -> Tuple[jax.Array, jax.Array]:
    """Eigendecomposition of a Hermitian matrix: ``A = S diag(w) S^H``.

    Returns ``(w, S)`` with ``w`` real ascending and ``S`` the
    eigenvector columns, matching ``scipy.linalg.eigh``.
    """
    n = a.shape[0]
    dtype = a.dtype
    rdtype = np.zeros(0, dtype=np.dtype(dtype)).real.dtype
    if n == 0:
        return (jnp.zeros(0, dtype=rdtype),
                jnp.zeros((0, 0), dtype=dtype))
    if n == 1:
        return (jnp.real(a[0, 0]).astype(rdtype).reshape(1),
                jnp.ones((1, 1), dtype=dtype))
    full = _hermitize(a, uplo)
    d, e, panels = _sytrd(full, nb=max(1, nb))
    w, z = _tridiag_eigh(d, e)
    s = _apply_q(panels, z, dtype)
    return jnp.asarray(w, dtype=rdtype), s
