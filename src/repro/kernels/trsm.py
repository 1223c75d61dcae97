"""Pallas TPU TRSM: triangular solve with a MXU-friendly decomposition.

CUDA trsm implementations are warp-synchronous substitution engines; that
mechanism has no TPU analogue, so this is a *re-design* for the MXU
(DESIGN.md hardware-adaptation): a divide-and-conquer blocked solve

    [A11  0 ] [X1]   [B1]      X1 = trsm(A11, B1)
    [A21 A22] [X2] = [B2]  =>  X2 = trsm(A22, B2 - A21 @ X1)

where all the heavy FLOPs are the ``A21 @ X1`` updates executed by the
Pallas GEMM kernel (exactly how cuBLAS reduces trsm to gemm), and only the
``base``-sized diagonal blocks run a row-substitution Pallas kernel on the
VPU. Total FLOPs match textbook trsm (m^2 n), with log2(m/base) recursion
levels of pure MXU work.

All eight (side, uplo, trans) variants canonicalize to lower-left-N via
conjugation/transpose/flip identities in :func:`trsm`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from repro.kernels.gemm import fp32_precision, gemm

BASE = 128


def _trsm_base_kernel(l_ref, b_ref, x_ref, *, nb: int, unit: bool):
    """Solve L x = b for one (nb x nb) lower block and (nb x bn) panel.

    Sequential row substitution; the panel dimension is vectorized on the
    VPU. Rows >= i of the scratch still hold unsolved values, so the dot
    masks columns >= i.
    """
    x_ref[...] = b_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, nb), 1)

    def body(i, carry):
        l_row = l_ref[pl.ds(i, 1), :]                              # (1, nb)
        l_masked = jnp.where(col < i, l_row, 0.0).astype(x_ref.dtype)
        partial = jnp.dot(l_masked, x_ref[...],
                          preferred_element_type=x_ref.dtype,
                          precision=fp32_precision(x_ref.dtype))  # (1, bn)
        upd = x_ref[pl.ds(i, 1), :] - partial
        if not unit:
            # the diagonal entry, picked out of the row already loaded (a
            # one-lane dynamic load is not tile-aligned)
            diag = jnp.sum(jnp.where(col == i, l_row, 0.0), axis=1,
                           keepdims=True)
            upd = upd / diag.astype(x_ref.dtype)
        x_ref[pl.ds(i, 1), :] = upd
        return carry

    # int32 bounds: Mosaic has no 64-bit index (jax_enable_x64 would
    # otherwise make the loop counter int64)
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(nb), body, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("unit", "bn", "interpret"))
def _trsm_base(l: jax.Array, b: jax.Array, *, unit: bool, bn: int = 256,
               interpret: bool = False) -> jax.Array:
    """Base-case solve via the Pallas substitution kernel."""
    nb, n = l.shape[0], b.shape[1]
    pad_n = (-n) % bn
    bp = jnp.pad(b, ((0, 0), (0, pad_n))) if pad_n else b
    grid = (bp.shape[1] // bn,)
    out = pl.pallas_call(
        functools.partial(_trsm_base_kernel, nb=nb, unit=unit),
        grid=grid,
        # int32 block indices (a literal 0 is int64 under x64)
        in_specs=[
            pl.BlockSpec((nb, nb), lambda j: (j * 0, j * 0)),
            pl.BlockSpec((nb, bn), lambda j: (j * 0, j)),
        ],
        out_specs=pl.BlockSpec((nb, bn), lambda j: (j * 0, j)),
        out_shape=jax.ShapeDtypeStruct(bp.shape, b.dtype),
        interpret=interpret,
    )(l, bp)
    return out[:, :n]


def _solve_lower(l: jax.Array, b: jax.Array, *, unit: bool,
                 interpret: bool) -> jax.Array:
    """Recursive lower-left-N solve; shapes are static so the recursion
    unrolls at trace time into a log-depth chain of Pallas GEMMs."""
    m = l.shape[0]
    if m <= BASE:
        return _trsm_base(l, b, unit=unit, interpret=interpret)
    # split at the largest power-of-two half for aligned gemm shapes
    half = max(BASE, 1 << (m - 1).bit_length() - 1)
    if half >= m:
        half = m // 2
    a11, a21, a22 = l[:half, :half], l[half:, :half], l[half:, half:]
    x1 = _solve_lower(a11, b[:half], unit=unit, interpret=interpret)
    upd = gemm(a21, x1, interpret=interpret) if not jnp.issubdtype(
        l.dtype, jnp.complexfloating) else a21 @ x1
    x2 = _solve_lower(a22, b[half:] - upd, unit=unit, interpret=interpret)
    return jnp.concatenate([x1, x2], axis=0)


@functools.partial(jax.jit, static_argnames=("side", "uplo", "trans",
                                             "diag", "interpret"))
def trsm(a: jax.Array, b: jax.Array, *, side: str = "L", uplo: str = "L",
         trans: str = "N", diag: str = "N",
         interpret: bool = False) -> jax.Array:
    """Solve op(A) X = B (side=L) or X op(A) = B (side=R)."""
    unit = diag == "U"
    if side == "R":
        # X op(A) = B  <=>  op(A)^T X^T = B^T
        flip_t = {"N": "T", "T": "N", "C": "N"}[trans]
        a_ = jnp.conj(a) if trans == "C" else a
        out = trsm(a_, b.mT, side="L", uplo=uplo, trans=flip_t,
                   diag=diag, interpret=interpret)
        return out.mT
    if trans != "N":
        # op(A) X = B with A lower  <=>  solve with upper A^(T|H)
        a_ = jnp.conj(a.mT) if trans == "C" else a.mT
        new_uplo = "U" if uplo == "L" else "L"
        return trsm(a_, b, side="L", uplo=new_uplo, trans="N", diag=diag,
                    interpret=interpret)
    if uplo == "U":
        # U X = B  <=>  (J U J)(J X) = (J B), J = index reversal
        lj = jnp.flip(a, axis=(-2, -1))
        bj = jnp.flip(b, axis=-2)
        xj = trsm(lj, bj, side="L", uplo="L", trans="N", diag=diag,
                  interpret=interpret)
        return jnp.flip(xj, axis=-2)
    if a.ndim > 2:  # batched: vmap the canonical solve
        f = functools.partial(trsm, side="L", uplo="L", trans="N",
                              diag=diag, interpret=interpret)
        return jax.vmap(f)(a, b)
    return _solve_lower(a, b, unit=unit, interpret=interpret)
