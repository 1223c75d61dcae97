"""Pallas TPU SYRK: rank-k update writing only one triangle.

C = A @ A^T touches only n(n+1)/2 output blocks; the kernel skips the MXU
work for blocks strictly on the wrong side of the diagonal (``pl.when`` on
block ids — the TPU equivalent of cuBLAS's triangle-restricted tile
scheduling), halving compute vs. a full GEMM. Off-triangle blocks are
zero-filled so the result composes with the full-storage BLAS semantics
in ``repro.core.blas``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gemm import fp32_precision


def _syrk_kernel(a_ref, at_ref, o_ref, acc_ref, *, k_steps: int,
                 lower: bool):
    i, j = pl.program_id(0), pl.program_id(1)
    s = pl.program_id(2)
    on_tri = (j <= i) if lower else (j >= i)

    @pl.when(s == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(on_tri)
    def _update():
        acc_ref[...] += jnp.dot(a_ref[...], at_ref[...],
                                preferred_element_type=acc_ref.dtype,
                                precision=fp32_precision(a_ref.dtype))

    @pl.when(s == k_steps - 1)
    def _store():
        # blocks straddling the diagonal get masked at the wrapper
        o_ref[...] = jnp.where(on_tri, acc_ref[...], 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("uplo", "trans", "bm", "bk",
                                             "interpret"))
def syrk(a: jax.Array, *, uplo: str = "L", trans: str = "N", bm: int = 256,
         bk: int = 256, interpret: bool = False) -> jax.Array:
    """C = op(A) op(A)^T, only the ``uplo`` triangle populated."""
    opa = a if trans == "N" else a.mT
    n, k = opa.shape
    pad_n, pad_k = (-n) % bm, (-k) % bk
    if pad_n or pad_k:
        opa = jnp.pad(opa, ((0, pad_n), (0, pad_k)))
    npad, kpad = opa.shape
    grid = (npad // bm, npad // bm, kpad // bk)
    acc_dtype = jnp.float64 if a.dtype == jnp.float64 else jnp.float32

    out = pl.pallas_call(
        functools.partial(_syrk_kernel, k_steps=grid[2],
                          lower=(uplo == "L")),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bm), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bm), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((npad, npad), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bm), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(opa, opa.mT)[:n, :n]
    # exact triangle mask for blocks that straddle the diagonal
    return jnp.tril(out) if uplo == "L" else jnp.triu(out)
