"""Pallas TPU GEMM kernel — the compute hot spot of the whole paper.

Classic MXU-tiled matmul: grid ``(M/bm, N/bn, K/bk)`` with a float32 VMEM
accumulator revisited along the K axis. Block shapes default to
``(256, 512, 256)`` — multiples of the 128x128 MXU systolic tile, sized so
A-, B- and accumulator blocks together stay well under the ~16 MB/core
VMEM budget:

    bm*bk*2B + bk*bn*2B + bm*bn*4B = 256K*2 + 512*256*2 + 256^2*4
                                   = 0.25 + 0.25 + 0.25 MB per step (bf16)

leaving room for double-buffered pipelining of the HBM->VMEM streams.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def fp32_precision(dtype):
    """Contraction precision for a BLAS product of ``dtype`` operands: an
    f32 product at full f32 (the MXU's multi-pass mode; its default is one
    bf16 pass, which rounds every input to 8 bits), anything else at the
    operands' own precision."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=acc_ref.dtype,
                            precision=fp32_precision(a_ref.dtype))

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_to(x: jax.Array, mult: Tuple[int, int]) -> jax.Array:
    m, n = x.shape
    pm = (-m) % mult[0]
    pn = (-n) % mult[1]
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "out_dtype",
                                             "interpret"))
def gemm(a: jax.Array, b: jax.Array, *, bm: int = 256, bk: int = 256,
         bn: int = 256, out_dtype=None, interpret: bool = False
         ) -> jax.Array:
    """C = A @ B via the Pallas kernel. 2-D operands; wrapper handles
    padding to block multiples and unpadding of the result."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    acc_dtype = jnp.float64 if a.dtype == jnp.float64 else jnp.float32

    ap = _pad_to(a, (bm, bk))
    bp = _pad_to(b, (bk, bn))
    mp, kp = ap.shape
    _, np_ = bp.shape
    grid = (mp // bm, np_ // bn, kp // bk)

    out = pl.pallas_call(
        functools.partial(_gemm_kernel, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(ap, bp)
    return out[:m, :n]
