"""Pallas TPU decode attention (single new token vs a long KV cache).

The decode cells are the worst roofline rows in EXPERIMENTS.md §Roofline:
one token against a 32k-entry cache is pure HBM streaming, and the XLA
path re-reads the padded cache with masking applied afterwards. This
kernel streams the cache once, block-by-block, with online softmax and
``kv_len`` masking fused in, and skips dead blocks entirely
(``pl.when`` on the block index) — so a cache filled to 25 % costs 25 %.

Grid ``(B, Hkv, Tk/bk)``: one program per (batch row, KV head, key
block); the GQA query group (Hq/Hkv rows) rides the sublane dimension of
a ``(group, bk)`` logit tile. f32 running max/denominator/accumulator
live in VMEM scratch across the key-block sweep.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(lenref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, bk: int, k_steps: int, scale: float,
                   softcap: float):
    s = pl.program_id(2)
    kv_len = lenref[0]

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # live if any key position in this block is < kv_len
    @pl.when(s * bk < kv_len)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)          # (group, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, D)
        logits = jnp.dot(q, k.T,
                         preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            logits = jnp.tanh(logits / softcap) * softcap
        kpos = s * bk + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        logits = jnp.where(kpos < kv_len, logits, NEG_INF)

        m_prev = m_ref[...]                          # (group, 1)
        m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(s == k_steps - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "scale", "bk",
                                             "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len: jax.Array, *, softcap: float = 0.0,
                     scale: Optional[float] = None, bk: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: [B,Hq,1,D]; k,v: [B,Hkv,S,D]; kv_len: scalar live length.

    Returns [B,Hq,1,D]. Equivalent to ``ref.attention(..., causal=True,
    kv_len=kv_len)`` for a single right-aligned query token.
    """
    b, hq, tq, d = q.shape
    _, hkv, s, _ = k.shape
    assert tq == 1, "decode kernel is single-token"
    group = hq // hkv
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))
    bk_ = min(bk, s)
    pad = (-s) % bk_
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    sp = k.shape[2]
    qg = q.reshape(b, hkv, group, d)
    grid = (b, hkv, sp // bk_)
    lenvec = jnp.asarray(kv_len, jnp.int32).reshape(1)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, bk=bk_, k_steps=grid[2],
                          scale=scale, softcap=softcap),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, group, d), lambda bb, h, s_: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, bk_, d), lambda bb, h, s_: (bb, h, s_, 0)),
            pl.BlockSpec((1, 1, bk_, d), lambda bb, h, s_: (bb, h, s_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, d),
                               lambda bb, h, s_: (bb, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lenvec, qg, k, v)
    return out.reshape(b, hq, 1, d)
