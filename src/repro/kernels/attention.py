"""Pallas TPU flash attention (prefill/train path).

Online-softmax attention tiled for VMEM: grid ``(batch*q_heads, Tq/bq,
Tk/bk)``, f32 running max/denominator/accumulator in VMEM scratch. GQA is
native — the K/V BlockSpec index maps divide the head id by the group
size, so K/V are never materialized per-q-head. Supports causal masking,
sliding-window locality and tanh logit soft-capping (gemma2's local/global
layers), and skips fully-masked key blocks (``pl.when`` on block ids) so
causal prefill does ~half the MXU work.

Decode (Tq=1) uses the XLA reference path — a 1-row MXU tile would waste
127/128 of the systolic array; XLA's fused GEMV path is the right tool.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  k_steps: int, bq: int, bk: int, scale: float,
                  causal: bool, window: int, softcap: float,
                  q_offset: int):
    iq, s = pl.program_id(1), pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions (right-aligned when Tq < Tk, e.g. chunked prefill)
    q_start = iq * bq + q_offset
    k_start = s * bk

    # skip key blocks that are entirely masked out
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + bq - 1
    if window > 0:
        live &= k_start + bk - 1 >= q_start - window + 1

    @pl.when(live)
    def _update():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            logits = jnp.tanh(logits / softcap) * softcap
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=bool)
        if causal:
            mask &= qpos >= kpos
        if window > 0:
            mask &= qpos - kpos < window
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_ref[...]                                   # (bq, 1)
        m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)                       # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(s == k_steps - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: Optional[float] = None,
                    bq: int = 256, bk: int = 256,
                    interpret: bool = False) -> jax.Array:
    """q: [B,Hq,Tq,D]; k,v: [B,Hkv,Tk,D] with Hq % Hkv == 0."""
    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    group = hq // hkv
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))

    bq_ = min(bq, tq)
    bk_ = min(bk, tk)
    pad_q, pad_k = (-tq) % bq_, (-tk) % bk_
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else v

    qf = qp.reshape(b * hq, qp.shape[2], d)
    kf = kp.reshape(b * hkv, kp.shape[2], d)
    vf = vp.reshape(b * hkv, vp.shape[2], d)
    grid = (b * hq, qf.shape[1] // bq_, kf.shape[1] // bk_)
    q_offset = tk - tq  # right-aligned query positions

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, k_steps=grid[2], bq=bq_, bk=bk_, scale=scale,
            causal=causal, window=window, softcap=softcap,
            q_offset=q_offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq_, d), lambda h, i, s: (h, i, 0)),
            pl.BlockSpec((1, bk_, d),
                         lambda h, i, s, g=group: (h // g, s, 0)),
            pl.BlockSpec((1, bk_, d),
                         lambda h, i, s, g=group: (h // g, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, d), lambda h, i, s: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(b, hq, qf.shape[1], d)
    return out[:, :, :tq] if pad_q else out
