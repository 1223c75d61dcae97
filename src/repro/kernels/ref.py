"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the shape/dtype sweep tests: each kernel's
output is ``assert_allclose``-checked against the function of the same
name here.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def matmul(a: jax.Array, b: jax.Array,
           out_dtype=None) -> jax.Array:
    """C = A @ B with f32 accumulation (MXU convention); f32 and
    complex64 products at full f32 precision, as BLAS ``s``/``c`` promise
    (a TPU's default is one bf16 pass)."""
    acc_t = jnp.float32
    if a.dtype in (jnp.float64, jnp.complex64, jnp.complex128):
        acc_t = a.dtype
    full = a.dtype in (jnp.float32, jnp.complex64)
    out = jnp.matmul(a, b, preferred_element_type=acc_t,
                     precision=jax.lax.Precision.HIGHEST if full else None)
    return out.astype(out_dtype or a.dtype)


def trsm(a: jax.Array, b: jax.Array, *, side: str = "L", uplo: str = "L",
         trans: str = "N", diag: str = "N") -> jax.Array:
    """Solve op(A) X = B (side=L) or X op(A) = B (side=R)."""
    lower = uplo == "L"
    unit = diag == "U"
    ta = {"N": 0, "T": 1, "C": 2}[trans]
    return jax.lax.linalg.triangular_solve(
        a, b, left_side=(side == "L"), lower=lower,
        transpose_a=(ta != 0), conjugate_a=(ta == 2),
        unit_diagonal=unit)


def syrk(a: jax.Array, *, uplo: str = "L", trans: str = "N") -> jax.Array:
    """C = op(A) op(A)^T, only the ``uplo`` triangle populated."""
    opa = a if trans == "N" else jnp.swapaxes(a, -1, -2)
    full = matmul(opa, jnp.swapaxes(opa, -1, -2))
    return jnp.tril(full) if uplo == "L" else jnp.triu(full)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: int = 0,
              softcap: float = 0.0, scale: Optional[float] = None,
              kv_len: Optional[jax.Array] = None,
              out_dtype=None) -> jax.Array:
    """Reference attention. q: [B,Hq,Tq,D]; k,v: [B,Hkv,Tk,D].

    GQA is expressed by Hq a multiple of Hkv. ``window`` > 0 restricts each
    query to the last ``window`` keys (gemma2 local layers); ``softcap``
    applies tanh logit soft-capping (gemma2). ``kv_len`` masks a
    pre-allocated decode cache: only keys < kv_len are live, and queries
    sit right-aligned at positions ``kv_len - Tq .. kv_len - 1``.
    """
    b, hq, tq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    kf = jnp.repeat(kf, group, axis=1)
    vf = jnp.repeat(vf, group, axis=1)
    s = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * s
    if softcap > 0.0:
        logits = jnp.tanh(logits / softcap) * softcap
    tk = k.shape[2]
    live_len = kv_len if kv_len is not None else tk
    qpos = jnp.arange(tq)[:, None] + (live_len - tq)  # right-aligned
    kpos = jnp.arange(tk)[None, :]
    mask = kpos < live_len
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= qpos - kpos < window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    return out.astype(out_dtype or q.dtype)


def attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      chunk_q: int, softcap: float = 0.0,
                      scale: Optional[float] = None,
                      out_dtype=None) -> jax.Array:
    """Causal attention in query chunks with causally-sliced keys.

    XLA-expressible flash-style saving: query chunk i only multiplies
    against keys [0, (i+1)*chunk_q) — static shapes per chunk, so the
    masked upper triangle is never computed or materialized. FLOPs and
    logits memory drop to ~(n+1)/2n of the full T^2 formulation.
    Gradients flow through each chunk independently (exact).
    """
    b, hq, t, d = q.shape
    assert t % chunk_q == 0, (t, chunk_q)
    outs = []
    for i in range(t // chunk_q):
        qs = q[:, :, i * chunk_q:(i + 1) * chunk_q]
        klen = (i + 1) * chunk_q
        outs.append(attention(qs, k[:, :, :klen], v[:, :, :klen],
                              causal=True, softcap=softcap, scale=scale,
                              out_dtype=out_dtype))
    return jnp.concatenate(outs, axis=2)
