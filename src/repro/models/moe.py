"""Mixture-of-Experts layer (granite-moe, moonshot, jamba).

Two execution plans behind one parameter layout:

* ``scatter`` (default, production): top-k routing with capacity-bounded
  scatter into per-expert buffers, batched expert GEMMs, gather+combine.
  Pure pjit-shardable XLA: expert weights and buffers shard over the
  ``model`` axis (expert parallelism); the scatter/gather lower to the
  all-to-all-style collectives visible in the dry-run roofline.
* ``dense``: every expert on every token, probability-weighted — O(E)
  FLOPs, used only by the tiny smoke configs where it doubles as the
  routing oracle for tests.

The router aux (load-balance) loss follows Switch-Transformer:
``E * mean(frac_tokens_e * mean_prob_e)``.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models.layers import _dense_init
from repro.models.sharding import shard

Params = Dict[str, jax.Array]


def init_moe(key, cfg: ModelConfig) -> Params:
    d, e, ff = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    kr, kg, ku, kd = jax.random.split(key, 4)
    return {
        "router": _dense_init(kr, (d, e)),
        "wg": _dense_init(kg, (e, d, ff)),
        "wu": _dense_init(ku, (e, d, ff)),
        "wd": _dense_init(kd, (e, ff, d),
                          scale=0.02 / (2 * cfg.n_layers) ** 0.5),
    }


def _route(p: Params, cfg: ModelConfig, xf: jax.Array):
    """Router probabilities + aux loss. xf: (N, d)."""
    logits = kops.matmul(xf, p["router"].astype(xf.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.top_k)
    weights = weights / jnp.maximum(
        weights.sum(-1, keepdims=True), 1e-9)
    # Switch aux loss: encourages uniform expert load
    e = cfg.n_experts
    sel = jax.nn.one_hot(idx[:, 0], e)            # primary assignment
    frac_tokens = sel.mean(0)
    mean_prob = probs.mean(0)
    aux = e * jnp.sum(frac_tokens * mean_prob) * cfg.router_aux_coef
    return weights, idx, aux


def _expert_ffn(p: Params, xe: jax.Array) -> jax.Array:
    """Batched expert SwiGLU. xe: (E, C, d) -> (E, C, d)."""
    dt = xe.dtype
    g = kops.matmul(xe, p["wg"].astype(dt))
    u = kops.matmul(xe, p["wu"].astype(dt))
    h = shard(jax.nn.silu(g) * u, "model", None, None)
    return kops.matmul(h, p["wd"].astype(dt))


def moe_fwd(p: Params, cfg: ModelConfig, x: jax.Array,
            impl: str = "scatter") -> Tuple[jax.Array, jax.Array]:
    """x: (B, T, d) -> (out, aux_loss)."""
    if impl == "a2a":
        from repro.models.sharding import get_env
        env = get_env()
        if env is not None and env.mesh is not None \
                and cfg.n_experts % dict(env.sizes).get(env.model, 1) == 0:
            return moe_fwd_a2a(p, cfg, x, env.mesh, env.batch, env.model)
        impl = "scatter"                    # no mesh bound: fall back
    b, t, d = x.shape
    n = b * t
    xf = x.reshape(n, d)
    weights, idx, aux = _route(p, cfg, xf)

    if impl == "dense":
        # (E, N, d): every expert everywhere; weight-combine
        h = _expert_ffn(p, jnp.broadcast_to(xf, (cfg.n_experts, n, d)))
        onehot = jax.nn.one_hot(idx, cfg.n_experts,
                                dtype=jnp.float32)          # (N,k,E)
        comb = (onehot * weights[..., None]).sum(1)         # (N,E)
        out = jnp.einsum("end,ne->nd", h.astype(jnp.float32), comb)
        return out.reshape(b, t, d).astype(x.dtype), aux

    # ---------------- scatter plan ---------------- #
    e, k = cfg.n_experts, cfg.top_k
    cap = int(math.ceil(k * n * cfg.capacity_factor / e))
    cap = max(8, min(cap, n))

    flat_e = idx.reshape(-1)                                # (N*k,)
    # position-in-expert via stable sort (§Perf iteration C2): a token-
    # axis cumsum of the (N*k, E) one-hot costs O((N*k)^2)-class work in
    # XLA's reduce-window lowering; sort + tiny E-length cumsum is
    # O(N*k log) and matches megablocks' TPU-side dispatch. Stable order
    # preserves the FIFO capacity-drop semantics exactly.
    nk = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts                    # (E,) tiny
    ranks_sorted = jnp.arange(nk, dtype=jnp.int32) - starts[sorted_e]
    pos_in_e = jnp.zeros((nk,), jnp.int32).at[order].set(ranks_sorted)
    keep = pos_in_e < cap
    dest = jnp.where(keep, flat_e * cap + pos_in_e, e * cap)

    xrep = jnp.repeat(xf, k, axis=0)                        # (N*k, d)
    buf = jnp.zeros((e * cap + 1, d), x.dtype)
    buf = buf.at[dest].add(xrep)
    xe = shard(buf[:e * cap].reshape(e, cap, d), "model", None, None)

    h = _expert_ffn(p, xe)                                  # (E, C, d)

    hflat = jnp.concatenate(
        [h.reshape(e * cap, d), jnp.zeros((1, d), h.dtype)], axis=0)
    gathered = hflat[dest]                                  # (N*k, d)
    gathered = gathered.reshape(n, k, d).astype(jnp.float32)
    out = (gathered * weights[..., None]).sum(1)
    return out.reshape(b, t, d).astype(x.dtype), aux


# ----------------------------------------------------------------------- #
# explicit expert-parallel plan: shard_map + all_to_all                    #
# ----------------------------------------------------------------------- #
def _dispatch_local(cfg: ModelConfig, xf, weights, idx, cap: int):
    """Sort-based capacity dispatch on one shard. Returns (buf, dest,
    keep) with buf (E, cap, d) ordered globally by expert id."""
    e, k = cfg.n_experts, cfg.top_k
    n, d = xf.shape
    flat_e = idx.reshape(-1)
    nk = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    ranks_sorted = jnp.arange(nk, dtype=jnp.int32) - starts[sorted_e]
    pos_in_e = jnp.zeros((nk,), jnp.int32).at[order].set(ranks_sorted)
    keep = pos_in_e < cap
    dest = jnp.where(keep, flat_e * cap + pos_in_e, e * cap)
    xrep = jnp.repeat(xf, k, axis=0)
    buf = jnp.zeros((e * cap + 1, d), xf.dtype).at[dest].add(xrep)
    return buf[:e * cap].reshape(e, cap, d), dest


def moe_fwd_a2a(p: Params, cfg: ModelConfig, x: jax.Array, mesh,
                batch_axes: tuple, model_axis: str = "model"
                ) -> Tuple[jax.Array, jax.Array]:
    """Expert parallelism with explicit all-to-all (1000+-node plan).

    Tokens stay sharded over the batch axes; each device dispatches its
    local tokens into per-expert buffers, all_to_all's them to the
    expert owners along ``model_axis``, runs its expert shard's FFN, and
    all_to_all's results back — two a2a's of (k·N_loc·cf·d) bytes per
    device instead of resharding gathers. Per-device capacity semantics
    (standard for EP).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    e, k = cfg.n_experts, cfg.top_k
    m_sz = mesh.shape[model_axis]
    assert e % m_sz == 0, (e, m_sz)
    e_loc = e // m_sz
    bt = batch_axes[0] if len(batch_axes) == 1 else tuple(batch_axes)
    all_axes = tuple(mesh.axis_names)

    def body(xl, router, wg, wu, wd):
        bsz, t, d = xl.shape
        n_loc = bsz * t
        xf = xl.reshape(n_loc, d)
        logits = jnp.dot(xf, router.astype(xf.dtype))
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        weights, idx = jax.lax.top_k(probs, k)
        weights = weights / jnp.maximum(
            weights.sum(-1, keepdims=True), 1e-9)
        sel = jax.nn.one_hot(idx[:, 0], e)
        aux = e * jnp.sum(sel.mean(0) * probs.mean(0)) \
            * cfg.router_aux_coef
        aux = jax.lax.pmean(aux, all_axes)

        cap = max(8, int(math.ceil(k * n_loc * cfg.capacity_factor / e)))
        buf, dest = _dispatch_local(cfg, xf, weights, idx, cap)
        # ship token blocks to their expert owners
        recv = jax.lax.all_to_all(
            buf.reshape(m_sz, e_loc, cap, d), model_axis,
            split_axis=0, concat_axis=0)               # (M, E_loc, C, d)
        xe = recv.reshape(e_loc, m_sz * cap, d)
        g = jnp.einsum("ecd,edf->ecf", xe, wg.astype(xe.dtype))
        u = jnp.einsum("ecd,edf->ecf", xe, wu.astype(xe.dtype))
        h = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                       wd.astype(xe.dtype))
        # ship results home
        back = jax.lax.all_to_all(
            h.reshape(e_loc, m_sz, cap, d).swapaxes(0, 1), model_axis,
            split_axis=0, concat_axis=0)               # (M, E_loc, C, d)
        hflat = jnp.concatenate(
            [back.reshape(e * cap, d), jnp.zeros((1, d), h.dtype)], 0)
        gathered = hflat[dest].reshape(n_loc, k, d).astype(jnp.float32)
        out = (gathered * weights[..., None]).sum(1)
        return out.reshape(bsz, t, d).astype(xl.dtype), aux

    import inspect
    params = inspect.signature(shard_map).parameters
    # the no-replication-check kwarg was renamed check_rep -> check_vma
    relax = ({"check_vma": False} if "check_vma" in params
             else {"check_rep": False})
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(bt, None, None), P(), P(model_axis, None, None),
                  P(model_axis, None, None), P(model_axis, None, None)),
        out_specs=(P(bt, None, None), P()),
        **relax)
    return fn(x, p["router"], p["wg"], p["wu"], p["wd"])
