"""Mixture-of-Experts layer (expert-parallel over the ``model`` mesh axis).

Group-local scatter dispatch (GShard-style, without the O(T·E·C) dense
dispatch tensor): tokens are split into G groups (G = the ``data`` mesh axis
size, so each group lives on one FSDP shard):

  1. per group, each (token, slot) gets a rank within its expert via a
     group-local cumulative sum — no cross-shard prefix sum;
  2. tokens are scattered into a (G, E, C, D) buffer (C = group capacity);
  3. the (G, E, C, D) -> (E, G, C, D) transpose IS the token->expert
     all-to-all (G sharded over 'data', E over 'model');
  4. experts run as a grouped einsum, results transpose back and are
     combined with the router gates.

Tokens over capacity are dropped (standard capacity-factor semantics).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import dense_init
from repro.sharding.constrain import maybe_constrain


def init_moe(key, cfg):
    e = cfg.num_experts
    d = cfg.d_model
    f = cfg.d_ff_expert or cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, e), jnp.float32),  # router in fp32
        "w_gate": dense_init(ks[1], (e, d, f), dt),
        "w_up": dense_init(ks[2], (e, d, f), dt),
        "w_down": dense_init(ks[3], (e, f, d), dt),
    }


def _num_groups(total_tokens: int) -> int:
    """Groups = data-axis size when the ambient mesh divides the tokens."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty or "data" not in am.axis_names:
        return 1
    g = dict(zip(am.axis_names, am.axis_sizes))["data"]
    return g if total_tokens % g == 0 else 1


def moe_capacity(tokens_per_group: int, cfg) -> int:
    per = tokens_per_group * cfg.experts_per_token / cfg.num_experts
    cap = int(per * cfg.moe_capacity_factor) + 1
    return -(-cap // 8) * 8        # multiple of 8 for tiling friendliness


def apply_moe(p, x, cfg, *, capacity: int | None = None):
    """x: (B, S, D) -> (B, S, D) plus aux losses dict."""
    B, S, D = x.shape
    T = B * S
    E, K = cfg.num_experts, cfg.experts_per_token
    G = _num_groups(T)
    Tg = T // G
    C = capacity if capacity is not None else moe_capacity(Tg, cfg)
    C = min(C, Tg * K)

    xt = x.reshape(G, Tg, D)
    xt = maybe_constrain(xt, "data", None, None)

    logits = (xt.astype(jnp.float32) @ p["router"])            # (G, Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = lax.top_k(probs, K)                           # (G, Tg, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # --- load-balance auxiliary loss (Switch-style, global) ---
    me = probs.mean((0, 1))                                    # (E,)
    ce = jnp.zeros((E,)).at[idx.reshape(-1)].add(1.0) / (T * K)
    aux_loss = E * jnp.sum(me * ce)

    # --- group-local rank of each (token, slot) within its expert ---
    flat_e = idx.reshape(G, Tg * K)                            # (G, TgK)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)        # (G, TgK, E)
    ranks = jnp.cumsum(onehot, axis=1) - onehot
    rank = jnp.take_along_axis(
        ranks, flat_e[..., None], axis=2)[..., 0]              # (G, TgK)
    keep = rank < C
    slot = jnp.where(keep, flat_e * C + rank, E * C)           # drop bucket

    # --- dispatch: group-local scatter into (G, E*C+1, D) ---
    # during the scatter the model dim D is sharded over 'model' so the 16
    # tensor-parallel shards scatter disjoint D-slices instead of each
    # materializing the full buffer.
    xrep = jnp.repeat(xt, K, axis=1)                           # (G, TgK, D)
    xrep = maybe_constrain(xrep, "data", None, "model")

    def scatter_group(xr, sl):
        return jnp.zeros((E * C + 1, D), xr.dtype).at[sl].set(xr)

    buf = jax.vmap(scatter_group)(xrep, slot)                  # (G, E*C+1, D)
    buf = maybe_constrain(buf, "data", None, "model")
    h = buf[:, : E * C].reshape(G, E, C, D)
    # all-to-all: (G, E, C, D) [G:'data', D:'model'] -> (E, G, C, D)
    # [E:'model', D: full]
    h = h.transpose(1, 0, 2, 3)
    h = maybe_constrain(h, "model", "data", None, None)

    # --- expert FFN as grouped einsum (E over 'model' axis) ---
    def _g(w):
        if getattr(cfg, "fsdp_gather_weights", False):
            return maybe_constrain(w, "model", None, None)
        return w

    g = jax.nn.silu(jnp.einsum("egcd,edf->egcf", h, _g(p["w_gate"])))
    u = jnp.einsum("egcd,edf->egcf", h, _g(p["w_up"]))
    y = jnp.einsum("egcf,efd->egcd", g * u, _g(p["w_down"]))   # (E, G, C, D)
    y = maybe_constrain(y, "model", "data", None, None)

    # --- return all-to-all + group-local gather & combine (D re-sharded
    # over 'model' so the gather/combine also touch only D-slices) ---
    y = y.transpose(1, 0, 2, 3).reshape(G, E * C, D)
    y = maybe_constrain(y, "data", None, "model")
    y = jnp.concatenate([y, jnp.zeros((G, 1, D), y.dtype)], axis=1)
    out = jnp.take_along_axis(y, slot[..., None], axis=1)      # (G, TgK, D)
    w = (gates.reshape(G, Tg * K, 1).astype(y.dtype)
         * keep[..., None].astype(y.dtype))
    out = (out * w.astype(out.dtype)).reshape(G, Tg, K, D).sum(axis=2)
    out = maybe_constrain(out, "data", None, "model")
    return out.reshape(B, S, D).astype(x.dtype), \
        {"moe_aux_loss": aux_loss, "moe_drop_frac": 1.0 - keep.mean()}
