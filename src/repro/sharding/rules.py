"""Partition rules: map parameter/batch/cache pytrees to PartitionSpecs.

Strategy (see DESIGN.md §5):
  * ``model`` axis — tensor parallel: attention heads / d_ff / experts / vocab.
  * ``data``  axis — FSDP: the d_model ("reduction") dimension of every large
    matrix is sharded over ``data``; GSPMD all-gathers per-layer on use.
  * ``pod``   axis — pure data parallel across FL cohorts: parameters are
    REPLICATED across pods (each pod is one federated cohort; the cross-pod
    all-reduce happens once per FL round at aggregation).

Batch dims shard over ("pod", "data"); decode caches shard batch over
``data`` when the batch is large enough, otherwise the sequence/state dim.

Rules are (regex over the tree path, rank -> PartitionSpec) pairs with a
replicate fallback, applied to shape trees from ``jax.eval_shape`` so no
memory is touched.
"""
from __future__ import annotations

import re
from typing import Any

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

D, M = "data", "model"


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


# (regex, {rank: spec}) — first match wins. Stacked block params have a
# leading num_groups dim -> specs here are written for the *unstacked* rank
# and get None prepended automatically when the leaf lives under blocks/.
_PARAM_RULES = [
    (r"tok_embed$",            {2: P(M, D)}),
    (r"lm_head$",              {2: P(D, M)}),
    (r"pos_embed$",            {2: P(None, None)}),
    # attention
    (r"(mixer|cross)/w[qkv]$", {2: P(D, M)}),
    (r"(mixer|cross)/wo$",     {2: P(M, D)}),
    (r"(mixer|cross)/b[qkv]$", {1: P(M)}),
    # dense mlp
    (r"ffn/w_(gate|up)$",      {2: P(D, M)}),
    (r"ffn/w_down$",           {2: P(M, D)}),
    (r"ffn/b_up$",             {1: P(M)}),
    (r"ffn/b_down$",           {1: P(None)}),
    # moe
    (r"ffn/router$",           {2: P(D, None)}),
    (r"ffn/w_(gate|up)$",      {3: P(M, D, None)}),
    (r"ffn/w_down$",           {3: P(M, None, D)}),
    # mamba
    (r"mixer/w_in$",           {2: P(D, M)}),
    (r"mixer/conv_w$",         {2: P(None, M)}),
    (r"mixer/conv_b$",         {1: P(M)}),
    (r"mixer/w_x_dbc$",        {2: P(M, None)}),
    (r"mixer/w_dt$",           {2: P(None, M)}),
    (r"mixer/b_dt$",           {1: P(M)}),
    (r"mixer/a_log$",          {2: P(M, None)}),
    (r"mixer/d_skip$",         {1: P(M)}),
    (r"mixer/w_out$",          {2: P(M, D)}),
    # mlstm / slstm
    (r"mixer/w[zifo]$",        {2: P(D, M)}),
    (r"mixer/wo_proj$",        {2: P(M, D)}),
    (r"mixer/r[zifo]$",        {3: P(None, None, None)}),
    (r"mixer/w[if]$",          {2: P(D, None)}),
    (r"mixer/b[if]$",          {1: P(None)}),
    # norms & anything else: replicate (fallback)
]


def _match_spec(path: str, rank: int):
    """First rule whose pattern matches AND lists this rank wins (MoE expert
    tensors share names with dense mlp weights; rank disambiguates)."""
    for pat, by_rank in _PARAM_RULES:
        if re.search(pat, path) and rank in by_rank:
            return by_rank[rank]
    return P(*([None] * rank))


def param_specs(cfg, params_shape) -> Any:
    """params_shape: pytree of ShapeDtypeStruct (from jax.eval_shape)."""

    def leaf_spec(path, leaf):
        ps = _path_str(path)
        rank = len(leaf.shape)
        stacked = "blocks/" in ps
        eff_rank = rank - 1 if stacked else rank
        spec = _match_spec(ps, eff_rank)
        if stacked:
            spec = P(None, *spec)
        return spec

    return jax.tree_util.tree_map_with_path(leaf_spec, params_shape)


# ----------------------------------------------------------------------
# divisibility sanitizer — pjit INPUT shardings must divide dims exactly
# (uneven GSPMD padding is only legal for intermediates). Drop any axis
# assignment that does not divide its dimension (e.g. whisper's vocab
# 51865 over 16, GQA kv=2 heads over 16).
# ----------------------------------------------------------------------

def _n_shards(entry, axis_sizes) -> int:
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        n = 1
        for e in entry:
            n *= axis_sizes.get(e, 1)
        return n
    return axis_sizes.get(entry, 1)


def sanitize_specs(spec_tree, shape_tree, axis_sizes: dict):
    """Zero out per-dim assignments that don't divide the dim evenly."""

    def fix(spec, leaf):
        dims = leaf.shape
        entries = tuple(spec) + (None,) * (len(dims) - len(spec))
        out = []
        for dim, entry in zip(dims, entries):
            ns = _n_shards(entry, axis_sizes)
            out.append(entry if ns > 0 and dim % ns == 0 else None)
        return P(*out)

    return jax.tree.map(fix, spec_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, P))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


# ----------------------------------------------------------------------
# batch / cache / opt-state specs
# ----------------------------------------------------------------------

def batch_axis(multi_pod: bool):
    return ("pod", D) if multi_pod else D


def train_batch_specs(cfg, multi_pod: bool = False):
    b = batch_axis(multi_pod)
    specs = {"tokens": P(b, None), "labels": P(b, None), "mask": P(b, None)}
    if cfg.num_prefix_tokens:
        specs["prefix_embeddings"] = P(b, None, None)
    if cfg.is_encdec:
        specs["encoder_frames"] = P(b, None, None)
    return specs


def _cache_leaf_spec(path: str, shape, *, batch_sharded: bool,
                     axis_sizes: dict):
    """Decode caches: leading num_groups dim, then batch. Shard batch over
    'data' when possible, otherwise the state/sequence dim. For KV caches,
    the kv-head dim goes to 'model' when it divides evenly; otherwise the
    *sequence* dim takes 'model' (GQA kv counts like 2, 4, 8 don't divide a
    16-way axis but 32k/500k sequences always do)."""
    rank = len(shape)
    bdim = D if batch_sharded else None
    nm = axis_sizes.get(M, 1)
    nd = axis_sizes.get(D, 1)
    if re.search(r"(^|/)(k|v)$", path) and rank == 5:     # (n,B,S,kv,hd)
        _, B, S, KV, _ = shape
        if KV % nm == 0:
            sdim = None if batch_sharded else (D if S % nd == 0 else None)
            return P(None, bdim, sdim, M, None)
        sq = M if S % nm == 0 else None
        return P(None, bdim, sq, None, None) if batch_sharded else \
            P(None, None, (D, M) if S % (nd * nm) == 0 else sq, None, None)
    if re.search(r"(k|v)_scale$", path) and rank == 4:    # (n,B,S,kv)
        _, B, S, KV = shape
        if KV % nm == 0:
            return P(None, bdim, None, M)
        return P(None, bdim, M if S % nm == 0 else None, None)
    if re.search(r"conv$", path) and rank == 4:           # (n,B,dc-1,di)
        return P(None, bdim, None, M)
    if re.search(r"ssm$", path) and rank == 4:            # (n,B,di,ds)
        return P(None, bdim, M, None)
    if re.search(r"C$", path) and rank == 5:              # (n,B,h,dk,dv)
        return P(None, bdim, None, None, M)
    if rank == 4:                                         # mlstm/slstm (n,B,h,dh)
        return P(None, bdim, None, M)
    if rank == 3:                                         # (n,B,h)
        return P(None, bdim, None)
    return P(*([None] * rank))


def decode_state_specs(cfg, state_shape, global_batch: int,
                       axis_sizes: dict):
    batch_sharded = global_batch % max(axis_sizes.get(D, 1), 1) == 0 \
        and global_batch >= axis_sizes.get(D, 1)

    def leaf_spec(path, leaf):
        return _cache_leaf_spec(_path_str(path), leaf.shape,
                                batch_sharded=batch_sharded,
                                axis_sizes=axis_sizes)

    return jax.tree_util.tree_map_with_path(leaf_spec, state_shape)


def decode_batch_specs(cfg, global_batch: int, multi_pod: bool = False):
    b = batch_axis(multi_pod)
    n = (2 if multi_pod else 1) * 16
    tok = P(b) if global_batch >= n else P(None)
    return {"tokens": tok}


def opt_state_specs(pspecs):
    """Optimizer state mirrors parameter sharding (momentum/adam moments)."""
    return pspecs


# ----------------------------------------------------------------------
# cohort runtime specs (repro.sim device backend on the cohort mesh)
# ----------------------------------------------------------------------
# Stage-3 local training is pure data parallelism over the cohort: the
# per-invocation winner tensors (all with a leading client axis) shard
# over 'data', the global params are replicated in, and the weighted
# FedAvg partial sum is psum-reduced across 'data' so the aggregate comes
# back replicated. The fleet store pads every tier to a multiple of the
# mesh's data size (weight-0 rows), so the shard split is always even.

def cohort_param_spec():
    """Global params in / aggregated params out: replicated (P() is a valid
    pytree prefix for the whole param tree)."""
    return P()


def fleet_class_specs():
    """Device-resident fleet path (repro.sim.fleet, ``--runtime device``):
    ``(class_x, class_y, rows, plans, step_mask, batch_n, weights)``.
    The class store tensors are replicated — every device gathers its own
    winners' rows out of the full store — while the per-invocation
    index/weight tensors shard their leading client axis over 'data' (the
    store pads ``client_cap`` to a multiple of the data-axis size)."""
    return (P(), P(), P(D), P(D), P(D), P(D), P(D))
