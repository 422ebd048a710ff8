"""Pallas TPU kernel: blockwise (flash) attention with causal + sliding-
window masking.

TPU mapping (vs. the CUDA original): the online softmax keeps the running
(max, denom, acc) in VMEM scratch across the *innermost grid dimension* —
on TPU the grid is executed as a sequential loop per core, so the KV-block
axis is placed innermost and scratch persists across its iterations (the
TPU analogue of a warp-persistent accumulator). Q/K/V tiles are staged
HBM->VMEM by BlockSpec; matmul dims are MXU-aligned (block_q, block_k
multiples of 128, head_dim padded to 128).

Grid: (batch*heads, num_q_blocks, num_kv_blocks).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, block_q: int,
            block_k: int, sq: int, sk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < sk
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
        p, v_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _pad_axis(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    cfgp = [(0, 0)] * x.ndim
    cfgp[axis] = (0, pad)
    return jnp.pad(x, cfgp)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q, k, v: (B, S, H, hd) with kv already expanded to H heads (GQA is the
    caller's reshape). Returns (B, S, H, hd)."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 8))
    scale = 1.0 / math.sqrt(hd)

    # (B,S,H,hd) -> (B*H, S, hd), pad S to block multiples
    def fold(t, s, b):
        t = t.transpose(0, 2, 1, 3).reshape(B * H, s, hd)
        return _pad_axis(t, b, 1)

    qf, kf, vf = fold(q, Sq, block_q), fold(k, Sk, block_k), fold(v, Sk, block_k)
    nq, nk = qf.shape[1] // block_q, kf.shape[1] // block_k
    grid = (B * H, nq, nk)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          block_q=block_q, block_k=block_k, sq=Sq, sk=Sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out[:, :Sq].reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
    return out
