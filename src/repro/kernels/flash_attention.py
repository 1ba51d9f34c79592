"""Pallas TPU flash attention (forward) — tiled online-softmax.

TPU adaptation of the CUDA flash-attention insight: q/k/v stream HBM->VMEM in
(block_q x head_dim) / (block_k x head_dim) tiles sized for VMEM and the MXU
(128-multiples); the online-softmax running max/denominator/accumulator live
in VMEM scratch that persists across the innermost (sequential) grid dim —
TPU grids execute in order, which replaces the CUDA thread-block reduction.

Supports causal masking, sliding windows (SWA), logit softcap and GQA
(kv-head indexing folded into the BlockSpec index_map — no KV repetition is
materialized).  Positions align at the END when Sq != Sk (decode/suffix).

Every fully-masked q-row would produce garbage (online softmax has no empty
case); callers guarantee >= 1 valid key per row (true for causal/SWA use).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            sm_scale: float, causal: bool, window: Optional[int],
            softcap: Optional[float], block_q: int, block_k: int,
            sq: int, sk: int, nk: int):
    i = pl.program_id(1)      # q block
    j = pl.program_id(2)      # kv block (sequential innermost)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qpos = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + (sk - sq)
    kpos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # skip kv blocks fully outside the (causal, window) band
    first_q = i * block_q + (sk - sq)
    last_q = first_q + block_q - 1
    relevant = True
    if causal:
        relevant = jnp.logical_and(relevant, j * block_k <= last_q)
    if window is not None:
        relevant = jnp.logical_and(
            relevant, (j + 1) * block_k - 1 > first_q - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                             # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B,Sq,H,hd); k/v: (B,Sk,Hk,hd) -> (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    G = H // Hk
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    nq, nk = Sq // block_q, Sk // block_k

    # (B,S,H,hd) -> (B*H, S, hd); kv head resolved in the index maps
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(B * Hk, Sk, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(B * Hk, Sk, hd)

    def q_map(bh, i, j):
        return (bh, i, 0)

    def kv_map(bh, i, j):
        return ((bh // H) * Hk + (bh % H) // G, j, 0)

    kern = functools.partial(
        _kernel, sm_scale=1.0 / math.sqrt(hd), causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, sq=Sq, sk=Sk,
        nk=nk)
    out = pl.pallas_call(
        kern,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
