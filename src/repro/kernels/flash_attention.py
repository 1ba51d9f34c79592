"""Pallas TPU flash attention, forward and backward under one
``jax.custom_vjp``.

TPU adaptation of the CUDA flash-attention insight: q/k/v stream HBM->VMEM
in (block x head_dim) tiles; a (block_q x block_k) tile of scores lives in
VMEM only, and the online-softmax running max / denominator / accumulator
persist in VMEM scratch across the innermost (sequential) grid dim.  The
operands stay in their dtype (bf16 on the training path) and every dot
accumulates in fp32; the softmax statistics are fp32.

The forward writes the output and the per-row log-sum-exp, the backward's
only residuals besides q, k and v.  The backward computes
``D = rowsum(dO * o)`` and runs two kernels that recompute their score
tiles from the log-sum-exp: ``dq`` walks the key blocks of each query block,
``dk/dv`` the query blocks (and the query heads of its group) of each key
block.  No score tensor reaches HBM.

Tiles wholly outside the (causal, window) band are skipped in all three
kernels, and their index maps repeat the last block fetched, so a skipped
tile costs no DMA either; only tiles that straddle the band's edge are
masked.  The q/k head dim and the v head dim may differ (MLA: 192 and 128).
An operand whose head dim is a multiple of 128 lanes is read and written in
place, as (B, S, heads * d); the others are turned heads-major first.
GQA is folded into the index maps (no KV repetition is materialized), and
positions align at the END when Sq != Sk (decode/suffix).  Softmax scale
1/sqrt(q/k head dim); an optional logit softcap.

Every fully-masked q-row would produce garbage (online softmax has no empty
case); callers guarantee >= 1 valid key per row (true for causal/SWA use).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# query and key rows per tile on the training path: the fastest pair of a
# v5e sweep at Moonlight's core (PERF.md) whose score, softmax and gradient
# tiles fit the 16 MiB of scoped VMEM (2048 x 1024 does not)
BLOCK_Q = 1024
BLOCK_K = 1024

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


class _Opts(NamedTuple):
    causal: bool
    window: Optional[int]
    softcap: Optional[float]
    block_q: int
    block_k: int
    offset: int                     # Sk - Sq: q row r sits at key r + offset
    interpret: bool


def _rows(o: _Opts, i):
    """First and last position of q block ``i``."""
    first = i * o.block_q + o.offset
    return first, first + o.block_q - 1


def _cols(o: _Opts, j):
    """First and last position of key block ``j``."""
    return j * o.block_k, j * o.block_k + o.block_k - 1


def _tile_kind(o: _Opts, i, j):
    """(any pair attends, some pair is masked) for tile (q block i, key
    block j)."""
    fq, lq = _rows(o, i)
    fk, lk = _cols(o, j)
    live, partial = True, False
    if o.causal:
        live = jnp.logical_and(live, fk <= lq)
        partial = jnp.logical_or(partial, lk > fq)
    if o.window is not None:
        live = jnp.logical_and(live, lk > fq - o.window)
        partial = jnp.logical_or(partial, fk <= lq - o.window)
    return live, partial


def _key_blocks(o: _Opts, i, nk):
    """Key block for grid step j of q block i: j clamped into the band, so
    the steps outside it fetch nothing new."""
    fq, lq = _rows(o, i)
    lo = jnp.maximum(fq - o.window + 1, 0) // o.block_k \
        if o.window is not None else 0
    hi = lq // o.block_k if o.causal else nk - 1
    return lambda j: jnp.clip(jnp.clip(j, lo, hi), 0, nk - 1)


def _query_blocks(o: _Opts, j, nq):
    """Q block for grid step i of key block j, clamped likewise."""
    fk, lk = _cols(o, j)
    lo = jnp.maximum(fk - o.offset, 0) // o.block_q if o.causal else 0
    hi = jnp.maximum(lk + o.window - 1 - o.offset, 0) // o.block_q \
        if o.window is not None else nq - 1
    return lambda i: jnp.clip(jnp.clip(i, lo, hi), 0, nq - 1)


def _scores(o: _Opts, a, b, scale):
    """fp32 a @ b.T * scale, softcapped; also the softcap's derivative
    factor (None without a cap)."""
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    s = s * scale
    if not o.softcap:
        return s, None
    t = jnp.tanh(s / o.softcap)
    return o.softcap * t, 1.0 - t * t


def _mask(o: _Opts, s, i, j, keys_on_rows: bool = False):
    """Tile ``s`` of (q block i, key block j) with the pairs outside the
    band set to NEG_INF; q rows on the sublanes unless ``keys_on_rows``."""
    q_dim, k_dim = (1, 0) if keys_on_rows else (0, 1)
    qpos = _rows(o, i)[0] + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                      q_dim)
    kpos = _cols(o, j)[0] + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                      k_dim)
    keep = jnp.ones(s.shape, jnp.bool_)
    if o.causal:
        keep &= kpos <= qpos
    if o.window is not None:
        keep &= kpos > qpos - o.window
    return jnp.where(keep, s, NEG_INF)


def _on_live_tiles(o: _Opts, i, j, step):
    """Runs ``step(masked)`` on a tile that attends, masking only where
    the tile crosses the band's edge."""
    live, partial = _tile_kind(o, i, j)
    pl.when(jnp.logical_and(live, partial))(lambda: step(True))
    pl.when(jnp.logical_and(live, jnp.logical_not(partial)))(
        lambda: step(False))


# ---------------------------------------------------------------- layout ---
def _in_place(d: int) -> bool:
    """An operand whose head dim fills whole 128-lane tiles is read and
    written in place, as (B, S, heads * d); the others are turned
    heads-major, (B * heads, S, d)."""
    return d % 128 == 0


def _to_kernel(x):
    B, S, Hx, d = x.shape
    if _in_place(d):
        return x.reshape(B, S, Hx * d)
    return x.transpose(0, 2, 1, 3).reshape(B * Hx, S, d)


def _kernel_shape(B, S, Hx, d):
    return (B, S, Hx * d) if _in_place(d) else (B * Hx, S, d)


def _from_kernel(y, B, S, Hx, d):
    if _in_place(d):
        return y.reshape(B, S, Hx, d)
    return y.reshape(B, Hx, S, d).transpose(0, 2, 1, 3)


def _spec(heads: int, d: int, rows: int, at):
    """BlockSpec of ``rows`` sequence rows of one head of an operand with
    ``heads`` heads of width ``d``; ``at(*grid)`` gives the head's row in
    [0, B * heads) and the sequence block."""
    def index_map(*grid):
        r, i = at(*grid)
        return (r // heads, i, r % heads) if _in_place(d) else (r, i, 0)
    return pl.BlockSpec((None, rows, d), index_map)


def _row_spec(rows: int, at):
    """BlockSpec of a (B * H, 1, Sq) fp32 row (log-sum-exp, D)."""
    def index_map(*grid):
        r, i = at(*grid)
        return (r, 0, i)
    return pl.BlockSpec((None, 1, rows), index_map)


# ---------------------------------------------------------------- forward --
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, o: _Opts, scale: float, nk: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked):
        s, _ = _scores(o, q_ref[...], k_ref[...], scale)      # (bq, bk)
        if masked:
            s = _mask(o, s, i, j)
        m_prev = m_scr[...]                                    # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[...]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    _on_live_tiles(o, i, j, step)

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse = m_scr[...] + jnp.log(l)                          # (bq, 1)
        # the backward reads it as a row: one (bq, 128) -> (128, bq) turn
        lse_ref[...] = jnp.broadcast_to(lse, (lse.shape[0], 128)).T[:1]


def _forward(o: _Opts, q, k, v):
    """q (B,Sq,H,dqk), k (B,Sk,Hk,dqk), v (B,Sk,Hk,dv) -> out (B,Sq,H,dv)
    and the log-sum-exp (B*H, 1, Sq) fp32."""
    B, Sq, H, dqk = q.shape
    Sk, Hk, dv = k.shape[1], k.shape[2], v.shape[3]
    G = H // Hk
    bq, bk = o.block_q, o.block_k
    nq, nk = Sq // bq, Sk // bk

    def q_at(bh, i, j):
        return bh, i

    def kv_at(bh, i, j):            # kv head row of q head row bh
        return bh // G, _key_blocks(o, i, nk)(j)

    kern = functools.partial(_fwd_kernel, o=o, scale=1.0 / math.sqrt(dqk),
                             nk=nk)
    out, lse = pl.pallas_call(
        kern,
        grid=(B * H, nq, nk),
        in_specs=[_spec(H, dqk, bq, q_at), _spec(Hk, dqk, bk, kv_at),
                  _spec(Hk, dv, bk, kv_at)],
        out_specs=[_spec(H, dv, bq, q_at), _row_spec(bq, q_at)],
        out_shape=[
            jax.ShapeDtypeStruct(_kernel_shape(B, Sq, H, dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, Sq), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        interpret=o.interpret,
        name="attn_core_fwd",
    )(_to_kernel(q), _to_kernel(k), _to_kernel(v))
    return _from_kernel(out, B, Sq, H, dv), lse


# --------------------------------------------------------------- backward --
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_scr,
               *, o: _Opts, scale: float, nk: int):
    """dq of one q block, its key blocks visited in order."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked):
        k = k_ref[...]
        s, cap = _scores(o, q_ref[...], k, scale)              # (bq, bk)
        if masked:
            s = _mask(o, s, i, j)
        p = jnp.exp(s - jnp.expand_dims(lse_ref[0], -1))
        dp = jax.lax.dot_general(do_ref[...], v_ref[...], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.expand_dims(di_ref[0], -1))
        if cap is not None:
            ds = ds * cap
        acc_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    _on_live_tiles(o, i, j, step)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[...] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, o: _Opts, scale: float, nq: int, G: int):
    """dk and dv of one key block, the q blocks of each of its group's q
    heads visited in order.  Scores are held transposed, (bk, bq), so the
    log-sum-exp and D are rows."""
    j, t = pl.program_id(1), pl.program_id(2)
    i = t % nq

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked):
        q, do = q_ref[...], do_ref[...]
        s, cap = _scores(o, k_ref[...], q, scale)              # (bk, bq)
        if masked:
            s = _mask(o, s, i, j, keys_on_rows=True)
        p = jnp.exp(s - lse_ref[...])
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[...], do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[...])
        if cap is not None:
            ds = ds * cap
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    _on_live_tiles(o, i, j, step)

    @pl.when(t == G * nq - 1)
    def _finish():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _backward(o: _Opts, q, k, v, out, lse, do):
    """The gradients in q, k and v, each in its operand's shape."""
    B, Sq, H, dqk = q.shape
    Sk, Hk, dv = k.shape[1], k.shape[2], v.shape[3]
    G = H // Hk
    bq, bk = o.block_q, o.block_k
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(dqk)
    di = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    di = di.transpose(0, 2, 1).reshape(B * H, 1, Sq)
    args = (_to_kernel(q), _to_kernel(k), _to_kernel(v), _to_kernel(do),
            lse, di)

    def q_at(bh, i, j):
        return bh, i

    def kv_at(bh, i, j):
        return bh // G, _key_blocks(o, i, nk)(j)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, o=o, scale=scale, nk=nk),
        grid=(B * H, nq, nk),
        in_specs=[_spec(H, dqk, bq, q_at), _spec(Hk, dqk, bk, kv_at),
                  _spec(Hk, dv, bk, kv_at), _spec(H, dv, bq, q_at),
                  _row_spec(bq, q_at), _row_spec(bq, q_at)],
        out_specs=_spec(H, dqk, bq, q_at),
        out_shape=jax.ShapeDtypeStruct(_kernel_shape(B, Sq, H, dqk),
                                       q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dqk), jnp.float32)],
        interpret=o.interpret,
        name="attn_core_bwd_dq",
    )(*args)

    # grid step t of kv head row bhk's key block j visits q block t % nq
    # of the group's q head t // nq
    def q_of(bhk, j, t):
        return bhk * G + t // nq, _query_blocks(o, j, nq)(t % nq)

    def kv_own(bhk, j, t):
        return bhk, j

    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, o=o, scale=scale, nq=nq, G=G),
        grid=(B * Hk, nk, G * nq),
        in_specs=[_spec(H, dqk, bq, q_of), _spec(Hk, dqk, bk, kv_own),
                  _spec(Hk, dv, bk, kv_own), _spec(H, dv, bq, q_of),
                  _row_spec(bq, q_of), _row_spec(bq, q_of)],
        out_specs=[_spec(Hk, dqk, bk, kv_own), _spec(Hk, dv, bk, kv_own)],
        out_shape=[
            jax.ShapeDtypeStruct(_kernel_shape(B, Sk, Hk, dqk), k.dtype),
            jax.ShapeDtypeStruct(_kernel_shape(B, Sk, Hk, dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, dqk), jnp.float32),
                        pltpu.VMEM((bk, dv), jnp.float32)],
        interpret=o.interpret,
        name="attn_core_bwd_dkv",
    )(*args)
    return (_from_kernel(dq, B, Sq, H, dqk), _from_kernel(dk, B, Sk, Hk, dqk),
            _from_kernel(dv_, B, Sk, Hk, dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(o: _Opts, q, k, v):
    return _forward(o, q, k, v)[0]


def _flash_fwd(o: _Opts, q, k, v):
    out, lse = _forward(o, q, k, v)
    return out, (q, k, v, out, lse)


def _flash_bwd(o: _Opts, res, do):
    # the backward is traced outside the forward's scopes: name it so the
    # device time of its kernels counts for the attention core
    with jax.named_scope("attn_core"):
        return _backward(o, *res, do)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """q: (B,Sq,H,dqk); k: (B,Sk,Hk,dqk); v: (B,Sk,Hk,dv) -> (B,Sq,H,dv),
    differentiable in q, k and v.  Sq and Sk must be multiples of their
    blocks (each capped at its sequence)."""
    Sq, Sk = q.shape[1], k.shape[1]
    block_q, block_k = min(block_q, Sq), min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q,
                                                     block_k)
    return _flash(_Opts(causal, window, softcap, block_q, block_k, Sk - Sq,
                        interpret), q, k, v)
