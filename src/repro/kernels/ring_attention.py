"""Ring attention over sequence chunks — the cp (context-parallel) kernel.

The sequence axis is split into ``cp`` contiguous chunks (possibly
UNEQUAL — ``segmentation.cp_split`` sizes them so the causal triangle and
slow ring ranks balance).  Every ring rank keeps its q chunk resident and
streams the KV chunks around the ring: at ring step ``s`` rank ``r``
holds the KV of rank ``(r - s) % cp`` — exactly what ``cp`` repeated
pod-axis collective permutes (``jnp.roll`` on a pod-sharded leading axis)
deliver.  Each step folds the visiting KV block into the carried
online-softmax state ``(m, l, acc)``; after ``cp`` steps ``acc / l`` is
the exact attention output for the rank's chunk.

Ragged chunks ride a pad-to-max layout: every rank's buffers are padded
to ``max(cp_chunks)`` and masked by the true per-rank token counts, so
the permuted block shape is uniform (collective permutes need identical
shapes on every rank) while the math sees only valid tokens.

Two step implementations share the math:

* ``_ring_step_ref`` — pure jnp, differentiable; what the SPMD cp loss
  builder and CPU runs use (the repo's usual kernel split, see
  ``kernels/ref.py``).
* ``ring_step`` — the Pallas kernel for one ring hop (interpret mode on
  CPU), carrying ``(m, l, acc)`` through VMEM in/out refs instead of the
  per-call scratch of ``kernels/flash_attention.py``.

``ring_flash_attention`` runs the full simulated ring on the host in the
distributed accumulation ORDER — it is the single-host math contract the
equivalence suite locks against ``kernels/ref.py``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def chunk_starts(cp_chunks: Sequence[int]) -> Tuple[int, ...]:
    """Global start position of each ring rank's sequence chunk."""
    starts, b = [], 0
    for c in cp_chunks:
        starts.append(b)
        b += c
    return tuple(starts)


def pad_chunks(x: jax.Array, cp_chunks: Sequence[int],
               axis: int = 1) -> jax.Array:
    """Split ``x`` along ``axis`` into the (ragged) cp chunks and pad each
    to the max chunk: (..., S, ...) -> (cp, ..., Cmax, ...) with rank as
    the new leading axis (the pod-sharded dim of the SPMD layout).
    Padding is zeros; consumers mask by the true counts."""
    cmax = max(cp_chunks)
    out, b = [], 0
    for c in cp_chunks:
        sl = [slice(None)] * x.ndim
        sl[axis] = slice(b, b + c)
        chunk = x[tuple(sl)]
        if c < cmax:
            pads = [(0, 0)] * x.ndim
            pads[axis] = (0, cmax - c)
            chunk = jnp.pad(chunk, pads)
        out.append(chunk)
        b += c
    return jnp.stack(out, axis=0)


def unpad_chunks(x: jax.Array, cp_chunks: Sequence[int],
                 axis: int = 1) -> jax.Array:
    """Inverse of ``pad_chunks``: (cp, ..., Cmax, ...) -> (..., S, ...)."""
    out = []
    for r, c in enumerate(cp_chunks):
        sl = [slice(None)] * (x.ndim - 1)
        sl[axis] = slice(0, c)
        out.append(x[r][tuple(sl)])
    return jnp.concatenate(out, axis=axis)


# --------------------------------------------------- jnp step (reference) --
def _ring_step_ref(q, k, v, m, l, acc, *, q_start, k_start, k_valid,
                   causal: bool, sm_scale: float):
    """Fold one visiting KV block into the carried online-softmax state.

    q: (B, Cq, H, hd); k/v: (B, Ck, Hk, hd) (padded); m/l: (B, Cq, H, 1);
    acc: (B, Cq, H, hd).  ``q_start``/``k_start`` are the chunks' global
    positions, ``k_valid`` the number of real (non-pad) kv tokens.
    Differentiable — the SPMD cp loss builder runs exactly this.
    """
    B, Cq, H, hd = q.shape
    Ck, Hk = k.shape[1], k.shape[2]
    G = H // Hk
    qg = q.reshape(B, Cq, Hk, G, hd)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    kpos = k_start + jnp.arange(Ck)
    mask = kpos[None, :] < k_start + k_valid          # pad validity
    if causal:
        qpos = q_start + jnp.arange(Cq)
        mask = mask & (kpos[None, :] <= qpos[:, None])
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    s = s.reshape(B, Cq, H, Ck)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bqhgk,bkhd->bqhgd", p.reshape(B, Cq, Hk, G, Ck),
                    v.astype(jnp.float32)).reshape(B, Cq, H, hd)
    acc_new = acc * alpha + pv
    return m_new, l_new, acc_new


# ------------------------------------------------------- Pallas step kernel --
def _step_kernel(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                 mo_ref, lo_ref, acco_ref, *, sm_scale: float, causal: bool,
                 q_start: int, k_start: int, k_valid: int, block_q: int,
                 block_k: int, nk: int):
    i = pl.program_id(1)      # q block
    j = pl.program_id(2)      # kv block (sequential innermost)

    @pl.when(j == 0)
    def _carry_in():
        mo_ref[...] = m_ref[...]
        lo_ref[...] = l_ref[...]
        acco_ref[...] = acc_ref[...]

    qpos = q_start + i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = k_start + j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # skip kv blocks with no visible key: fully padded, or (causal) fully
    # in this q block's future — the distributed ring skips them too
    first_q = q_start + i * block_q
    relevant = j * block_k < k_valid
    if causal:
        relevant = jnp.logical_and(
            relevant, k_start + j * block_k <= first_q + block_q - 1)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        mask = kpos < k_start + k_valid
        if causal:
            mask &= kpos <= qpos
        s = jnp.where(mask, s, NEG_INF)
        m_prev = mo_ref[0]                              # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        lo_ref[0] = lo_ref[0] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acco_ref[0] = acco_ref[0] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        mo_ref[0] = m_new


def ring_step(q, k, v, m, l, acc, *, q_start: int, k_start: int,
              k_valid: int, causal: bool = True,
              block_q: int = 128, block_k: int = 128,
              interpret: bool = False):
    """One ring hop as a Pallas kernel: fold the visiting (padded) KV
    block into the carried ``(m, l, acc)`` online-softmax state.

    Shapes as ``_ring_step_ref``.  The carried state rides in/out refs —
    at ``j == 0`` the kernel copies the carry in, then accumulates across
    the kv blocks of this hop (TPU grids run the innermost dim
    sequentially, so the output block persists); the wrap hop and masked
    partial chunks are just ``k_start``/``k_valid`` choices.
    """
    B, Cq0, H, hd = q.shape
    Ck0, Hk = k.shape[1], k.shape[2]
    G = H // Hk
    block_q = min(block_q, Cq0)
    block_k = min(block_k, Ck0)
    # pad ragged chunks up to the block grid; kv pad rows sit past
    # ``k_valid`` (masked out), q pad rows are sliced off on return
    Cq = -(-Cq0 // block_q) * block_q
    Ck = -(-Ck0 // block_k) * block_k

    def padq(x, fill=0.0):
        return x if x.shape[1] == Cq else jnp.pad(
            x, ((0, 0), (0, Cq - Cq0), (0, 0), (0, 0)),
            constant_values=fill)

    def padk(x):
        return x if x.shape[1] == Ck else jnp.pad(
            x, ((0, 0), (0, Ck - Ck0), (0, 0), (0, 0)))

    q, m, l, acc = padq(q), padq(m, NEG_INF), padq(l), padq(acc)
    k, v = padk(k), padk(v)
    nq, nk = Cq // block_q, Ck // block_k

    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Cq, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(B * Hk, Ck, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(B * Hk, Ck, hd)
    mt = m.transpose(0, 2, 1, 3).reshape(B * H, Cq, 1)
    lt = l.transpose(0, 2, 1, 3).reshape(B * H, Cq, 1)
    acct = acc.transpose(0, 2, 1, 3).reshape(B * H, Cq, hd)

    def q_map(bh, i, j):
        return (bh, i, 0)

    def kv_map(bh, i, j):
        return ((bh // H) * Hk + (bh % H) // G, j, 0)

    kern = functools.partial(
        _step_kernel, sm_scale=1.0 / math.sqrt(hd), causal=causal,
        q_start=q_start, k_start=k_start, k_valid=k_valid,
        block_q=block_q, block_k=block_k, nk=nk)
    mo, lo, acco = pl.pallas_call(
        kern,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_q, 1), q_map),
            pl.BlockSpec((1, block_q, 1), q_map),
            pl.BlockSpec((1, block_q, hd), q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, 1), q_map),
            pl.BlockSpec((1, block_q, 1), q_map),
            pl.BlockSpec((1, block_q, hd), q_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Cq, 1), jnp.float32),
            jax.ShapeDtypeStruct((B * H, Cq, 1), jnp.float32),
            jax.ShapeDtypeStruct((B * H, Cq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, mt, lt, acct)

    def back(x, d):
        return x.reshape(B, H, Cq, d).transpose(0, 2, 1, 3)[:, :Cq0]

    return back(mo, 1), back(lo, 1), back(acco, hd)


# ----------------------------------------------------- the simulated ring --
def ring_flash_attention(q, k, v, cp_chunks: Sequence[int], *,
                         causal: bool = True, use_pallas: bool = False,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False) -> jax.Array:
    """Full ring attention on one host, in the distributed ring's exact
    accumulation order — the math contract for the cp loss builder.

    q: (B, S, H, hd); k/v: (B, S, Hk, hd); ``cp_chunks`` the (possibly
    ragged) per-rank chunk sizes summing to S.  Returns (B, S, H, hd),
    matching ``kernels.ref.flash_attention_ref`` within float tolerance
    (the online-softmax regrouping is not bit-associative for cp > 1).

    ``use_pallas`` selects the Pallas ``ring_step`` kernel per hop
    (forward only); the default jnp steps are differentiable.
    """
    B, S, H, hd = q.shape
    assert sum(cp_chunks) == S and all(c >= 1 for c in cp_chunks)
    cp = len(cp_chunks)
    sm_scale = 1.0 / math.sqrt(hd)
    starts = chunk_starts(cp_chunks)
    cmax = max(cp_chunks)
    qs = pad_chunks(q, cp_chunks)                     # (cp, B, Cmax, H, hd)
    ks = pad_chunks(k, cp_chunks)
    vs = pad_chunks(v, cp_chunks)

    outs = []
    for r in range(cp):
        m = jnp.full((B, cmax, H, 1), NEG_INF, jnp.float32)
        l = jnp.zeros((B, cmax, H, 1), jnp.float32)
        acc = jnp.zeros((B, cmax, H, hd), jnp.float32)
        for step in range(cp):
            src = (r - step) % cp                     # who the ring delivers
            if causal and src > r:
                continue                              # fully in the future
            if use_pallas:
                m, l, acc = ring_step(
                    qs[r], ks[src], vs[src], m, l, acc,
                    q_start=starts[r], k_start=starts[src],
                    k_valid=cp_chunks[src], causal=causal,
                    block_q=block_q, block_k=block_k, interpret=interpret)
            else:
                m, l, acc = _ring_step_ref(
                    qs[r], ks[src], vs[src], m, l, acc,
                    q_start=starts[r], k_start=starts[src],
                    k_valid=cp_chunks[src], causal=causal,
                    sm_scale=sm_scale)
        outs.append((acc / jnp.maximum(l, 1e-30)).astype(q.dtype))
    return unpad_chunks(jnp.stack(outs, axis=0), cp_chunks)
