"""Context-parallel (cp) execution: the ring-attention loss builder.

``make_cp_loss_fn`` runs a plan's cp ring as ONE SPMD program: the
sequence axis of every microbatch is split into the plan's (possibly
unequal) ``cp_chunks``, padded to the max chunk, and laid out on a new
leading rank axis constrained to the mesh's ``pod`` axis — the same axis
(and the same ``jnp.roll`` collective-permute idiom) the pipeline loss
builder shifts activations on.  Each transformer block then runs:

  rank-local qkv projection (per-rank RoPE positions carry the GLOBAL
  chunk offsets) -> ``cp`` ring steps, each folding the visiting KV block
  into the carried online-softmax state (``kernels.ring_attention``'s
  differentiable step) and rolling K/V one hop around the pod axis ->
  rank-local output projection, residual, MLP.

Ragged chunks ride the pad-to-max layout: permuted blocks keep one
uniform shape (collective permutes require it) while ``k_valid`` masks
confine the math to real tokens.  Fully-masked folds are exact no-ops of
the carried state (every score is ``NEG_INF`` so the running max, sum and
accumulator pass through unchanged once the rank's own block — always
step 0 — has seeded a finite max), so the SPMD program needs no causal
skip: every rank executes the same ``cp`` steps, exactly like the
distributed ring would.

Numerics contract (tests/test_context_parallel.py): cp = 1 plans never
enter this builder — the trainer keeps the reference loss, bit-for-bit.
For cp > 1 the online-softmax regrouping is not bit-associative, so the
loss matches the reference within float tolerance (2e-5 fp32 / 2e-2
bf16), on equal and ragged splits alike.

Scope: uniform scanned attention stacks (``"blocks"`` in params) with
global causal attention — no sliding window, logit softcap, or MoE
(``make_cp_loss_fn`` raises on such configs; the planner still prices cp
for them, it just can't be executed here yet).
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.iccl.communicator import _note as _iccl_note
from repro.kernels.ring_attention import (NEG_INF, _ring_step_ref,
                                          chunk_starts, pad_chunks,
                                          unpad_chunks)
from repro.models.config import ModelConfig
from repro.models.layers import _qkv, mlp, rmsnorm
from repro.models.transformer import (_embed_tokens, _remat, _unembed,
                                      final_norm)
from repro.train.steps import AUX_COEF, constrain, cross_entropy


def _pod_axis(mesh) -> Optional[str]:
    """Mirror of ``pipeline._stage_axis``: 'pod' when the mesh has one (or
    none is bound yet), None so a pod-less CPU mesh runs the identical
    program unsharded on the rank dim."""
    if mesh is None:
        return "pod"
    return "pod" if "pod" in getattr(mesh, "axis_names", ()) else None


def check_cp_supported(cfg: ModelConfig) -> None:
    """Raise ValueError when ``cfg`` falls outside the cp builder's scope
    (the trainer calls this before routing a cp > 1 plan here)."""
    kinds = cfg.layer_kinds()
    if set(kinds) != {"attn"} or not cfg.scan_layers:
        raise ValueError(
            "cp execution needs a uniform scanned attention stack "
            f"(got kinds={sorted(set(kinds))}, scan_layers={cfg.scan_layers})")
    if cfg.window is not None:
        raise ValueError("cp execution does not support sliding-window "
                         "attention (cfg.window)")
    if cfg.attn_logit_softcap:
        raise ValueError("cp execution does not support attn_logit_softcap")
    if cfg.n_experts:
        raise ValueError("cp execution does not support MoE blocks")


def make_cp_loss_fn(cfg: ModelConfig, mesh, cp_chunks: Sequence[int]):
    """Builds loss_fn(params, batch) running the pod-axis cp ring.

    ``cp_chunks``: per-rank sequence chunk sizes (summing to the batch's
    seq_len), from ``ParallelPlan.cp_chunk_sizes``.  The returned loss is
    interchangeable with ``steps.make_loss_fn``'s: same CE + aux
    composition, same metrics dict.
    """
    check_cp_supported(cfg)
    chunks = tuple(int(c) for c in cp_chunks)
    cp = len(chunks)
    assert cp > 1, "cp=1 plans keep the reference loss (bit-for-bit)"
    starts = chunk_starts(chunks)
    cmax = max(chunks)
    H, Hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    sm_scale = 1.0 / math.sqrt(hd)
    # per-rank global RoPE positions, and the per-step (rank,) tables of
    # who the ring delivers: after s rolls rank r holds rank (r-s)%cp's KV
    pos = jnp.asarray(np.stack([starts[r] + np.arange(cmax)
                                for r in range(cp)]))          # (cp, Cmax)
    q_starts = jnp.asarray(starts, jnp.int32)                  # (cp,)
    k_start_steps = [jnp.asarray([starts[(r - s) % cp] for r in range(cp)],
                                 jnp.int32) for s in range(cp)]
    k_valid_steps = [jnp.asarray([chunks[(r - s) % cp] for r in range(cp)],
                                 jnp.int32) for s in range(cp)]

    buf_spec = P(_pod_axis(mesh), ("data",), None, None)

    def _fold(q, k, v, m, l, acc, q_start, k_start, k_valid):
        return _ring_step_ref(q, k, v, m, l, acc, q_start=q_start,
                              k_start=k_start, k_valid=k_valid,
                              causal=True, sm_scale=sm_scale)

    vfold = jax.vmap(_fold)     # over the rank axis

    def ring_attention(p, x):
        """The pre-normed attention of x on the (cp, B, Cmax, D) rank
        layout, the KV blocks passed around the ring."""
        h = rmsnorm(p["ln1"], x, cfg.norm_eps)
        q, k, v = jax.vmap(
            lambda hr, pr: _qkv(p["attn"], hr, cfg, pr))(h, pos)
        B = x.shape[1]
        m = jnp.full((cp, B, cmax, H, 1), NEG_INF, jnp.float32)
        l = jnp.zeros((cp, B, cmax, H, 1), jnp.float32)
        acc = jnp.zeros((cp, B, cmax, H, hd), jnp.float32)
        for s in range(cp):
            m, l, acc = vfold(q, k, v, m, l, acc, q_starts,
                              k_start_steps[s], k_valid_steps[s])
            if s + 1 < cp:
                # the ring hop: KV blocks advance one rank around the pod
                # axis (collective-permute — the pipeline's roll idiom)
                _iccl_note("cp_ring", "pod", k)
                _iccl_note("cp_ring", "pod", v)
                k = jnp.roll(k, 1, axis=0)
                v = jnp.roll(v, 1, axis=0)
        o = (acc / jnp.maximum(l, 1e-30)).astype(x.dtype)
        o = o.reshape(cp, B, cmax, H * hd)
        return jnp.einsum("rbse,ed->rbsd", o, p["attn"]["wo"],
                          preferred_element_type=jnp.float32).astype(x.dtype)

    def block_fwd(p, x):
        """One attention block on the (cp, B, Cmax, D) rank layout —
        ``transformer._block_fwd``'s attn branch with the ring inside."""
        x = constrain(x, buf_spec)
        with jax.named_scope("attn"):
            x = x + ring_attention(p, x)
        with jax.named_scope("mlp"):
            h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
            y = jax.vmap(lambda hr: mlp(p["mlp"], hr, cfg))(h2)
            return x + y, jnp.zeros((), jnp.float32)

    def loss_fn(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        x = _embed_tokens(params, tokens, cfg, None)       # (B, S, D)
        xs = pad_chunks(x, chunks)                         # (cp, B, Cmax, D)
        xs = constrain(xs, buf_spec)
        fwd = _remat(block_fwd, cfg) if cfg.remat else block_fwd
        xs, auxs = jax.lax.scan(lambda c, p: fwd(p, c), xs,
                                params["blocks"])
        aux = jnp.sum(auxs)
        x = unpad_chunks(xs, chunks)                       # (B, S, D)
        x = final_norm(params, x, cfg)
        logits = _unembed(params, x, cfg)
        ce = cross_entropy(logits, labels)
        return ce + AUX_COEF * aux, {"ce": ce, "aux": aux}

    return loss_fn
