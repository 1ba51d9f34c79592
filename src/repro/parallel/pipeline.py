"""SPMD pipeline parallelism over the ``pod`` mesh axis (HETHUB's
heterogeneous boundary).

Implementation: GSPMD-native pipelining (the praxis/GSPMD-paper pattern).
A stage buffer (n_stages, B_tick, S, D) carries one in-flight microbatch per
stage with the stage dim sharded over ``pod``; each tick applies
``vmap(stage_fn)`` over the stage dim — GSPMD runs stage s on pod s — and
``jnp.roll`` shifts activations stage->stage, lowering to collective-permute
(ICCL iSend/iRecv) on the inter-pod links.  Pure pjit: no shard_map, fully
differentiable (the backward pass reverse-pipelines automatically; the
workload simulator models true 1F1B timing for planning — DESIGN.md §2).

Non-uniform stage segmentation (the paper's headline mechanism): stages are
padded to the max layer count and carry a per-(stage, layer) mask; masked
layers are identity.  On heterogeneous hardware the planner assigns more
real layers to faster pods.

Interleaved virtual stages (planner schedule "interleaved-1f1b"): with
``vpp > 1`` each pod holds vpp model chunks, params stack to
(n_stages, vpp, Lmax, ...), and activations traverse all n_stages*vpp
virtual slots — so plans the planner scores under interleaving execute in
the trainer with the same chunk-granular layer assignment.

Batches arrive pre-microbatched: tokens/labels shaped (m, B_tick, S) with
B_tick sharded over 'data' — so no resharding at the microbatch split.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.iccl.communicator import _note as _iccl_note
from repro.models.config import ModelConfig
from repro.models.transformer import (_block_fwd, _embed_tokens, _constrain_act,
                                      _unembed, final_norm)
from repro.train.steps import cross_entropy, constrain, AUX_COEF


def _stage_axis(mesh) -> Optional[str]:
    """'pod' when the mesh has a pod axis (or no mesh is bound yet — the
    constraint then no-ops at trace time); None when a pod-less mesh is
    active, so the trainer's CPU-mesh pipeline mode runs the identical
    program unsharded on the stage dim."""
    if mesh is None:
        return "pod"
    return "pod" if "pod" in getattr(mesh, "axis_names", ()) else None


def _tick_mark(telemetry, t: int, probe) -> None:
    """Ordered host-callback tick boundary for the telemetry recorder.
    ``probe`` is a scalar slice of the tick's output, making the callback
    data-dependent on the tick's compute (it cannot be hoisted); fires
    once per tick during the forward pass only (jax.checkpoint remats
    re-run block bodies, not this top-level marker)."""
    if telemetry is None:
        return
    jax.debug.callback(telemetry.on_tick, t, probe, ordered=True)


def stack_blocks_for_stages(params: Dict[str, Any], n_stages: int,
                            layers_per_stage: Optional[Sequence[int]] = None,
                            vpp: int = 1) -> Dict[str, Any]:
    """Reshape stacked layer params (L, ...) -> (n_stages, Lmax, ...) with
    zero padding for non-uniform splits (the per-stage layer mask is static,
    derived from ``layers_per_stage`` inside make_pp_loss_fn).

    ``vpp > 1`` (interleaved-1F1B virtual stages): the model is cut into
    n_stages*vpp chunks assigned round-robin — virtual stage vs = c*pp + s
    holds contiguous layers, living on pod s as its chunk c — and params
    stack to (n_stages, vpp, Lmax_chunk, ...).  ``layers_per_stage`` is
    then per VIRTUAL stage in virtual order (``ParallelPlan.virtual_layers``
    / planner ``chunk_layers``)."""
    blocks = params["blocks"]
    L = jax.tree.leaves(blocks)[0].shape[0]
    V = n_stages * vpp
    if layers_per_stage is None:
        assert L % V == 0
        layers_per_stage = [L // V] * V
    assert sum(layers_per_stage) == L and len(layers_per_stage) == V
    lmax = max(layers_per_stage)

    def restack(a):
        pieces = []
        off = 0
        for ls in layers_per_stage:
            piece = a[off:off + ls]
            off += ls
            if ls < lmax:
                pad = jnp.zeros((lmax - ls,) + a.shape[1:], a.dtype)
                piece = jnp.concatenate([piece, pad], axis=0)
            pieces.append(piece)
        stages = jnp.stack(pieces)              # (V, Lmax, ...) virtual order
        if vpp == 1:
            return stages
        # virtual index c*pp + s -> [s, c]: reshape to (vpp, pp, ...) then
        # swap so the pod-sharded stage dim leads
        return jnp.swapaxes(
            stages.reshape((vpp, n_stages) + stages.shape[1:]), 0, 1)

    new = dict(params)
    new["blocks"] = jax.tree.map(restack, blocks)
    return new


def pp_param_specs(specs: Dict[str, Any]) -> Dict[str, Any]:
    """Shard the leading stage dim of block params over 'pod'; everything
    else (embed/unembed/norms) stays replicated across pods."""
    out = dict(specs)

    def podify(s):
        parts = tuple(s) if len(s) else (None,)
        return P(*(("pod",) + tuple(parts[1:])))

    out["blocks"] = jax.tree.map(podify, specs["blocks"])
    return out


def _mixed_tp(stage_tp: Optional[Sequence[int]]) -> bool:
    return stage_tp is not None and len(set(stage_tp)) > 1


def make_pp_loss_fn(cfg: ModelConfig, mesh, n_stages: int,
                    n_microbatches: int,
                    layers_per_stage: Optional[Sequence[int]] = None,
                    vpp: int = 1, telemetry=None,
                    stage_tp: Optional[Sequence[int]] = None):
    """Builds loss_fn(params, batch) running the pod-axis pipeline.

    ``vpp > 1`` runs interleaved virtual stages: params stacked
    (n_stages, vpp, Lmax, ...) by ``stack_blocks_for_stages(..., vpp=)``,
    ``layers_per_stage`` per virtual stage in virtual order, and the
    activation buffer walks all n_stages*vpp virtual slots — chunk c of
    pod s computes virtual stage c*n_stages + s, the roll returns wrapped
    activations to pod 0 at the next chunk (the planner's
    interleaved-1f1b wrap-around hop).

    ``stage_tp`` (per-physical-stage tensor widths, from the plan's
    ``tps``) arms the asymmetric-parallelism boundary reshard: when
    stages disagree on tp and activations are model-sharded
    (``cfg.act_sharding``), the buffer is constrained model-UNsharded for
    the pod roll — GSPMD lowers that to the all-gather at the sender and
    the re-split at the receiver (the collectives the predictor's
    ``reshard_time`` charges).  Numerically the round trip is the
    identity, so mixed-tp plans keep reference loss/grads bit-for-bit.

    ``telemetry`` (repro.telemetry.StageTelemetry) inserts ordered
    host-callback tick boundaries so the trainer can observe per-stage
    compute and bubble online (the HETHUB closed loop)."""
    kinds = cfg.layer_kinds()
    kind = kinds[0]
    assert len(set(kinds)) == 1, "PP requires a uniform scanned stack"
    if cfg.first_dense:
        raise ValueError("PP stages only the scanned stack; a model with "
                         "leading dense layers (first_dense) is not staged")
    m = n_microbatches
    if stage_tp is not None:
        assert len(stage_tp) == n_stages, \
            f"stage_tp needs {n_stages} entries, got {len(stage_tp)}"
    if vpp > 1:
        return _make_pp_loss_fn_vpp(cfg, mesh, n_stages, m,
                                    layers_per_stage, vpp, kind, telemetry,
                                    stage_tp)

    if layers_per_stage is not None:
        lmax = max(layers_per_stage)
        mask_rows = [[i < ls for i in range(lmax)] for ls in layers_per_stage]
    else:
        mask_rows = None

    def stage_fn(blocks, mask, x):
        """One stage: scan its (Lmax, ...) layers; masked layers identity."""

        def body(x, xs):
            p, keep = xs
            fn = functools.partial(_block_fwd, cfg=cfg, kind=kind)
            if cfg.remat:
                fn = jax.checkpoint(fn)
            y, aux = fn(p, x)
            y = jnp.where(keep, y, x)
            return y, jnp.where(keep, aux, 0.0)

        x, auxs = jax.lax.scan(body, x, (blocks, mask))
        return x, jnp.sum(auxs)

    buf_spec = P(_stage_axis(mesh), ("data",),
                 "model" if cfg.act_sharding else None, None)
    # asymmetric tp: the hop crosses stages of different model widths, so
    # the rolled buffer must leave the sender model-UNsharded (all-gather)
    # and the next tick's buf_spec constraint re-splits it at the receiver
    hop_spec = (P(_stage_axis(mesh), ("data",), None, None)
                if _mixed_tp(stage_tp) and cfg.act_sharding else buf_spec)

    def loss_fn(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        extra = batch.get("image_embeds")
        blocks = params["blocks"]
        lmax_ = jax.tree.leaves(blocks)[0].shape[1]
        if mask_rows is None:
            mask = jnp.ones((n_stages, lmax_), bool)
        else:
            mask = jnp.asarray(mask_rows)
        Bt, S = tokens.shape[1], tokens.shape[2]
        S_tot = S + (extra.shape[2] if extra is not None else 0)
        D = cfg.d_model

        buf = jnp.zeros((n_stages, Bt, S_tot, D), cfg.adtype)
        loss_sum = jnp.zeros((), jnp.float32)
        aux_sum = jnp.zeros((), jnp.float32)

        for t in range(m + n_stages - 1):
            if t < m:  # inject next microbatch into stage 0
                inject = _embed_tokens(
                    params, tokens[t], cfg,
                    extra[t] if extra is not None else None)
                buf = buf.at[0].set(inject.astype(cfg.adtype))
            buf = constrain(buf, buf_spec)
            out, auxs = jax.vmap(stage_fn)(blocks, mask, buf)
            _tick_mark(telemetry, t, out[-1, 0, 0, 0])
            j_out = t - (n_stages - 1)   # microbatch finishing this tick
            if 0 <= j_out < m:
                h = final_norm(params, out[-1], cfg)
                logits = _unembed(params, h, cfg)
                logits = constrain(logits, P(("data",), None, "model"))
                loss_sum = loss_sum + cross_entropy(logits, labels[j_out])
            valid = jnp.asarray([1.0 if 0 <= t - s < m else 0.0
                                 for s in range(n_stages)], jnp.float32)
            aux_sum = aux_sum + jnp.sum(auxs * valid)
            out = constrain(out, hop_spec)
            if hop_spec is not buf_spec:
                # boundary reshard (tp-asymmetric plans): the constraint
                # above is the model-axis all-gather before the hop
                _iccl_note("pp_reshard", "model", out)
            # trace-time P2P accounting: the roll is the pipeline's
            # stage->stage activation hop (collective-permute over 'pod')
            _iccl_note("pp_shift", "pod", out)
            buf = jnp.roll(out, 1, axis=0)   # collective-permute over 'pod'

        _tick_mark(telemetry, m + n_stages - 1, loss_sum)
        loss = loss_sum / m + AUX_COEF * (aux_sum / m)
        return loss, {"ce": loss_sum / m, "aux": aux_sum / m}

    return loss_fn


def _make_pp_loss_fn_vpp(cfg: ModelConfig, mesh, n_stages: int, m: int,
                         layers_per_stage: Optional[Sequence[int]],
                         vpp: int, kind: str, telemetry=None,
                         stage_tp: Optional[Sequence[int]] = None):
    """Interleaved virtual-stage pipeline: the (n_stages, vpp, B, S, D)
    buffer holds one in-flight microbatch per VIRTUAL stage; each tick runs
    every (pod, chunk) slot, then activations shift one virtual slot —
    a pod-axis roll (collective-permute) plus, on the wrapped pod-0 row, a
    local chunk-index advance.  Microbatch j finishes at tick
    j + n_stages*vpp - 1, so interleaving trades more ticks for vpp-times
    shallower per-chunk stacks (the planner's bubble-vs-memory trade is
    modeled in core/simulator.py; this builder makes such plans
    executable)."""
    pp = n_stages
    V = pp * vpp

    if layers_per_stage is not None:
        assert len(layers_per_stage) == V, \
            f"vpp={vpp} needs {V} virtual-stage layer counts"
        lmax = max(layers_per_stage)
        # [s][c] -> mask row of virtual stage c*pp + s
        mask_rows = [[[i < layers_per_stage[c * pp + s] for i in range(lmax)]
                      for c in range(vpp)] for s in range(pp)]
    else:
        mask_rows = None

    def stage_fn(blocks, mask, x):
        """One chunk: scan its (Lmax, ...) layers; masked layers identity."""

        def body(x, xs):
            p, keep = xs
            fn = functools.partial(_block_fwd, cfg=cfg, kind=kind)
            if cfg.remat:
                fn = jax.checkpoint(fn)
            y, aux = fn(p, x)
            y = jnp.where(keep, y, x)
            return y, jnp.where(keep, aux, 0.0)

        x, auxs = jax.lax.scan(body, x, (blocks, mask))
        return x, jnp.sum(auxs)

    buf_spec = P(_stage_axis(mesh), None, ("data",),
                 "model" if cfg.act_sharding else None, None)
    # same boundary-reshard rule as the vpp=1 builder: mixed stage tp
    # means the pod roll carries model-UNsharded activations
    hop_spec = (P(_stage_axis(mesh), None, ("data",), None, None)
                if _mixed_tp(stage_tp) and cfg.act_sharding else buf_spec)

    def loss_fn(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        extra = batch.get("image_embeds")
        blocks = params["blocks"]                 # (pp, vpp, Lmax, ...)
        lmax_ = jax.tree.leaves(blocks)[0].shape[2]
        if mask_rows is None:
            mask = jnp.ones((pp, vpp, lmax_), bool)
        else:
            mask = jnp.asarray(mask_rows)
        Bt, S = tokens.shape[1], tokens.shape[2]
        S_tot = S + (extra.shape[2] if extra is not None else 0)
        D = cfg.d_model

        buf = jnp.zeros((pp, vpp, Bt, S_tot, D), cfg.adtype)
        loss_sum = jnp.zeros((), jnp.float32)
        aux_sum = jnp.zeros((), jnp.float32)

        for t in range(m + V - 1):
            if t < m:  # inject next microbatch into virtual stage 0
                inject = _embed_tokens(
                    params, tokens[t], cfg,
                    extra[t] if extra is not None else None)
                buf = buf.at[0, 0].set(inject.astype(cfg.adtype))
            buf = constrain(buf, buf_spec)
            out, auxs = jax.vmap(jax.vmap(stage_fn))(blocks, mask, buf)
            _tick_mark(telemetry, t, out[-1, -1, 0, 0, 0])
            j_out = t - (V - 1)          # microbatch finishing this tick
            if 0 <= j_out < m:
                h = final_norm(params, out[-1, -1], cfg)
                logits = _unembed(params, h, cfg)
                logits = constrain(logits, P(("data",), None, "model"))
                loss_sum = loss_sum + cross_entropy(logits, labels[j_out])
            valid = jnp.asarray(
                [[1.0 if 0 <= t - (c * pp + s) < m else 0.0
                  for c in range(vpp)] for s in range(pp)], jnp.float32)
            aux_sum = aux_sum + jnp.sum(auxs * valid)
            out = constrain(out, hop_spec)
            if hop_spec is not buf_spec:
                _iccl_note("pp_reshard", "model", out)
            # virtual slot shift: pod roll (collective-permute), then the
            # wrapped pod-0 row advances one chunk locally
            _iccl_note("pp_shift", "pod", out)
            rolled = jnp.roll(out, 1, axis=0)
            buf = rolled.at[0].set(jnp.roll(rolled[0], 1, axis=0))

        _tick_mark(telemetry, m + V - 1, loss_sum)
        loss = loss_sum / m + AUX_COEF * (aux_sum / m)
        return loss, {"ce": loss_sum / m, "aux": aux_sum / m}

    return loss_fn
