"""Builds the (architecture x input-shape x mesh) dry-run cells.

``input_specs(arch, shape)`` returns ShapeDtypeStruct stand-ins for every
model input — weak-type-correct, shardable, no device allocation.  The cell
builder attaches PartitionSpec shardings and the jit-able step function so
launch/dryrun.py can ``.lower().compile()`` each cell.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.shapes import SHAPES, ShapeSpec, applicable
from repro.models import registry
from repro.models.config import ModelConfig
from repro.parallel import pipeline
from repro.parallel.sharding import ShardingRules
from repro.train import steps

TP = 16
PP_MULTIPOD = 2
PP_MICROBATCHES = 8

# archs whose multi-pod training uses DP over 'pod' instead of pipeline
# (non-uniform layer stacks can't stack into SPMD stages; tiny models don't
#  warrant PP — exactly what the HETHUB planner decides)
NO_PP = {"recurrentgemma-9b", "whisper-tiny"}


def _overrides(arch: str, shape: ShapeSpec, multi_pod: bool = False
               ) -> Dict[str, Any]:
    ov: Dict[str, Any] = {}
    dp_axes = ("pod", "data") if (multi_pod and not (
        shape.step == "train" and arch not in NO_PP)) else ("data",)
    ov["mesh_axes"] = (dp_axes, "model")
    if shape.step in ("decode",):
        ov["cache_update"] = "onehot"       # seq-sharded cache scatter
    if shape.step in ("prefill", "train"):
        if shape.seq_len >= 32768:
            ov["attn_chunk"] = 2048         # bound (B,H,Sq,Sk) transient
        # Megatron-style sequence parallelism: stored scan carries shard
        # their seq dim over TP ranks (16x activation-memory saving)
        ov["act_sharding"] = (dp_axes, "model", None)
        if not multi_pod:
            # manual SP-boundary MoE (§Perf): O(B*S*D) per-layer traffic
            # instead of GSPMD's O(B*E*C*D) capacity-buffer reductions
            ov["moe_impl"] = "shard_map"
    if arch == "whisper-tiny" and shape.step != "decode":
        ov["attn_chunk"] = 1024             # heads replicated (6 < tp)
    return ov


def batch_sds(cfg: ModelConfig, B: int, S: int, with_labels: bool
              ) -> Dict[str, jax.ShapeDtypeStruct]:
    i32 = jnp.int32
    bf = cfg.adtype
    out: Dict[str, jax.ShapeDtypeStruct] = {}
    if cfg.family == "encdec":
        out["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), bf)
        out["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        total = S
    elif cfg.family == "vlm":
        n = cfg.n_vision_tokens
        out["tokens"] = jax.ShapeDtypeStruct((B, S - n), i32)
        out["image_embeds"] = jax.ShapeDtypeStruct((B, n, cfg.d_model), bf)
        total = S
    else:
        out["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        total = S
    if with_labels:
        out["labels"] = jax.ShapeDtypeStruct((B, total), i32)
    return out


@dataclasses.dataclass
class Cell:
    arch: str
    shape: ShapeSpec
    mesh_kind: str                 # "single" | "multi"
    step_fn: Callable
    in_shardings: Any
    out_shardings: Any
    args_sds: Tuple[Any, ...]
    cfg: ModelConfig
    meta: Dict[str, Any]

    def lower(self, mesh):
        step = (self.step_fn(mesh) if self.meta.get("needs_mesh")
                else self.step_fn)
        ns = lambda s: NamedSharding(mesh, s)
        jitted = jax.jit(step,
                         in_shardings=jax.tree.map(ns, self.in_shardings),
                         out_shardings=jax.tree.map(ns, self.out_shardings),
                         donate_argnums=self.meta.get("donate", ()))
        with jax.set_mesh(mesh):  # activation constraints need mesh context
            return jitted.lower(*self.args_sds)


def _sds_of(f, *args):
    return jax.eval_shape(f, *args)


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               extra_overrides: Optional[Dict[str, Any]] = None,
               strategy: str = "tp", grad_accum: int = 1) -> Optional[Cell]:
    shape = SHAPES[shape_name]
    cfg0 = registry.get_config(arch)
    b0 = registry.bundle_for(cfg0)
    if not applicable(arch, shape_name, registry.bundle_for(cfg0).subquadratic):
        return None
    ov = _overrides(arch, shape, multi_pod)
    if strategy == "fsdp":
        # ZeRO-3: batch shards over (data, model); the block-boundary
        # constraint pins activations batch-sharded so GSPMD gathers the
        # (small) layer weights instead of the (large) activations
        dp_all = ((("pod", "data") if multi_pod else ("data",)) + ("model",))
        ov["act_sharding"] = (dp_all, None, None)
        ov["mesh_axes"] = (dp_all, None)
        ov["head_act_sharding"] = (dp_all[:-1], None, None)
    ov.update(extra_overrides or {})
    cfg = registry.get_config(arch, **ov)
    bundle = registry.bundle_for(cfg)
    mesh_kind = "multi" if multi_pod else "single"
    data_size = 16
    key = jax.random.PRNGKey(0)

    if shape.step == "train":
        if multi_pod and arch not in NO_PP:
            return _train_pp_cell(arch, shape, cfg, bundle, key, mesh_kind)
        dp_axes = ("pod", "data") if multi_pod else ("data",)
        dp_total = 32 if multi_pod else 16
        rules = ShardingRules(cfg, tp=TP, dp_axes=dp_axes, mode=strategy,
                              ep=(cfg.moe_impl == 'shard_map_ep'))
        state_sds = _sds_of(
            functools.partial(steps.init_train_state, bundle), key)
        bspec = batch_sds(cfg, shape.global_batch, shape.seq_len, True)
        st_specs = steps.state_specs(bundle, rules, state_sds, data_size)
        b_specs = steps.batch_specs(cfg, rules, bspec)
        step = steps.make_train_step(bundle, rules, grad_accum=grad_accum)
        metrics_spec = {k: P() for k in
                        ("ce", "aux", "loss", "grad_norm", "lr")}
        par = (f"fsdp{dp_total * TP}" if strategy == "fsdp"
               else f"dp{dp_total}xtp{TP}")
        if grad_accum > 1:
            par += f" ga={grad_accum}" 
        return Cell(arch, shape, mesh_kind, step,
                    (st_specs, b_specs), (st_specs, metrics_spec),
                    (state_sds, bspec), cfg,
                    {"parallelism": par, "donate": (0,)})

    # ---- serving ----
    dp_axes = ("pod", "data") if multi_pod else ("data",)
    data_total = 32 if multi_pod else 16
    rules = ShardingRules(cfg, tp=TP, dp_axes=dp_axes,
                          ep=(cfg.moe_impl == "shard_map_ep"))
    params_sds = _sds_of(functools.partial(bundle.init, cfg=cfg), key)
    p_specs = rules.param_specs(params_sds)

    if shape.step == "prefill":
        bspec = batch_sds(cfg, shape.global_batch, shape.seq_len, False)
        b_specs = steps.batch_specs(cfg, rules, bspec)
        step = steps.make_prefill_step(bundle, max_len=shape.seq_len)
        out_sds = _sds_of(step, params_sds, bspec)
        cache_sp = steps.cache_specs(cfg, rules, out_sds[1], data_total)
        logits_sp = P(dp_axes, None)
        return Cell(arch, shape, mesh_kind, step,
                    (p_specs, b_specs), (logits_sp, cache_sp),
                    (params_sds, bspec), cfg,
                    {"parallelism": f"dp{data_total}xtp{TP}"})

    # decode
    B = shape.global_batch
    cache_sds = _sds_of(
        functools.partial(bundle.init_cache, B, shape.seq_len))
    tok_sds = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    cache_sp = steps.cache_specs(cfg, rules, cache_sds, data_total)
    tok_sp = P(dp_axes, None) if B % data_total == 0 else P()
    step = steps.make_decode_step(bundle)
    logits_sp = P(dp_axes, None) if B % data_total == 0 else P()
    return Cell(arch, shape, mesh_kind, step,
                (p_specs, tok_sp, cache_sp), (logits_sp, cache_sp),
                (params_sds, tok_sds, cache_sds), cfg,
                {"parallelism": f"dp{data_total}xtp{TP}", "donate": (2,)})


def _train_pp_cell(arch, shape, cfg, bundle, key, mesh_kind) -> Cell:
    """Multi-pod training: HETHUB pipeline over the 'pod' axis."""
    rules = ShardingRules(cfg, tp=TP, dp_axes=("data",))
    m = PP_MICROBATCHES
    Bt = shape.global_batch // m

    def init_state(k):
        params = bundle.init(k, cfg)
        params = pipeline.stack_blocks_for_stages(params, PP_MULTIPOD)
        from repro.optim import adamw
        keep_master = cfg.param_dtype != "float32"
        return {"params": params,
                "opt": adamw.init_opt_state(params, keep_master=keep_master),
                "step": jnp.zeros((), jnp.int32)}

    state_sds = _sds_of(init_state, key)
    raw_specs = rules.param_specs(state_sds["params"])
    p_specs = pipeline.pp_param_specs(raw_specs)
    st_specs = {"params": p_specs, "step": P()}
    opt_specs: Dict[str, Any] = {"count": P()}
    for kk in ("m", "v", "master"):
        if kk in state_sds["opt"]:
            opt_specs[kk] = jax.tree.map(
                lambda sp, sh: rules.opt_state_spec(sp, sh.shape, 16),
                p_specs, state_sds["opt"][kk])
    st_specs["opt"] = opt_specs

    bsd = batch_sds(cfg, shape.global_batch, shape.seq_len, True)
    bsd = {k: jax.ShapeDtypeStruct((m, Bt) + v.shape[1:], v.dtype)
           for k, v in bsd.items()}
    b_specs = {k: P(None, ("data",)) if v.ndim == 3
               else P(None, ("data",), None, None)
               for k, v in bsd.items()}

    mesh = None  # bound at lower time via closure-free loss_fn builder

    def make_step(mesh):
        loss_fn = pipeline.make_pp_loss_fn(cfg, mesh, PP_MULTIPOD, m)
        return steps.make_train_step(bundle, rules, loss_fn=loss_fn)

    metrics_spec = {k: P() for k in ("ce", "aux", "loss", "grad_norm", "lr")}
    cell = Cell(arch, shape, mesh_kind, make_step,
                (st_specs, b_specs), (st_specs, metrics_spec),
                (state_sds, bsd), cfg,
                {"parallelism": f"pp{PP_MULTIPOD}xdp16xtp{TP} m={m}",
                 "donate": (0,), "needs_mesh": True})
    return cell
