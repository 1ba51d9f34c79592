"""Pipeline-parallel loss equivalence, non-uniform segmentation, MoE
dispatch properties, sharding-rule resolution."""
import dataclasses
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.launch.mesh import make_mesh
from repro.models import moe, registry
from repro.models.config import ModelConfig
from repro.parallel import pipeline
from repro.parallel.sharding import ShardingRules
from repro.train import steps


# ----------------------------------------------------------- pipeline ------
def _pp_setup(num_layers=2, layers_per_stage=None):
    b = registry.get_bundle("llama3-8b", smoke=True, num_layers=num_layers)
    cfg = b.cfg
    params = b.init(jax.random.PRNGKey(0), cfg)
    m, Bt, S = 4, 2, 32
    batch = registry.make_batch(cfg, batch=m * Bt, seq=S)
    rules = ShardingRules(cfg, tp=1, dp_axes=("data",))
    ref, _ = steps.make_loss_fn(b, rules)(params, batch)
    pp_params = pipeline.stack_blocks_for_stages(params, 2, layers_per_stage)
    pp_batch = {k: v.reshape(m, Bt, *v.shape[1:]) for k, v in batch.items()}
    lf = pipeline.make_pp_loss_fn(cfg, None, 2, m,
                                  layers_per_stage=layers_per_stage)
    got, _ = jax.jit(lf)(pp_params, pp_batch)
    return float(ref), float(got), params, pp_params, lf, pp_batch, b, batch


def test_pipeline_matches_reference():
    ref, got, *_ = _pp_setup()
    assert abs(ref - got) < 1e-4


def test_pipeline_nonuniform_matches_reference():
    ref, got, *_ = _pp_setup(num_layers=4, layers_per_stage=[3, 1])
    assert abs(ref - got) < 1e-4


def test_pipeline_grads_match_reference():
    _, _, params, pp_params, lf, pp_batch, b, batch = _pp_setup()
    rules = ShardingRules(b.cfg, tp=1, dp_axes=("data",))
    g_ref = jax.grad(lambda p: steps.make_loss_fn(b, rules)(p, batch)[0])(
        params)
    g_pp = jax.jit(jax.grad(lambda p: lf(p, pp_batch)[0]))(pp_params)
    d = float(jnp.max(jnp.abs(g_ref["embed"] - g_pp["embed"])))
    assert d < 1e-4
    wq_ref = g_ref["blocks"]["attn"]["wq"]
    wq_pp = g_pp["blocks"]["attn"]["wq"]
    assert float(jnp.max(jnp.abs(
        wq_ref.reshape(wq_pp.shape) - wq_pp))) < 1e-4


def _pp_vpp_setup(virtual_layers, vpp, num_layers=4):
    """Interleaved virtual stages (pp=2): loss must equal the reference."""
    b = registry.get_bundle("llama3-8b", smoke=True, num_layers=num_layers)
    cfg = b.cfg
    params = b.init(jax.random.PRNGKey(0), cfg)
    m, Bt, S = 4, 2, 32
    batch = registry.make_batch(cfg, batch=m * Bt, seq=S)
    rules = ShardingRules(cfg, tp=1, dp_axes=("data",))
    ref, _ = steps.make_loss_fn(b, rules)(params, batch)
    pp_params = pipeline.stack_blocks_for_stages(params, 2, virtual_layers,
                                                 vpp=vpp)
    pp_batch = {k: v.reshape(m, Bt, *v.shape[1:]) for k, v in batch.items()}
    lf = pipeline.make_pp_loss_fn(cfg, None, 2, m,
                                  layers_per_stage=virtual_layers, vpp=vpp)
    got, _ = jax.jit(lf)(pp_params, pp_batch)
    return float(ref), float(got), params, pp_params, lf, pp_batch, b, batch


def test_pipeline_vpp_matches_reference():
    """vpp=2 round-robin chunk stacking == the plain forward pass, both for
    the even split and a non-uniform virtual split (zero-layer chunk)."""
    ref, got, *_ = _pp_vpp_setup(None, vpp=2)
    assert abs(ref - got) < 1e-4
    ref, got, *_ = _pp_vpp_setup([2, 1, 1, 0], vpp=2)
    assert abs(ref - got) < 1e-4


def test_pipeline_vpp_grads_and_train_step():
    """Interleaved pipeline gradients match the reference, and the loss fn
    drives a full train step (optimizer included) — interleaved plans are
    executable, not just predictable."""
    _, _, params, pp_params, lf, pp_batch, b, batch = _pp_vpp_setup(
        [2, 1, 1, 0], vpp=2)
    rules = ShardingRules(b.cfg, tp=1, dp_axes=("data",))
    g_ref = jax.grad(lambda p: steps.make_loss_fn(b, rules)(p, batch)[0])(
        params)
    g_pp = jax.jit(jax.grad(lambda p: lf(p, pp_batch)[0]))(pp_params)
    d = float(jnp.max(jnp.abs(g_ref["embed"] - g_pp["embed"])))
    assert d < 1e-4
    from repro.optim import adamw
    state = {"params": pp_params,
             "opt": adamw.init_opt_state(pp_params, True),
             "step": jnp.zeros((), jnp.int32)}
    step = steps.make_train_step(b, rules, loss_fn=lf)
    state2, metrics = jax.jit(step)(state, pp_batch)
    assert float(metrics["loss"]) == pytest.approx(
        float(lf(pp_params, pp_batch)[0]), rel=1e-5)
    moved = jnp.max(jnp.abs(state2["params"]["embed"] - pp_params["embed"]))
    assert float(moved) > 0.0


def test_pipeline_vpp_mixed_tp_matches_reference():
    """Asymmetric per-stage tp arms the boundary reshard in BOTH loss
    builders (the pod-roll buffer is constrained model-unsharded when
    stages disagree on width): the all-gather/re-split round trip is the
    numerical identity, so interleaved mixed-tp plans keep loss AND
    gradients reference-exact."""
    assert pipeline._mixed_tp([2, 1]) and not pipeline._mixed_tp([4, 4])
    b = registry.get_bundle("llama3-8b", smoke=True, num_layers=4,
                            act_sharding=(("data",), "model", None))
    cfg = b.cfg
    params = b.init(jax.random.PRNGKey(0), cfg)
    m, Bt, S = 4, 2, 32
    batch = registry.make_batch(cfg, batch=m * Bt, seq=S)
    rules = ShardingRules(cfg, tp=1, dp_axes=("data",))
    ref = steps.make_loss_fn(b, rules)(params, batch)[0]
    g_ref = jax.grad(lambda p: steps.make_loss_fn(b, rules)(p, batch)[0])(
        params)
    pp_batch = {k: v.reshape(m, Bt, *v.shape[1:]) for k, v in batch.items()}
    for vpp, vl in [(1, [3, 1]), (2, [2, 1, 1, 0])]:
        pp_params = pipeline.stack_blocks_for_stages(params, 2, vl, vpp=vpp)
        lf = pipeline.make_pp_loss_fn(cfg, None, 2, m, layers_per_stage=vl,
                                      vpp=vpp, stage_tp=[2, 1])
        got = jax.jit(lf)(pp_params, pp_batch)[0]
        assert abs(float(ref) - float(got)) < 1e-4
        g_pp = jax.jit(jax.grad(lambda p: lf(p, pp_batch)[0]))(pp_params)
        assert float(jnp.max(jnp.abs(g_ref["embed"] - g_pp["embed"]))) < 1e-4
    with pytest.raises(AssertionError, match="stage_tp needs 2 entries"):
        pipeline.make_pp_loss_fn(cfg, None, 2, m, stage_tp=[2, 1, 1])


def test_pipeline_mpod_compiles_sharded():
    """Full fwd+bwd+AdamW pipeline step compiles on a (2,2,2) fake-device
    mesh with collective-permutes on the pod axis (subprocess: device count
    must be set before jax init)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.models import registry
from repro.launch.mesh import make_mesh
from repro.parallel import pipeline
from repro.parallel.sharding import ShardingRules
from repro.train import steps
from repro.optim import adamw
b = registry.get_bundle("llama3-8b", smoke=True, num_layers=4,
                        param_dtype="bfloat16", dtype="bfloat16",
                        act_sharding=(("data",), "model", None))
cfg = b.cfg
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
rules = ShardingRules(cfg, tp=2, dp_axes=("data",))
def init_state(k):
    p = pipeline.stack_blocks_for_stages(b.init(k, cfg), 2)
    return {"params": p, "opt": adamw.init_opt_state(p, True),
            "step": jnp.zeros((), jnp.int32)}
sds = jax.eval_shape(init_state, jax.random.PRNGKey(0))
p_specs = pipeline.pp_param_specs(rules.param_specs(sds["params"]))
st_specs = {"params": p_specs, "step": P(),
            "opt": {"count": P(), **{k: jax.tree.map(
                lambda sp, sh: rules.opt_state_spec(sp, sh.shape, 2),
                p_specs, sds["opt"][k]) for k in ("m", "v", "master")}}}
bsd = {k: jax.ShapeDtypeStruct((4, 4, 32), jnp.int32)
       for k in ("tokens", "labels")}
b_specs = {k: P(None, ("data",)) for k in bsd}
lf = pipeline.make_pp_loss_fn(cfg, mesh, 2, 4)
step = steps.make_train_step(b, rules, loss_fn=lf)
ns = lambda s: NamedSharding(mesh, s)
with jax.set_mesh(mesh):
    c = jax.jit(step, in_shardings=jax.tree.map(ns, (st_specs, b_specs)),
                out_shardings=jax.tree.map(ns, (st_specs, {k: P() for k in
                ("ce","aux","loss","grad_norm","lr")}))).lower(sds, bsd).compile()
import repro.utils.hlo as H
st = H.collective_stats(c.as_text())
assert st.count_by_op.get("collective-permute", 0) > 0, st.count_by_op
print("PP_COMPILE_OK")
"""
    r = subprocess.run([sys.executable, "-c", code],
                       cwd=str(Path(__file__).resolve().parents[1]),
                       capture_output=True, text=True, timeout=900)
    assert "PP_COMPILE_OK" in r.stdout, r.stderr[-2000:]


# ----------------------------------------------------------------- moe -----
def _moe_cfg(**kw):
    base = dict(name="m", family="moe", num_layers=1, d_model=32,
                n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                n_experts=4, top_k=2, param_dtype="float32",
                dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def test_moe_no_drop_matches_dense_mixture():
    """With capacity >= tokens, capacity-dispatch == explicit expert mixture."""
    cfg = _moe_cfg(capacity_factor=8.0)
    p = moe.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    got, aux = moe.moe_mlp(p, x, cfg)

    # reference: route every token through its top-k experts exactly
    logits = jnp.einsum("bsd,de->bse", x, p["router"])
    gates = jax.nn.softmax(logits, -1)
    gval, gidx = jax.lax.top_k(gates, cfg.top_k)
    gval = gval / gval.sum(-1, keepdims=True)
    y_all = []
    for e in range(cfg.n_experts):
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"][e])
        u = jnp.einsum("bsd,df->bsf", x, p["w_up"][e])
        h = jax.nn.silu(g) * u
        y_all.append(jnp.einsum("bsf,fd->bsd", h, p["w_down"][e]))
    y_all = jnp.stack(y_all, axis=2)                     # (B,S,E,D)
    want = jnp.zeros_like(x)
    for k in range(cfg.top_k):
        want = want + gval[..., k:k + 1] * jnp.take_along_axis(
            y_all, gidx[..., k][..., None, None], axis=2)[..., 0, :]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    assert float(aux) >= 1.0 - 1e-5       # E * sum(me*ce) >= 1 at balance


def test_moe_capacity_drops_bounded():
    cfg = _moe_cfg(capacity_factor=0.5)
    p = moe.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 32))
    got, _ = moe.moe_mlp(p, x, cfg)
    assert not bool(jnp.any(jnp.isnan(got)))


@given(st.integers(1, 3), st.sampled_from([4, 8]), st.sampled_from([1, 2]))
@settings(max_examples=10, deadline=None)
def test_moe_capacity_rounding(cf_x, E, K):
    cfg = _moe_cfg(n_experts=E, top_k=K, capacity_factor=float(cf_x))
    C = moe.row_capacity(64, cfg)
    assert C >= 1 and C % 8 == 0


# ------------------------------------------------------------- sharding ----
@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_sharding_specs_divisible(arch):
    """Every sharded dim must divide by the mesh axis it's mapped to."""
    cfg = registry.get_config(arch)
    b = registry.bundle_for(cfg)
    rules = ShardingRules(cfg, tp=16, dp_axes=("data",))
    sds = jax.eval_shape(lambda k: b.init(k, cfg), jax.random.PRNGKey(0))
    specs = rules.param_specs(sds)
    sizes = {"data": 16, "model": 16}

    def check(leaf, spec):
        for dim, part in zip(leaf.shape, tuple(spec)):
            if part is None:
                continue
            parts = part if isinstance(part, tuple) else (part,)
            n = 1
            for ax in parts:
                n *= sizes[ax]
            assert dim % n == 0, f"{arch}: {leaf.shape} vs {spec}"

    jax.tree.map(check, sds, specs)


def test_sharding_kv_replication_rule():
    cfg = registry.get_config("llama3-8b")          # kv=8 < tp=16
    rules = ShardingRules(cfg, tp=16)
    assert not rules.shard_kv and rules.shard_q
    cfg2 = registry.get_config("phi-3-vision-4.2b")  # kv=32
    assert ShardingRules(cfg2, tp=16).shard_kv
    cfg3 = registry.get_config("whisper-tiny")       # 6 heads
    r3 = ShardingRules(cfg3, tp=16)
    assert not r3.shard_q and r3.shard_ff and r3.shard_vocab


def test_ep_rule_phi35():
    cfg = registry.get_config("phi3.5-moe-42b-a6.6b")
    assert ShardingRules(cfg, tp=16, ep=True).ep       # 16 experts / 16
    cfg2 = registry.get_config("mixtral-8x7b")
    assert not ShardingRules(cfg2, tp=16, ep=True).ep  # 8 experts / 16


# ----------------------------------------- beyond-paper §Perf features -----
def test_moe_manual_shard_map_matches_gspmd():
    """Manual SP-boundary MoE == GSPMD MoE (single-device mesh: collectives
    degenerate but the dispatch/combine math is fully exercised)."""
    cfg = _moe_cfg(capacity_factor=8.0)
    cfg_m = dataclasses.replace(cfg, moe_impl="shard_map",
                                mesh_axes=(("data",), "model"))
    p = moe.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    ref, _ = moe._moe_mlp_gspmd(p, x, cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        got, _ = jax.jit(lambda p, x: moe.moe_mlp(p, x, cfg_m))(p, x)
        g_ref = jax.grad(
            lambda p: jnp.sum(moe._moe_mlp_gspmd(p, x, cfg)[0] ** 2))(p)
        g_got = jax.jit(jax.grad(
            lambda p: jnp.sum(moe.moe_mlp(p, x, cfg_m)[0] ** 2)))(p)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g_ref["w_gate"]),
                               np.asarray(g_got["w_gate"]),
                               rtol=1e-3, atol=1e-4)


def test_moe_ep_matches_gspmd():
    """EP-MoE (full-width experts per shard) == GSPMD MoE."""
    cfg = _moe_cfg(capacity_factor=8.0)
    cfg_ep = dataclasses.replace(cfg, moe_impl="shard_map_ep",
                                 mesh_axes=(("data",), "model"))
    p = moe.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    ref, _ = moe._moe_mlp_gspmd(p, x, cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        got, _ = jax.jit(lambda p, x: moe.moe_mlp(p, x, cfg_ep))(p, x)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-4, atol=1e-5)


def test_fsdp_sharding_rules():
    """FSDP mode: every param shards its last divisible dim over 'model';
    batch axes extend with the model axis."""
    cfg = registry.get_config("llama3-8b")
    b = registry.bundle_for(cfg)
    rules = ShardingRules(cfg, tp=16, mode="fsdp")
    assert rules.batch_axes == ("data", "model")
    sds = jax.eval_shape(lambda k: b.init(k, cfg), jax.random.PRNGKey(0))
    specs = rules.param_specs(sds)

    def check(leaf, spec):
        parts = tuple(spec)
        sharded = [q for q in parts if q is not None]
        if max(leaf.shape, default=0) >= 16 and any(
                d % 16 == 0 and d >= 16 for d in leaf.shape):
            assert sharded == ["model"], (leaf.shape, parts)
        for d, q in zip(leaf.shape, parts):
            if q == "model":
                assert d % 16 == 0

    jax.tree.map(check, sds, specs)
