"""The main path compiled for a described TPU v5e chip (nothing runs, no
chip is needed): each Pallas kernel at the smoke model's real widths must
lower to a TPU custom call, and the 2-layer full-width train step must fit
one chip's HBM.  The topology is described inside a fixture, never at
import, so only the worker that runs this file loads the TPU library."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.kernels import (flash_attention as fa, ring_attention as ra,
                           rmsnorm as rn, ssm_scan as ss, swiglu as sg)
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import ShardingRules
from repro.train import steps

# what the compiler reports as one v5e chip's usable HBM
V5E_HBM_BYTES = 15.75e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler otherwise writes its logs outside the checkout
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's executables cannot be read back from the
        # persistent cache: keep them out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases():
    """name -> (fn, arg shapes); h2o-danube-3-4b widths at seq 2048 (the
    ring hop at 4096-token chunks), ssm_scan at falcon-mamba-7b's."""
    cfg = registry.get_config("h2o-danube-3-4b")
    ssm = registry.get_config("falcon-mamba-7b")
    B, S, D, F = 4, 2048, cfg.d_model, cfg.d_ff
    H, Hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    C = 4096
    di, ds = ssm.d_inner, ssm.ssm_state
    f32 = jnp.float32
    return {
        "rmsnorm": (rn.rmsnorm, [(B, S, D), (D,)]),
        "swiglu": (sg.swiglu, [(B, S, F), (B, S, F)]),
        "flash_attention": (
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                               window=cfg.window),
            [(1, S, H, hd), (1, S, Hk, hd), (1, S, Hk, hd)]),
        "ring_step": (
            lambda q, k, v, m, l, acc: ra.ring_step(
                q, k, v, m, l, acc, q_start=C, k_start=0, k_valid=C),
            [(1, C, H, hd), (1, C, Hk, hd), (1, C, Hk, hd),
             ((1, C, H, 1), f32), ((1, C, H, 1), f32),
             ((1, C, H, hd), f32)]),
        "ssm_scan": (ss.ssm_scan,
                     [(1, S, di), (1, S, di), (1, S, ds), (1, S, ds),
                      ((di, ds), f32)]),
        "ssm_scan_bwd": (
            lambda u, dt, b, c, a, dy: jax.vjp(ss.ssm_scan, u, dt, b, c,
                                               a)[1](dy),
            [(1, S, di), ((1, S, di), f32), ((1, S, ds), f32),
             ((1, S, ds), f32), ((di, ds), f32), ((1, S, di), f32)]),
    }


@pytest.mark.parametrize("name", ["rmsnorm", "swiglu", "flash_attention",
                                  "ring_step", "ssm_scan", "ssm_scan_bwd"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    args = [_sds(one_chip, *s) if isinstance(s[0], tuple)
            else _sds(one_chip, s) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_scan_kernels_count_for_the_scan_scope(one_chip, monkeypatch):
    """The gradient of ``selective_scan``'s kernel path, compiled for a v5e:
    every Mosaic call of the forward and backward kernels maps to the
    ``ssm_scan`` scope, so the scan's device time cannot move to
    ``unscoped``.  (Here and not beside the other scope tests: only one
    test file may describe the chip.)"""
    from repro.kernels import ops
    from repro.models import mamba
    from repro.obs import scopes
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    ssm = registry.get_config("falcon-mamba-7b")
    S, di, ds = 2048, ssm.d_inner, ssm.ssm_state
    f32 = jnp.float32
    shapes = [(1, S, di), ((1, S, di), f32), ((1, S, ds), f32),
              ((1, S, ds), f32), ((di, ds), f32), ((di,), f32), (1, S, di)]
    args = [_sds(one_chip, *s) if isinstance(s[0], tuple)
            else _sds(one_chip, s) for s in shapes]

    def loss(*a):
        with jax.named_scope("ssm_block"):
            y = jax.checkpoint(mamba.selective_scan)(*a)
        return jnp.sum(y.astype(f32))

    text = jax.jit(jax.grad(loss, argnums=tuple(range(7)))).lower(
        *args).compile().as_text()
    table = scopes.op_scopes(text)
    calls = {ln.split(" = ")[0].split()[-1].lstrip("%"):
             ln.split('custom_call_target="')[1].split('"')[0]
             for ln in text.splitlines() if "custom-call(" in ln}
    mosaic = [n for n, target in calls.items()
              if target == "tpu_custom_call"]
    assert {n.rsplit(".", 1)[0] for n in mosaic} == {"ssm_scan_fwd",
                                                      "ssm_scan_bwd"}
    assert {table.get(n) for n in mosaic} == {"ssm_scan"}


def test_held_experts_grouped_matmul_compiles_for_v5e(one_chip, monkeypatch):
    """The held experts' Pallas grouped matmul at the Moonlight cell's
    shapes (1 x 8192 tokens x top 6 rows, 8 experts of 2,048 x 1,408),
    forward and backward, fits a v5e's VMEM with its tiles, and every
    Mosaic call of it maps to the ``moe_experts`` scope."""
    from repro.kernels import ops
    from repro.models import moe
    from repro.obs import scopes
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    cfg = registry.get_config("moonlight-16b-a3b")
    M, D, F, E = 8192 * cfg.top_k, cfg.d_model, cfg.d_expert, 8
    args = (_sds(one_chip, (M, D)), _sds(one_chip, (E, D, F)),
            _sds(one_chip, (E, F, D)), _sds(one_chip, (E,), jnp.int32))

    def loss(x, w_up, w_down, sizes):
        with jax.named_scope("moe_experts"):
            h = moe.grouped_matmul(x, w_up, sizes).astype(x.dtype)
            y = moe.grouped_matmul(h, w_down, sizes)
        return jnp.sum(jnp.sin(y))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    table = scopes.op_scopes(text)
    mosaic = [ln.split(" = ")[0].split()[-1].lstrip("%")
              for ln in text.splitlines()
              if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(mosaic) == 6          # 2 forward, 2 x 2 backward
    assert {table.get(n) for n in mosaic} == {"moe_experts"}


@pytest.mark.parametrize("pp", [1, 2], ids=["data4", "pp2"])
def test_mamba_train_step_compiles_on_four_v5e(topo, monkeypatch, pp):
    """falcon-mamba's train step on the Trainer's mesh over four described
    v5e chips, (data 4) or two pipeline stages on (pod 2, data 2), with
    the scan on its kernel path.  The compiler refuses a Mosaic kernel
    left to partitioning, so each kernel call must run per shard."""
    from types import SimpleNamespace

    from jax.sharding import PartitionSpec as P

    from repro.ckpt import checkpoint as ckpt
    from repro.kernels import ops
    from repro.launch.mesh import make_train_mesh
    from repro.parallel import pipeline
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    bundle = registry.get_bundle("falcon-mamba-7b", smoke=True, num_layers=2)
    mesh = make_train_mesh(SimpleNamespace(pp=pp), topo.devices)
    rules = ShardingRules(bundle.cfg, tp=1, dp_axes=("data",))
    data = mesh.shape["data"]
    state = jax.eval_shape(lambda k: steps.init_train_state(bundle, k),
                           jax.random.PRNGKey(0))
    tokens, batch_spec, loss_fn = (8, 64), rules.batch_spec(), None
    if pp == 1:
        specs = steps.state_specs(bundle, rules, state, data_size=data)
    else:
        # the Trainer's stacked state and its shardings for a 1 + 1 plan
        layout = {"pp": pp, "vpp": 1, "virtual_layers": [1, 1],
                  "stage_tp": [1, 1]}
        state = jax.eval_shape(lambda s: ckpt.migrate(s, None, layout),
                               state)
        p_specs = pipeline.pp_param_specs(rules.param_specs(state["params"]))
        specs = {"params": p_specs, "step": P(), "opt": {"count": P()}}
        for k in ("m", "v", "master"):
            if k in state["opt"]:
                specs["opt"][k] = jax.tree.map(
                    lambda sp, sh: rules.opt_state_spec(sp, sh.shape, data),
                    p_specs, state["opt"][k])
        loss_fn = pipeline.make_pp_loss_fn(bundle.cfg, mesh, pp, 2,
                                           layers_per_stage=[1, 1])
        tokens, batch_spec = (2, 4, 64), P(None, *batch_spec)
    state = jax.tree.map(
        lambda s, p: _sds(NamedSharding(mesh, p), s.shape, s.dtype),
        state, specs)
    tok = _sds(NamedSharding(mesh, batch_spec), tokens, jnp.int32)
    step = steps.make_train_step(bundle, rules, AdamWConfig(),
                                 loss_fn=loss_fn)
    with jax.set_mesh(mesh):
        text = jax.jit(step, donate_argnums=0).lower(
            state, {"tokens": tok, "labels": tok}).compile().as_text()
    kernels = {ln.split(" = ")[0].split()[-1].lstrip("%").rsplit(".", 1)[0]
               for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln}
    assert kernels == {"ssm_scan_fwd", "ssm_scan_bwd"}


def test_init_master_is_the_rounded_params_on_v5e(one_chip):
    """The fp32 master must start at the bf16 params' values.  Fused with
    the random draw, the TPU compiler emits the master from the unrounded
    f32 draw in the same fusion as the params; the pipeline and the plain
    step then start from different masters and part at step 1."""
    bundle = registry.get_bundle("h2o-danube-3-4b", smoke=True,
                                 param_dtype="bfloat16", dtype="bfloat16")
    key = _sds(one_chip, (2,), jnp.uint32)
    hlo = jax.jit(lambda k: steps.init_train_state(
        bundle, jax.random.wrap_key_data(k))).lower(key).compile().as_text()
    entry = hlo[hlo.index("ENTRY"):].splitlines()
    fused_both = [ln for ln in entry if " fusion(" in ln
                  and "= (f32" in ln and "bf16" in ln.split(" fusion(")[0]]
    assert not fused_both, fused_both[0][:200]


def test_full_width_train_step_fits_one_v5e(topo):
    """The Trainer's plain step (AdamW, fp32 master, donated state) for
    h2o-danube-3-4b at published widths, 2 layers, batch 4 x seq 2048."""
    bundle = registry.get_bundle("h2o-danube-3-4b", num_layers=2)
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    rules = ShardingRules(bundle.cfg, tp=1, dp_axes=("data",))
    state = jax.eval_shape(lambda k: steps.init_train_state(bundle, k),
                           jax.random.PRNGKey(0))
    specs = steps.state_specs(bundle, rules, state, data_size=1)
    state = jax.tree.map(
        lambda s, p: _sds(NamedSharding(mesh, p), s.shape, s.dtype),
        state, specs)
    tok = _sds(NamedSharding(mesh, rules.batch_spec()), (4, 2048), jnp.int32)
    step = steps.make_train_step(bundle, rules, AdamWConfig())
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, donate_argnums=0).lower(
            state, {"tokens": tok, "labels": tok}).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, (mem.argument_size_in_bytes,
                                  mem.temp_size_in_bytes)
