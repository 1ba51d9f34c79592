"""Named scopes of the train step and the Trainer's profiler spans.

- ``scope_of`` reads the innermost scope through transform wrappers;
- the compiled train step maps its instructions onto each layer's scope,
  whichever loss builder made it (plain, chunked loss, pipeline, cp);
- the registry compiles nothing until its table is asked for, and its
  table is the one of the compiled program;
- ``Trainer.run`` writes each ``trainer.*`` span once per step in a
  ``jax.profiler`` trace, and no span named ``train``;
- the first step after a build is still left out of the profile, and the
  operator's ``input_s`` gauge is written.
"""
import collections

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.obs import Observability, read_jsonl, scopes
from repro.optim.adamw import AdamWConfig
from repro.parallel import context, pipeline
from repro.parallel.sharding import ShardingRules
from repro.profile.store import ProfileStore
from repro.train import steps
from repro.train.trainer import Trainer, TrainerConfig

LAYERS = {"embed", "attn", "mlp", "head_loss"}


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train_step)/jvp(attn)/dot_general", "attn"),
    ("jit(train_step)/transpose(jvp(mlp))/mul", "mlp"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/tanh", "attn"),
    ("jit(train_step)/jvp()/while/body/closed_call/ssm_block/ssm_scan/"
     "while/body/add", "ssm_scan"),
    ("jit(train_step)/jvp(ssm_block)/jvp(ssm_scan)/exp", "ssm_scan"),
    ("jit(train_step)/optimizer/sqrt", "optimizer"),
    ("jit(train_step)/jvp()/add_any", None),
], ids=["forward", "backward", "remat", "nested", "nested_wrapped",
        "plain", "none"])
def test_scope_of(op_name, scope):
    assert scopes.scope_of(op_name) == scope


HLO = """HloModule m

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %mul.3 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/jvp(mlp)/mul"}
}

%body.5 (t: f32[4]) -> f32[4] {
  %t = f32[4]{0} parameter(0)
  %copy-start.6 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%t)
  ROOT %copy-done.7 = f32[4]{0} copy-done(%copy-start.6)
}

%cond.8 (c: f32[4]) -> pred[] {
  %c = f32[4]{0} parameter(0)
  ROOT %constant.9 = pred[] constant(false)
}

ENTRY %main.10 (a: f32[4], /*index=1*/b: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/jvp(mlp)/mul"}
  %add.2 = f32[4]{0} add(%a, %a), metadata={op_name="jit(f)/transpose(jvp(attn))/add"}
  %while.4 = f32[4]{0} while(%add.2), condition=%cond.8, body=%body.5, metadata={op_name="jit(f)/ssm_scan/while"}
  ROOT %copy.11 = f32[4]{0} copy(%while.4), metadata={op_name="jit(f)/copy"}
}
"""


def test_op_scopes_reads_op_names_and_loops():
    """Each instruction's own scope; a copy the compiler put in a loop
    body takes the loop's."""
    assert scopes.op_scopes(HLO) == {
        "mul.3": "mlp", "fusion.1": "mlp", "add.2": "attn",
        "while.4": "ssm_scan", "t": "ssm_scan", "copy-start.6": "ssm_scan",
        "copy-done.7": "ssm_scan", "c": "ssm_scan", "constant.9": "ssm_scan"}


def _compiled_scopes(fn, *args):
    return set(scopes.op_scopes(
        jax.jit(fn).lower(*args).compile().as_text()).values())


@pytest.mark.parametrize("arch, want", [
    ("h2o-danube-3-4b", LAYERS | {"optimizer"}),
    ("falcon-mamba-7b", {"embed", "ssm_block", "ssm_scan", "head_loss",
                         "optimizer"}),
])
def test_compiled_train_step_maps_to_each_layer(arch, want):
    b = registry.get_bundle(arch, smoke=True)
    rules = ShardingRules(b.cfg, tp=1, dp_axes=("data",))
    state = steps.init_train_state(b, jax.random.PRNGKey(0))
    batch = registry.make_batch(b.cfg, batch=2, seq=32)
    step = steps.make_train_step(b, rules, AdamWConfig())
    assert _compiled_scopes(step, state, batch) == want


def test_compiled_moonlight_step_maps_to_each_layer():
    """Latent attention, its chunked core (forced at the smoke size), the
    expert routing and the held experts' grouped matmuls each have their
    scope; the shared experts and the dense layer's MLP are in ``mlp``."""
    b = registry.get_bundle("moonlight-16b-a3b", smoke=True, attn_chunk=8)
    rules = ShardingRules(b.cfg, tp=1, dp_axes=("data",))
    state = steps.init_train_state(b, jax.random.PRNGKey(0))
    batch = registry.make_batch(b.cfg, batch=2, seq=32)
    step = steps.make_train_step(b, rules, AdamWConfig())
    assert _compiled_scopes(step, state, batch) == {
        "embed", "mla", "attn_core", "mlp", "moe_route", "moe_experts",
        "head_loss", "optimizer"}


def test_scan_kernels_map_to_the_scan_scope(monkeypatch):
    """The falcon-mamba step on the scan's kernel path (interpreted on the
    CPU): every instruction of the forward and backward kernels, remat
    recompute included, maps to ``ssm_scan``, and the step's layers are
    the same as on the jnp path."""
    import functools
    from repro.kernels import ops, ssm_scan as ss
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    monkeypatch.setattr(ss, "ssm_scan",
                        functools.partial(ss.ssm_scan, interpret=True))
    b = registry.get_bundle("falcon-mamba-7b", smoke=True)
    rules = ShardingRules(b.cfg, tp=1, dp_axes=("data",))
    state = steps.init_train_state(b, jax.random.PRNGKey(0))
    batch = registry.make_batch(b.cfg, batch=2, seq=32)
    step = steps.make_train_step(b, rules, AdamWConfig())
    text = jax.jit(step).lower(state, batch).compile().as_text()
    table = scopes.op_scopes(text)
    kernel_ops = collections.defaultdict(set)
    for line in text.splitlines():
        instr, op = scopes._INSTR.match(line), scopes._OP_NAME.search(line)
        for kernel in ("ssm_scan_fwd", "ssm_scan_bwd"):
            if instr and op and f"/{kernel}/" in op.group(1):
                kernel_ops[kernel].add(table.get(instr.group(1)))
    assert kernel_ops == {"ssm_scan_fwd": {"ssm_scan"},
                          "ssm_scan_bwd": {"ssm_scan"}}
    assert set(table.values()) == {"embed", "ssm_block", "ssm_scan",
                                   "head_loss", "optimizer"}


def _loss_case(builder):
    b = registry.get_bundle("llama3-8b", smoke=True, num_layers=2)
    cfg = b.cfg
    params = b.init(jax.random.PRNGKey(0), cfg)
    batch = registry.make_batch(cfg, batch=4, seq=32)
    rules = ShardingRules(cfg, tp=1, dp_axes=("data",))
    if builder == "loss_chunk":
        b = registry.get_bundle("llama3-8b", smoke=True, num_layers=2,
                                loss_chunk=8)
        return steps.make_loss_fn(b, rules), params, batch
    if builder == "cp":
        return context.make_cp_loss_fn(cfg, None, (20, 12)), params, batch
    vpp = 2 if builder == "pipeline_vpp" else 1
    layers = [1, 1, 0, 0] if vpp == 2 else None
    pp_params = pipeline.stack_blocks_for_stages(params, 2, layers, vpp=vpp)
    pp_batch = {k: v.reshape(2, 2, *v.shape[1:]) for k, v in batch.items()}
    return (pipeline.make_pp_loss_fn(cfg, None, 2, 2, layers_per_stage=layers,
                                     vpp=vpp), pp_params, pp_batch)


@pytest.mark.parametrize("builder", ["loss_chunk", "pipeline",
                                     "pipeline_vpp", "cp"])
def test_every_loss_builder_reaches_the_scopes(builder):
    loss, params, batch = _loss_case(builder)
    grad = jax.value_and_grad(lambda p, b: loss(p, b)[0])
    assert _compiled_scopes(grad, params, batch) == LAYERS


def _count_compiles():
    events = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            events.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    return events, listen


def test_note_compiles_nothing_and_table_is_the_compiled_programs():
    b = registry.get_bundle("h2o-danube-3-4b", smoke=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = ShardingRules(b.cfg, tp=1, dp_axes=("data",))
    step = jax.jit(steps.make_train_step(b, rules, AdamWConfig()),
                   donate_argnums=0)
    with jax.set_mesh(mesh):
        state = steps.init_train_state(b, jax.random.PRNGKey(1))
    args = scopes.abstract((state, registry.make_batch(b.cfg, batch=2,
                                                       seq=16)))
    events, listen = _count_compiles()
    try:
        scopes.note_program("test_step", step, args, mesh)
        assert events == []
        got = scopes.table("test_step")
        assert len(events) <= 1
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    with jax.set_mesh(mesh):
        want = scopes.op_scopes(step.lower(*args).compile().as_text())
    assert got == want and set(got.values()) == LAYERS | {"optimizer"}
    assert scopes.table("never_noted") is None


@pytest.fixture
def trainer(tmp_path):
    b = registry.get_bundle("h2o-danube-3-4b", smoke=True)
    return Trainer(b, make_mesh((1, 1), ("data", "model")),
                   TrainerConfig(global_batch=2, seq_len=16,
                                 ckpt_dir=str(tmp_path / "ck"),
                                 ckpt_every=100),
                   profile_store=ProfileStore(),
                   obs=Observability(metrics_out=tmp_path / "m.jsonl"))


def test_trainer_spans_on_the_profilers_clock(trainer, tmp_path):
    trainer.run(1)                       # compiles outside the trace
    with jax.profiler.trace(str(tmp_path / "trace")):
        trainer.run(2)
    pd = ProfileData.from_file(str(next(
        (tmp_path / "trace").rglob("*.xplane.pb"))))
    names = collections.Counter(
        ev.name for plane in pd.planes if plane.name.startswith("/host")
        for line in plane.lines for ev in line.events)
    spans = {n: c for n, c in names.items() if n.startswith("trainer.")}
    assert spans == {f"trainer.{s}": 2 for s in
                     ("batch", "put", "dispatch", "sync", "after")}
    assert names["train"] == 0
    # the step it ran is noted, and its table names the step's layers
    assert set(scopes.table("train_step").values()) == \
        LAYERS | {"optimizer"}


def test_first_step_after_a_build_is_not_folded(trainer, tmp_path):
    trainer.run(3)
    folded = trainer.profile_store.entries(op="observed_step")
    assert sum(e.value["n"] for e in folded) == 2
    trainer._build()
    trainer.run(1)
    folded = trainer.profile_store.entries(op="observed_step")
    assert sum(e.value["n"] for e in folded) == 2
    trainer.obs.close()
    gauges = [r for r in read_jsonl(tmp_path / "m.jsonl")
              if r.get("name") == "input_s"]
    assert gauges and all(np.isfinite(r["value"]) and r["value"] > 0
                          for r in gauges)


def test_observability_writes_input_seconds(tmp_path):
    obs = Observability(metrics_out=tmp_path / "m.jsonl")
    obs.on_step(1, 0.5, None, input_s=0.004)
    obs.on_step(2, 0.5, None)
    obs.close()
    recs = [r for r in read_jsonl(tmp_path / "m.jsonl")
            if r.get("name") == "input_s"]
    assert [(r["step"], r["value"]) for r in recs] == [(1, 0.004)]


def test_scopes_are_named_once():
    assert len(set(scopes.SCOPES)) == len(scopes.SCOPES)
    assert "train" not in scopes.SCOPES
    assert all(scopes.scope_of(f"jit(f)/{s}/x") == s for s in scopes.SCOPES)
