"""Adaptation-controller lockdown suite (repro.adapt — the closed loop,
autonomous edition):

  * ReplanPolicy unit tests — hysteresis never flaps on oscillating
    bubble ratios, cooldown is respected, the min-expected-gain gate
    blocks unprofitable migrations, bucketed (timer-mode) observations
    earn less trust;
  * planner expected-gain accounting (PlannerResult.baseline_time /
    .expected_gain under a shared cost source);
  * multi-host telemetry aggregation — ProfileStore fold-merge is exact
    (n-weighted running means compose), the in-memory fan-in builds one
    per-island view from per-process stores, and the allgather
    aggregator's wire format round-trips;
  * provenance fix — timer-mode folds are marked ``bucketed`` and
    down-weighted by the cost model;
  * the e2e acceptance scenario on a CPU mesh: inject a degrade mid-run
    and the controller detects, replans, gain-gates and live-migrates BY
    ITSELF — with the final train state bit-exact against the PR-4
    manual degrade->replan path, and never migrating when the predicted
    gain is below ε.
"""
import argparse
import dataclasses
import json
import tempfile
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.adapt import (AdaptConfig, InMemoryFanIn, LocalAggregator,
                         ProcessAllGatherAggregator, ReplanPolicy,
                         default_aggregator, events_json, merge_stores)
from repro.core import cluster as C
from repro.core import planner
from repro.core.plan import ParallelPlan, StagePlacement
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.profile.model import BUCKETED_WEIGHT, ProfiledCostModel
from repro.profile.store import ProfileStore
from repro.telemetry import StageTelemetry
from repro.train.trainer import Trainer, TrainerConfig


# ------------------------------------------------------------ policy unit --
def _cfg(**kw):
    base = dict(straggler_enter=2.0, straggler_exit=1.5, bubble_enter=1.5,
                bubble_exit=1.2, patience=2, cooldown=4, baseline_steps=2,
                ewma=1.0, min_gain=0.05)
    base.update(kw)
    return AdaptConfig(**base)


def test_config_validation():
    with pytest.raises(ValueError, match="straggler_enter"):
        AdaptConfig(straggler_enter=1.0, straggler_exit=1.5)
    with pytest.raises(ValueError, match="bubble_enter"):
        AdaptConfig(bubble_enter=1.0, bubble_exit=1.2)
    with pytest.raises(ValueError, match="patience"):
        AdaptConfig(patience=0.5)
    with pytest.raises(ValueError, match="min_gain"):
        AdaptConfig(min_gain=1.0)
    with pytest.raises(ValueError, match="bucketed_weight"):
        AdaptConfig(bucketed_weight=0.0)
    with pytest.raises(ValueError, match="ewma"):
        AdaptConfig(ewma=0.0)


def test_hysteresis_no_flap_crossing_exit():
    """A bubble ratio oscillating ACROSS the exit band never accumulates
    patience: each dip below exit disarms and resets the counter."""
    p = ReplanPolicy(_cfg(patience=2, cooldown=0))
    for step in range(40):
        ratio = 1.6 if step % 2 == 0 else 1.1   # 1.1 <= exit (1.2)
        assert p.observe(step, None, bubble_ratio=ratio) is None
    assert p.triggers == 0


def test_hysteresis_holds_armed_inside_band():
    """Oscillating INSIDE the band (below enter, above exit) keeps the
    signal armed — one clean trigger, then cooldown silence; no flapping
    (trigger spacing always > cooldown)."""
    p = ReplanPolicy(_cfg(patience=3, cooldown=10))
    fired = []
    for step in range(30):
        ratio = 1.6 if step % 2 == 0 else 1.4   # 1.4 > exit, < enter
        if p.observe(step, None, bubble_ratio=ratio) is not None:
            fired.append(step)
    assert fired and fired[0] == 2          # armed at 0, patience 3 at 2
    assert all(b - a > 10 for a, b in zip(fired, fired[1:]))
    assert len(fired) <= 3


def test_cooldown_respected_under_sustained_signal():
    p = ReplanPolicy(_cfg(patience=2, cooldown=6))
    fired = [step for step in range(30)
             if p.observe(step, None, bubble_ratio=5.0) is not None]
    assert fired[0] == 1
    # after a trigger: 6 observed steps of cooldown, then re-arm (1 obs)
    # and re-accumulate patience (1 more) => spacing exactly 8
    assert all(b - a == 8 for a, b in zip(fired, fired[1:]))


def test_straggler_trigger_names_stage_and_factor():
    p = ReplanPolicy(_cfg(patience=2, baseline_steps=2, ewma=1.0))
    assert p.observe(0, [1.0, 1.0]) is None      # baseline sample 1
    assert p.observe(1, [1.0, 1.0]) is None      # baseline formed
    assert p.observe(2, [1.0, 4.0]) is None      # armed
    d = p.observe(3, [1.0, 4.0])                 # patience crossed
    assert d is not None and d.action == "replan-straggler"
    assert d.stage == 1
    assert d.factor == pytest.approx(4.0)
    assert p.cooling


def test_bucketed_observations_earn_less_patience():
    """Timer-mode (bucketed) telemetry counts bucketed_weight toward
    patience: with weight 0.5 and patience 2, the trigger needs 4 armed
    observations instead of 2."""
    exact = ReplanPolicy(_cfg(patience=2, bucketed_weight=0.5))
    bucketed = ReplanPolicy(_cfg(patience=2, bucketed_weight=0.5))
    for step in range(2):
        exact.observe(step, [1.0, 1.0])
        bucketed.observe(step, [1.0, 1.0], provenance="bucketed")
    exact_steps = bucketed_steps = None
    for k in range(10):
        if exact_steps is None and \
                exact.observe(2 + k, [1.0, 4.0]) is not None:
            exact_steps = k + 1
        if bucketed_steps is None and \
                bucketed.observe(2 + k, [1.0, 4.0],
                                 provenance="bucketed") is not None:
            bucketed_steps = k + 1
    assert exact_steps == 2
    assert bucketed_steps == 4


def test_stage_count_change_reforms_baseline():
    p = ReplanPolicy(_cfg(patience=2, baseline_steps=2))
    p.observe(0, [1.0, 1.0])
    p.observe(1, [1.0, 1.0])
    # plan changed: 3 stages now — must not index the stale baseline
    assert p.observe(2, [1.0, 1.0, 1.0]) is None
    assert p.observe(3, [1.0, 1.0, 1.0]) is None
    assert p.observe(4, [1.0, 1.0, 9.0]) is None
    assert p.observe(5, [1.0, 1.0, 9.0]).stage == 2


def test_min_gain_gate():
    p = ReplanPolicy(_cfg(min_gain=0.05))
    assert not p.gain_ok(types.SimpleNamespace(expected_gain=0.01))
    assert p.gain_ok(types.SimpleNamespace(expected_gain=0.2))
    # no scored incumbent (fresh search / node loss): nothing to stay on
    assert p.gain_ok(types.SimpleNamespace(expected_gain=None))


# ------------------------------------------------- planner expected gain ---
def _two_island_cluster():
    return C.ClusterSpec(groups=(C.NodeGroup(C.AMD, 1, accel_per_node=1),
                                 C.NodeGroup(C.GPU_A, 1, accel_per_node=1)))


SEARCH_KW = dict(global_batch=8, seq_len=32, pp_options=[2],
                 tp_options=[1], micro_bs_options=[2], require_fit=False,
                 include_tp_comm=False, schedule="1f1b",
                 explore_orders=False)


def test_planner_surfaces_expected_gain():
    from repro.configs.llama3_8b import CONFIG
    cfg = dataclasses.replace(CONFIG, num_layers=6)
    cl = _two_island_cluster()
    base = planner.search(cl, cfg, **SEARCH_KW)
    assert base.baseline_time is None and base.expected_gain is None
    res = planner.search(cl.degrade("gpu-a", 4.0), cfg,
                         baseline_plan=base.plan, **SEARCH_KW)
    assert res.baseline_time == \
        dict(res.log)[f"baseline {base.plan.describe()}"]
    assert res.expected_gain == pytest.approx(
        1.0 - res.prediction.iter_time / res.baseline_time)
    # the winner is never predicted worse than the scored incumbent
    assert res.expected_gain >= 0.0


# ----------------------------------------------- aggregation (multi-host) --
def test_store_merge_equals_single_store_folds():
    """Fold-merge is exact: N per-process stores merged == every
    observation folded into one store (n-weighted means compose)."""
    shape = {"arch": "m", "stage": 0}
    obs = [1.0, 3.0, 5.0, 7.0, 11.0]
    one = ProfileStore()
    a, b = ProfileStore(), ProfileStore()
    for i, v in enumerate(obs):
        one.fold("amd", "observed_stage_tick", shape, "tick_s", v)
        (a if i % 2 == 0 else b).fold("amd", "observed_stage_tick",
                                      shape, "tick_s", v)
    merged = merge_stores([a, b])
    e, ref = merged.get("amd", "observed_stage_tick", shape), \
        one.get("amd", "observed_stage_tick", shape)
    assert e.value["n"] == ref.value["n"]
    assert e.value["tick_s"] == pytest.approx(ref.value["tick_s"])


def test_inmemory_fanin_builds_per_island_view():
    """Two simulated processes on different islands: the fan-in yields ONE
    store holding both device kinds — what the policy and the replan
    search must see — and gathering twice is idempotent."""
    tick = {"arch": "m", "seq_len": 32, "tp": 1, "schedule": "1f1b",
            "pp": 2, "vpp": 1, "layers": 3, "padded_layers": 3,
            "micro_bs": 2}
    bub = {"arch": "m", "schedule": "1f1b", "pp": 2, "vpp": 1, "m": 4}
    proc0, proc1 = ProfileStore(), ProfileStore()
    for _ in range(3):
        proc0.fold("amd", "observed_stage_tick", {**tick, "stage": 0},
                   "tick_s", 0.3)
        proc0.fold("amd", "observed_bubble", bub, "bubble_frac", 0.2)
        proc1.fold("gpu-a", "observed_stage_tick", {**tick, "stage": 1},
                   "tick_s", 0.9)
        proc1.fold("gpu-a", "observed_bubble", bub, "bubble_frac", 0.25)
    agg = InMemoryFanIn([proc1])
    merged = agg.gather(proc0)
    kinds = {e.device_kind for e in merged.entries(op="observed_stage_tick")}
    assert kinds == {"amd", "gpu-a"}
    cfg = types.SimpleNamespace(name="m")
    pcm = ProfiledCostModel(merged)
    assert pcm.stage_tick_per_layer("amd", cfg, 32, 1) == \
        pytest.approx(0.3 / (3 * 2))
    assert pcm.stage_tick_per_layer("gpu-a", cfg, 32, 1) == \
        pytest.approx(0.9 / (3 * 2))
    again = agg.gather(proc0)
    for e in merged.entries():
        assert again.get(e.device_kind, e.op, e.shape).value == e.value
    # the per-process stores were not mutated by the gather
    assert len(proc0.entries()) == 2 and len(proc1.entries()) == 2


def test_allgather_wire_format_roundtrip():
    """The allgather aggregator's payload encode/merge path, exercised
    without a multi-process runtime: a remote store's observed entries
    survive the JSON wire format and fold-merge exactly."""
    local, remote = ProfileStore(), ProfileStore()
    shape = {"arch": "m", "stage": 0}
    local.fold("amd", "observed_stage_tick", shape, "tick_s", 1.0)
    remote.fold("gpu-a", "observed_stage_tick", {**shape, "stage": 1},
                "tick_s", 2.0)
    remote.fold("amd", "observed_stage_tick", shape, "tick_s", 3.0)
    # calibration entries stay host-local: never shipped
    remote.put("hlo", "layer_cost", {"arch": "m", "seq_len": 32},
               {"flops_fwd": 1e9})
    agg = ProcessAllGatherAggregator()
    merged = agg._merge_payloads(local, [agg._encode(remote)])
    assert merged.get("amd", "observed_stage_tick", shape).value == \
        {"tick_s": 2.0, "n": 2.0}
    assert merged.get("gpu-a", "observed_stage_tick",
                      {**shape, "stage": 1}).value["tick_s"] == 2.0
    assert merged.get("hlo", "layer_cost",
                      {"arch": "m", "seq_len": 32}) is None
    # single-process gather is the identity (no copy, no network)
    assert agg.gather(local) is local
    assert isinstance(default_aggregator(), LocalAggregator)


# --------------------------------------------------- provenance (fix) ------
def _feed_ticks(tele, durs):
    """Replay one step's tick marks with a controlled clock."""
    from repro.telemetry import recorder as rec
    clock = {"t": 100.0}
    orig = rec.time
    rec.time = types.SimpleNamespace(perf_counter=lambda: clock["t"])
    try:
        tele.on_tick(0)
        for t in range(1, tele.n_ticks + 1):
            clock["t"] += durs[t - 1]
            tele.on_tick(t)
    finally:
        rec.time = orig


def _fold_kw(**kw):
    base = dict(arch="m", seq_len=32, tp=1, schedule="1f1b",
                layers_per_vstage=[3, 3], padded_per_stage=[3, 3],
                micro_bs_per_stage=[2, 2])
    base.update(kw)
    return base


def test_timer_folds_marked_bucketed_callback_exact():
    st = ProfileStore()
    timer = StageTelemetry(pp=2, vpp=1, m=4, mode="timer", drop_first=False)
    timer.observe_step(0.9)
    timer.fold_into(st, ["cpu", "cpu"], **_fold_kw())
    cb = StageTelemetry(pp=2, vpp=1, m=4, mode="callback", drop_first=False)
    _feed_ticks(cb, [0.5] * (cb.n_ticks + 1))
    cb.fold_into(st, ["amd", "amd"], **_fold_kw())
    for e in st.entries("cpu"):
        assert e.meta["provenance"] == "bucketed"
    for e in st.entries("amd"):
        assert e.meta["provenance"] == "exact"


def test_bucketed_entries_downweighted_in_cost_model():
    """An exact callback observation must dominate a bucketed timer fold
    of the same (kind, arch, seq_len, tp): the serving mean weights
    bucketed entries by BUCKETED_WEIGHT."""
    st = ProfileStore()
    shape = dict(arch="m", seq_len=32, tp=1, schedule="1f1b", pp=2, vpp=1,
                 layers=2, padded_layers=2, micro_bs=1)
    st.fold("cpu", "observed_stage_tick", {**shape, "stage": 0},
            "tick_s", 2.0)                      # exact: 1.0 per layer-seq
    e = st.fold("cpu", "observed_stage_tick", {**shape, "stage": 1},
                "tick_s", 20.0)                 # bucketed: 10.0
    e.meta["provenance"] = "bucketed"
    got = ProfiledCostModel(st).stage_tick_per_layer(
        "cpu", types.SimpleNamespace(name="m"), 32, 1)
    want = (1.0 * 1.0 + BUCKETED_WEIGHT * 10.0) / (1.0 + BUCKETED_WEIGHT)
    assert got == pytest.approx(want)
    # merge keeps the LESS trusted provenance on collision
    other = ProfileStore()
    other.fold("cpu", "observed_stage_tick", {**shape, "stage": 0},
               "tick_s", 2.0).meta["provenance"] = "bucketed"
    merged = merge_stores([st, other])
    assert merged.get("cpu", "observed_stage_tick",
                      {**shape, "stage": 0}).meta["provenance"] == "bucketed"


def test_fold_into_stage_scale_injects_skew():
    st = ProfileStore()
    tele = StageTelemetry(pp=2, vpp=1, m=4, mode="callback",
                          drop_first=False)
    _feed_ticks(tele, [0.5] * (tele.n_ticks + 1))
    tele.fold_into(st, ["cpu", "cpu"], **_fold_kw(),
                   stage_scale=[1.0, 3.0])
    def tick(stage, layers):
        return st.get("cpu", "observed_stage_tick",
                      dict(arch="m", seq_len=32, tp=1, schedule="1f1b",
                           stage=stage, pp=2, vpp=1, layers=layers,
                           padded_layers=3, micro_bs=2)).value["tick_s"]
    assert tick(1, 3) == pytest.approx(3.0 * tick(0, 3))


# --------------------------------------------- e2e: the autonomous loop ----
ADAPT_SEARCH_KW = {k: v for k, v in SEARCH_KW.items()
                   if k not in ("global_batch", "seq_len")}


def _mk_trainer(tmp, policy=None, aggregator=None):
    mesh = make_mesh((1, 1), ("data", "model"))
    bundle = registry.get_bundle("llama3-8b", smoke=True, num_layers=6)
    cl = _two_island_cluster()
    plan = ParallelPlan(stages=(StagePlacement(0, 3, 1, 1, False),
                                StagePlacement(1, 3, 1, 1, True)),
                        micro_bs=2, global_batch=8, seq_len=32)
    t = Trainer(bundle, mesh,
                TrainerConfig(global_batch=8, seq_len=32,
                              ckpt_dir=str(Path(tmp) / "ckpt"),
                              ckpt_every=100, replan_profile_min_obs=4),
                cluster=cl, plan=plan, profile_store=ProfileStore(),
                policy=policy, aggregator=aggregator,
                adapt_search_kw=ADAPT_SEARCH_KW)
    return t


@pytest.fixture(scope="module")
def auto_e2e():
    """The acceptance scenario: healthy steps -> injected degrade ->
    the controller detects, replans and live-migrates with NO caller
    intervention."""
    tmp = tempfile.mkdtemp()
    policy = ReplanPolicy(_cfg(patience=2, cooldown=4, baseline_steps=2,
                               ewma=1.0, min_gain=0.0))
    t = _mk_trainer(tmp, policy=policy)
    r1 = t.run(4)
    t.inject_degrade("gpu-a", 8.0)
    r2 = t.run(6)
    return dict(trainer=t, policy=policy, r1=r1, r2=r2,
                state=jax.device_get(t.state), total=10)


def test_e2e_controller_replans_and_migrates_itself(auto_e2e):
    t = auto_e2e["trainer"]
    assert t.replans == 1
    assert t.migrations["memory"] == 1
    actions = [e.action for e in t.adapt_log]
    assert actions.count("trigger") == 1
    assert actions.count("migrate") == 1
    assert "skip" not in actions
    trig = next(e for e in t.adapt_log if e.action == "trigger")
    assert trig.detail["stage"] == 1              # gpu-a hosts stage 1
    assert trig.detail["factor"] >= 2.0           # sustained well past enter
    rep = next(e for e in t.adapt_log if e.action == "replan")
    assert rep.detail["expected_gain"] > 0.0
    assert rep.detail["baseline_time"] > rep.detail["iter_time"]
    # the new plan moved layers off the degraded island
    deg = sum(st.n_layers for st in t.plan.stages
              if t.cluster.groups[st.group].device.name == "gpu-a")
    assert deg < 3
    assert all(np.isfinite(v) for v in auto_e2e["r2"]["losses"])
    # structured log serializes (the operator artifact)
    assert "expected_gain" in events_json(t.adapt_log)


def test_e2e_autonomous_bit_exact_vs_manual_path(auto_e2e):
    """The controller's degrade->replan->migrate produces the SAME final
    train state, bit for bit, as the PR-4 manual path driven with the
    controller's own decisions (same trigger step, same estimated
    factor)."""
    t = auto_e2e["trainer"]
    trig = next(e for e in t.adapt_log if e.action == "trigger")
    tmp = tempfile.mkdtemp()
    m = _mk_trainer(tmp)                          # no policy: manual
    m.run(4)
    m.inject_degrade("gpu-a", 8.0)                # identical telemetry skew
    m.run(trig.step - 4)                          # up to the trigger step
    res = m.replan(m.cluster.degrade("gpu-a", trig.detail["factor"]),
                   global_batch=8, seq_len=32, migrate="memory",
                   **ADAPT_SEARCH_KW)
    assert res.plan == t.plan                     # same decision...
    m.run(auto_e2e["total"] - trig.step)
    assert m.step == t.step
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)),
        auto_e2e["state"], jax.device_get(m.state))   # ...same state, bitwise


def test_e2e_min_gain_gate_blocks_migration(tmp_path):
    """Acceptance: the policy never migrates when the predicted gain is
    below ε — the search runs, the gate rejects, the state stays put."""
    policy = ReplanPolicy(_cfg(patience=2, cooldown=4, baseline_steps=2,
                               ewma=1.0, min_gain=0.95))
    t = _mk_trainer(tmp_path, policy=policy)
    t.run(4)
    t.inject_degrade("gpu-a", 8.0)
    t.run(5)
    actions = [e.action for e in t.adapt_log]
    assert "trigger" in actions and "skip" in actions
    assert "migrate" not in actions
    assert t.replans == 0 and t.migrations["memory"] == 0
    skip = next(e for e in t.adapt_log if e.action == "skip")
    assert skip.detail["expected_gain"] < 0.95
    assert t.plan.layers == (3, 3)                # incumbent untouched


def test_e2e_link_degrade_triggers_replan_schedule(tmp_path):
    """A slowed inter-island boundary link stretches only the pipeline's
    idle ticks: stage compute stays healthy, so the STRAGGLER signal must
    stay quiet and the bubble ratio is what departs from prediction — the
    policy's decision is ``replan-schedule``, the re-search runs on the
    UNCHANGED cluster (no device kind degraded), and training continues
    with finite loss."""
    policy = ReplanPolicy(_cfg(patience=2, cooldown=4, baseline_steps=2,
                               ewma=1.0, min_gain=0.0))
    t = _mk_trainer(tmp_path, policy=policy)
    t.run(4)
    healthy = {g.device.name: g.device.effective_tflops
               for g in t.cluster.groups}
    # the natural CPU-mesh bubble ratio varies with machine load: derive
    # the injection factor from the measured baseline so the slowed link
    # lands a deterministic 8x-enter excess (injection composes
    # multiplicatively on the observed bubble)
    h0 = t.schedule_health()
    assert h0 is not None and h0["ratio"] > 0.0
    t.inject_link_degrade(8.0 * policy.cfg.bubble_enter / h0["ratio"])
    health = t.schedule_health()
    assert health is not None and health["ratio"] > policy.cfg.bubble_enter
    r = t.run(6)
    trigs = [e for e in t.adapt_log if e.action == "trigger"]
    assert trigs and trigs[0].detail["action"] == "replan-schedule"
    assert all(e.detail["action"] == "replan-schedule" for e in trigs)
    assert "stage" not in trigs[0].detail         # no straggler blamed
    assert trigs[0].detail["signal"] >= policy.cfg.bubble_enter
    # the wrong-schedule path re-scores against the SAME cluster: no
    # device kind was degraded by the adoption
    rep = next(e for e in t.adapt_log if e.action == "replan")
    assert rep is not None                        # the search actually ran
    assert {g.device.name: g.device.effective_tflops
            for g in t.cluster.groups} == healthy
    assert all(np.isfinite(v) for v in r["losses"])


def test_e2e_cp_ring_link_degrade_triggers_replan_schedule(tmp_path):
    """cp composed with pp (carried-forward "schedule replans in anger"):
    under a pp>1 plan the cp ring is an advisory pricing dimension — the
    pipeline still executes, and a slowed pod link stretches ring hops
    and boundary sends alike while stage compute stays healthy.  The
    policy must fire ``replan-schedule`` (no straggler blamed) and the
    re-search must sweep ``cp_options`` on the UNCHANGED cluster."""
    from repro.core import segmentation
    mesh = make_mesh((1, 1), ("data", "model"))
    bundle = registry.get_bundle("llama3-8b", smoke=True, num_layers=6)
    # two accelerators per island so every stage has dp=2 (cp=2 | dp)
    cl = C.ClusterSpec(groups=(C.NodeGroup(C.AMD, 1, accel_per_node=2),
                               C.NodeGroup(C.GPU_A, 1, accel_per_node=2)))
    chunks = tuple(segmentation.cp_split(32, 2, attn=0.5 / 32, lin=0.5))
    assert chunks[0] > chunks[1]            # causal triangle: ragged ring
    plan = ParallelPlan(stages=(StagePlacement(0, 3, 2, 1, False),
                                StagePlacement(1, 3, 2, 1, True)),
                        micro_bs=2, global_batch=8, seq_len=32,
                        cp=2, cp_chunks=chunks)
    policy = ReplanPolicy(_cfg(patience=2, cooldown=4, baseline_steps=2,
                               ewma=1.0, min_gain=0.0))
    kw = dict(ADAPT_SEARCH_KW, cp_options=(1, 2))
    t = Trainer(bundle, mesh,
                TrainerConfig(global_batch=8, seq_len=32,
                              ckpt_dir=str(tmp_path / "ckpt"),
                              ckpt_every=100, replan_profile_min_obs=4),
                cluster=cl, plan=plan, profile_store=ProfileStore(),
                policy=policy, adapt_search_kw=kw)
    assert t._pipeline_active() and not t._cp_active()
    t.run(4)
    h0 = t.schedule_health()
    assert h0 is not None and h0["ratio"] > 0.0
    t.inject_link_degrade(8.0 * policy.cfg.bubble_enter / h0["ratio"])
    r = t.run(6)
    trigs = [e for e in t.adapt_log if e.action == "trigger"]
    assert trigs and trigs[0].detail["action"] == "replan-schedule"
    assert "stage" not in trigs[0].detail         # no straggler blamed
    rep = next(e for e in t.adapt_log if e.action == "replan")
    assert rep is not None                        # cp-aware search ran
    assert all(np.isfinite(v) for v in r["losses"])


def test_planner_infeasible_incumbent_records_no_baseline():
    """An incumbent that fails require_fit is scored for the log but must
    NOT become the expected-gain baseline: gain_ok's "no scored incumbent
    -> pass" rule applies, so the controller can always migrate OFF a
    plan the planner itself considers infeasible."""
    from repro.configs.llama3_8b import CONFIG
    from repro.core.predictor import PerformancePredictor
    cfg = dataclasses.replace(CONFIG, num_layers=6)
    bad = ParallelPlan(stages=(StagePlacement(0, 5, 1, 1, False),
                               StagePlacement(1, 1, 1, 1, True)),
                       micro_bs=2, global_batch=8, seq_len=32)
    good = ParallelPlan(stages=(StagePlacement(0, 3, 1, 1, False),
                                StagePlacement(1, 3, 1, 1, True)),
                        micro_bs=2, global_batch=8, seq_len=32)
    pred = PerformancePredictor(_two_island_cluster(), cfg,
                                include_tp_comm=False)
    mem_bad = max(pred.predict(bad).peak_mem_gb)
    mem_ok = max(pred.predict(good).peak_mem_gb)
    assert mem_bad > mem_ok
    # HBM between the two: the lopsided incumbent no longer fits, a
    # balanced split does
    hbm = (mem_bad + mem_ok) / 2.0
    cl = C.ClusterSpec(groups=(
        C.NodeGroup(dataclasses.replace(C.AMD, hbm_gb=hbm), 1,
                    accel_per_node=1),
        C.NodeGroup(dataclasses.replace(C.GPU_A, hbm_gb=hbm), 1,
                    accel_per_node=1)))
    kw = dict(SEARCH_KW)
    kw["require_fit"] = True
    res = planner.search(cl, cfg, baseline_plan=bad, **kw)
    assert res.prediction.fits
    assert res.baseline_time is None and res.expected_gain is None
    assert ReplanPolicy().gain_ok(res)       # nothing to stay put on
    # the infeasible incumbent was still scored into the search log
    assert any(d.startswith("baseline ") for d, _ in res.log)


def test_plan_dict_roundtrip():
    """The adaptation directive ships the searched plan as JSON across
    processes: to_dict -> (wire) -> from_dict must be ``==``-exact,
    chunk-pinned interleaved plans included."""
    plans = [
        ParallelPlan(stages=(StagePlacement(0, 3, 1, 1, False),
                             StagePlacement(1, 3, 2, 1, True)),
                     micro_bs=2, global_batch=8, seq_len=32),
        ParallelPlan(stages=(StagePlacement(1, 5, 1, 1, False),
                             StagePlacement(0, 3, 1, 1, True)),
                     micro_bs=1, global_batch=8, seq_len=64,
                     schedule="interleaved-1f1b", vpp=2,
                     chunk_layers=(2, 1, 3, 2)),
        ParallelPlan(stages=(StagePlacement(0, 3, 2, 1, False),
                             StagePlacement(1, 3, 2, 1, True)),
                     micro_bs=2, global_batch=8, seq_len=32,
                     cp=2, cp_chunks=(20, 12)),
    ]
    for p in plans:
        wired = json.loads(json.dumps(p.to_dict()))
        assert ParallelPlan.from_dict(wired) == p


# --------------------------- degradation projection (no double count) ------
def test_degrade_projection_not_double_counted():
    """Folds taken under a degradation carry their ``obs_scale``; the cost
    model serves the REFERENCE-HEALTHY time (tick mean / obs_scale mean —
    exact under mixed healthy+degraded folds) and ``time_scale`` then
    applies the target slowdown exactly once, never factor^2."""
    st = ProfileStore()
    shape = dict(arch="m", seq_len=32, tp=1, schedule="1f1b", stage=1,
                 pp=2, vpp=1, layers=3, padded_layers=3, micro_bs=2)
    cfg = types.SimpleNamespace(name="m")
    for _ in range(3):       # healthy folds: 0.6s per 3-layer 2-seq tick
        st.fold("cpu", "observed_stage_tick", shape, "tick_s", 0.6,
                also={"obs_scale": 1.0})
    for _ in range(5):       # folded while the kind ran 8x slow
        st.fold("cpu", "observed_stage_tick", shape, "tick_s", 8 * 0.6,
                also={"obs_scale": 8.0})
    healthy = ProfiledCostModel(st).stage_tick_per_layer("cpu", cfg, 32, 1)
    assert healthy == pytest.approx(0.6 / (3 * 2))
    pcm = ProfiledCostModel(st, device_map={"gpu-x": "cpu"},
                            time_scale={"gpu-x": 8.0})
    fwd, bwd = pcm.layer_time("gpu-x", cfg, 32, micro_bs=2, tp=1)
    assert fwd == pytest.approx(8.0 * 0.6 / 3)       # 8x once, not 64x
    assert bwd == pytest.approx(2.0 * fwd)
    # obs_scale survives the multi-host fold-merge (same n-weighting)
    merged = merge_stores([st, ProfileStore()])
    e = merged.get("cpu", "observed_stage_tick", shape)
    assert e.value["tick_s"] / e.value["obs_scale"] == pytest.approx(0.6)


def test_legacy_entries_not_retagged_by_obs_scale_folds():
    """Folding a tagged observation into a pre-obs_scale legacy entry must
    back-fill the missing history at NEUTRAL (1.0) — not retroactively
    attribute the new scale to all prior observations, which would serve
    a 'reference-healthy' time far below anything ever measured."""
    st = ProfileStore()
    shape = {"arch": "m", "seq_len": 32, "tp": 1, "schedule": "1f1b",
             "stage": 0, "pp": 2, "vpp": 1, "layers": 1,
             "padded_layers": 1, "micro_bs": 1}
    # legacy: 100 healthy observations with no obs_scale field
    st.put("cpu", "observed_stage_tick", shape,
           {"tick_s": 0.6, "n": 100.0})
    st.fold("cpu", "observed_stage_tick", shape, "tick_s", 8 * 0.6,
            also={"obs_scale": 8.0})
    e = st.get("cpu", "observed_stage_tick", shape)
    assert e.value["obs_scale"] == pytest.approx((100 * 1.0 + 8.0) / 101)
    served = ProfiledCostModel(st).stage_tick_per_layer(
        "cpu", types.SimpleNamespace(name="m"), 32, 1)
    assert served == pytest.approx(0.6, rel=0.05)   # not 0.6/8
    # an untagged fold into a tagged entry counts at neutral too (the
    # observation must not inherit the entry's scale)
    st.fold("cpu", "observed_stage_tick", shape, "tick_s", 0.6)
    e = st.get("cpu", "observed_stage_tick", shape)
    assert e.value["obs_scale"] == \
        pytest.approx((100 * 1.0 + 8.0 + 1.0) / 102)
    # merge has the same rule IN BOTH ORDERS: whichever side's history
    # predates the field counts at neutral, never at the other's scale —
    # which also keeps the fold-merge order-independent
    def mk_tagged():
        s = ProfileStore()
        s.fold("cpu", "observed_stage_tick", shape, "tick_s", 8 * 0.6,
               also={"obs_scale": 8.0})
        return s

    def mk_legacy():
        s = ProfileStore()
        s.put("cpu", "observed_stage_tick", shape,
              {"tick_s": 0.6, "n": 100.0})
        return s

    want = (100 * 1.0 + 8.0) / 101
    for stores in ([mk_legacy(), mk_tagged()], [mk_tagged(), mk_legacy()]):
        m = merge_stores(stores).get("cpu", "observed_stage_tick", shape)
        assert m.value["obs_scale"] == pytest.approx(want)
        assert m.value["n"] == 101.0


def test_degrade_flag_validation():
    """--degrade rejects malformed specs at the flag with the expected
    shape, instead of a bare ValueError mid-run."""
    from repro.launch.train import degrade_spec
    assert degrade_spec("gpu-a:8") == ("gpu-a", 8.0, None)
    assert degrade_spec("gpu-a:2.5@6") == ("gpu-a", 2.5, 6)
    for bad in ("gpu-a", "gpu-a:", ":8", "gpu-a:x", "gpu-a:8@x",
                "gpu-a:0", "gpu-a:-2", "gpu-a:nan", "gpu-a:inf",
                "gpu-a:8@-3"):
        with pytest.raises(argparse.ArgumentTypeError):
            degrade_spec(bad)


def test_trainer_cost_source_reads_aggregated_view(tmp_path):
    """With an aggregator attached, the replan cost source opens its
    density gate on the CLUSTER-wide observation count — remote folds
    from peer processes included — not this process's 1/N view."""
    bundle = registry.get_bundle("llama3-8b", smoke=True, num_layers=2)
    remote = ProfileStore()
    for _ in range(8):
        remote.fold("cpu", "observed_layer_step",
                    {"arch": bundle.cfg.name, "seq_len": 32, "tp": 1},
                    "per_seq_s", 0.01)
    mesh = make_mesh((1, 1), ("data", "model"))
    cl = _two_island_cluster()
    t = Trainer(bundle, mesh,
                TrainerConfig(global_batch=8, seq_len=32,
                              ckpt_dir=str(tmp_path / "ckpt"),
                              replan_profile_min_obs=4),
                cluster=cl, profile_store=ProfileStore(),
                aggregator=InMemoryFanIn([remote]))
    src = t.profiled_cost_source(cl)
    assert isinstance(src, ProfiledCostModel)     # gate opened by peers
    t.aggregator = None
    assert t.profiled_cost_source(cl) is None     # 1/N view: too sparse


# ----------------------- cluster-symmetric decision (leader + broadcast) ---
class _ScriptedAggregator:
    """Collective-aggregator stand-in runnable in ONE process: gather is
    the identity, and ``broadcast`` records the directive stream (leader)
    or replays a recorded one (follower) — what
    ``ProcessAllGatherAggregator`` does over the wire, minus the wire."""
    collective = True

    def __init__(self, leader=True, replay=None):
        self.leader = leader
        self.sent = []                   # leader: one entry per broadcast
        self.replay = list(replay or [])

    def gather(self, local):
        return local

    def is_leader(self):
        return self.leader

    def broadcast(self, obj):
        if self.leader:
            self.sent.append(obj)
            return obj
        assert obj is None               # a follower never decides
        return self.replay.pop(0) if self.replay else None


def test_decision_is_cluster_symmetric_via_broadcast():
    """The adaptation decision must never be gated on per-process policy
    state: the LEADER decides (from the gathered cluster view) and its
    directive is broadcast, so a process that observed nothing anomalous
    locally still enters the collective adoption at the same step — same
    plan, same degraded cluster, bit-exact final state."""
    # leader: sees the injected telemetry skew, decides, broadcasts
    policy = ReplanPolicy(_cfg(patience=2, cooldown=4, baseline_steps=2,
                               ewma=1.0, min_gain=0.0))
    lead_agg = _ScriptedAggregator(leader=True)
    t = _mk_trainer(tempfile.mkdtemp(), policy=policy, aggregator=lead_agg)
    t.run(4)
    t.inject_degrade("gpu-a", 8.0)
    t.run(6)
    assert t.replans == 1 and t.migrations["memory"] == 1
    directives = [d for d in lead_agg.sent if d is not None]
    assert len(directives) == 1
    assert directives[0]["kind"] == "gpu-a"
    # every _maybe_adapt pass broadcast (None included): the collective
    # is entered unconditionally, never gated on policy state
    assert len(lead_agg.sent) == 10
    # follower: NO local anomaly (no injection), policy never consulted —
    # it replays the leader's directive stream (JSON round-tripped, as
    # the wire would deliver it) at the same per-step cadence
    follow_agg = _ScriptedAggregator(
        leader=False, replay=json.loads(json.dumps(lead_agg.sent)))
    m = _mk_trainer(tempfile.mkdtemp(),
                    policy=ReplanPolicy(_cfg(patience=2, cooldown=4,
                                             baseline_steps=2, ewma=1.0,
                                             min_gain=0.0)),
                    aggregator=follow_agg)
    m.run(10)
    assert not follow_agg.replay                  # consumed in lockstep
    assert m.replans == 1 and m.migrations["memory"] == 1
    assert m.plan == t.plan                       # identical adoption...
    assert [e.action for e in m.adapt_log] == ["migrate"]
    sc = {g.device.name: g.device.effective_tflops
          for g in m.cluster.groups}
    assert sc == {g.device.name: g.device.effective_tflops
                  for g in t.cluster.groups}      # ...identical cluster...
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)),
        jax.device_get(t.state), jax.device_get(m.state))  # ...same state
    # the leader's reference-based projection: after adopting the
    # degraded cluster the served-time scale is still the FULL factor vs
    # the healthy reference, not 1.0 vs the already-degraded incumbent
    trig = next(e for e in t.adapt_log if e.action == "trigger")
    assert t._degrade_scales(t.cluster)["gpu-a"] == \
        pytest.approx(trig.detail["factor"])
    # and the folds carry their observation-time health tag
    assert any(e.value.get("obs_scale", 1.0) > 1.0
               for e in t.profile_store.entries(op="observed_stage_tick"))
