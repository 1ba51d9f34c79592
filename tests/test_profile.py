"""Profiling & calibration subsystem: store round-trip, interpolation,
analytic-vs-profiled predictor parity, planner on a measured profile, and
the online refinement hook."""
import json
import tempfile
from pathlib import Path

import pytest

from repro.configs.llama2_paper import LLAMA2_70B
from repro.core import cluster as C
from repro.core import costmodel, planner, segmentation
from repro.core.plan import ParallelPlan, StagePlacement
from repro.core.predictor import PerformancePredictor
from repro.launch.mesh import make_mesh
from repro.profile.model import CALIB_DEVICE, ProfiledCostModel
from repro.profile.store import ProfileStore


# ------------------------------------------------------------------ store --
def test_store_roundtrip():
    with tempfile.TemporaryDirectory() as d:
        p = Path(d) / "prof.json"
        st = ProfileStore(p)
        st.put("cpu", "layer_step",
               {"arch": "llama3-8b", "seq_len": 128, "micro_bs": 1, "tp": 1},
               {"fwd_s": 1e-3, "bwd_s": 2e-3})
        st.put("cpu", "link", {"scope": "intra"}, {"gbps": 123.0})
        st.save()
        st2 = ProfileStore.load(p)
        assert len(st2) == 2
        e = st2.get("cpu", "layer_step",
                    {"arch": "llama3-8b", "seq_len": 128, "micro_bs": 1,
                     "tp": 1})
        assert e is not None and e.value["fwd_s"] == 1e-3
        assert e.meta["schema"] == 1                     # provenance kept
        assert st2.get("cpu", "link", {"scope": "intra"}).value["gbps"] == 123.0


def test_store_open_missing_and_newer_schema():
    with tempfile.TemporaryDirectory() as d:
        p = Path(d) / "none.json"
        st = ProfileStore.open(p)        # fresh store, no file yet
        assert len(st) == 0
        p.write_text('{"version": 99, "entries": []}')
        with pytest.raises(ValueError):
            ProfileStore.load(p)


def test_store_fold_running_mean():
    st = ProfileStore()
    shape = {"arch": "m", "seq_len": 64}
    st.fold("cpu", "observed_step", shape, "time_s", 1.0)
    st.fold("cpu", "observed_step", shape, "time_s", 3.0)
    e = st.get("cpu", "observed_step", shape)
    assert abs(e.value["time_s"] - 2.0) < 1e-12
    assert e.value["n"] == 2.0


# -------------------------------------------------------------- interpolate --
def _grid_store():
    st = ProfileStore()
    for seq in (64, 128, 256):
        for mbs in (1, 2, 4):
            st.put("cpu", "layer_step",
                   {"arch": "m", "seq_len": seq, "micro_bs": mbs, "tp": 1},
                   {"fwd_s": 1e-6 * seq * mbs})
    return st


def test_interpolation_exact_and_monotone():
    st = _grid_store()
    # exact grid point
    v = st.interpolate("cpu", "layer_step",
                       {"arch": "m", "seq_len": 128, "micro_bs": 2, "tp": 1},
                       "fwd_s")
    assert abs(v - 1e-6 * 256) < 1e-15
    # between grid points: bounded by neighbours and monotone in seq_len
    prev = 0.0
    for seq in (64, 96, 128, 192, 256):
        v = st.interpolate("cpu", "layer_step",
                           {"arch": "m", "seq_len": seq, "micro_bs": 1,
                            "tp": 1}, "fwd_s")
        assert 1e-6 * 64 <= v <= 1e-6 * 256
        assert v > prev
        prev = v
    # and monotone in micro_bs between grid points
    vals = [st.interpolate("cpu", "layer_step",
                           {"arch": "m", "seq_len": 100, "micro_bs": m,
                            "tp": 1}, "fwd_s") for m in (1, 1.5, 2, 3, 4)]
    assert all(a < b for a, b in zip(vals, vals[1:]))


def test_interpolation_clamps_and_misses():
    st = _grid_store()
    lo = st.interpolate("cpu", "layer_step",
                        {"arch": "m", "seq_len": 16, "micro_bs": 1, "tp": 1},
                        "fwd_s")
    assert abs(lo - 1e-6 * 64) < 1e-15      # clamped, not extrapolated
    assert st.interpolate("cpu", "layer_step",
                          {"arch": "other", "seq_len": 128, "micro_bs": 1,
                           "tp": 1}, "fwd_s") is None
    assert st.interpolate("gpu", "layer_step",
                          {"arch": "m", "seq_len": 128, "micro_bs": 1,
                           "tp": 1}, "fwd_s") is None


# ------------------------------------------------------- satellite fixes ----
def test_transport_validated_everywhere():
    cl = C.paper_cluster_of_size(12)
    with pytest.raises(ValueError, match="transport"):
        cl.link_gbps(0, 1, "ethernet")
    with pytest.raises(ValueError, match="transport"):
        ParallelPlan(stages=(StagePlacement(0, 4, 1, 1, True),),
                     micro_bs=1, global_batch=4, seq_len=64,
                     transport="rdma")
    # cpu staging really is slower than the direct path
    assert cl.link_gbps(0, 1, "cpu") < cl.link_gbps(0, 1, "gpu")


def test_calibrate_clamp_flag():
    analytic = (costmodel.layer_cost(LLAMA2_70B, 4096).flops_fwd
                * LLAMA2_70B.num_layers
                + costmodel.embedding_flops(LLAMA2_70B)) * 3.0
    faster = 0.9 * analytic       # fused kernels beat the analytic count
    assert costmodel.calibrate(LLAMA2_70B, 4096, faster) == 1.0
    got = costmodel.calibrate(LLAMA2_70B, 4096, faster, allow_speedup=True)
    assert abs(got - 0.9) < 1e-9


# ------------------------------------------------------------- predictor ----
def _plan(cl, pp=4, tp=8):
    groups = planner._stage_groups(cl, pp)
    split = segmentation.uniform_split(LLAMA2_70B.num_layers, pp)
    dpg = [cl.groups[g].n_accel // (tp * groups.count(g))
           for g in range(len(cl.groups))]
    stages = tuple(StagePlacement(group=groups[i], n_layers=split[i],
                                  dp=dpg[groups[i]], tp=tp,
                                  is_last=(i == pp - 1))
                   for i in range(pp))
    return ParallelPlan(stages=stages, micro_bs=1, global_batch=96,
                        seq_len=4096)


def test_profiled_matches_analytic_on_synthetic_profile():
    """A profile generated FROM the analytic model must reproduce the
    analytic prediction exactly (the fallback seam introduces no drift)."""
    cl = C.paper_cluster_of_size(12)
    plan = _plan(cl)
    seq = plan.seq_len
    st = ProfileStore()
    lc = costmodel.layer_cost(LLAMA2_70B, seq)
    st.put(CALIB_DEVICE, "layer_cost", {"arch": LLAMA2_70B.name,
                                        "seq_len": seq},
           {"flops_fwd": lc.flops_fwd, "param_bytes": lc.param_bytes,
            "act_bytes_per_token": lc.act_bytes_per_token})
    st.put(CALIB_DEVICE, "embedding_flops", {"arch": LLAMA2_70B.name},
           {"flops": costmodel.embedding_flops(LLAMA2_70B)})
    for gi, g in enumerate(cl.groups):
        st.put(g.device.name, "link", {"scope": "intra"},
               {"gbps": cl.ib_gbps * cl.ib_eff})
        st.put(g.device.name, "link", {"scope": "inter", "transport": "gpu"},
               {"gbps": cl.eth_gbps * cl.eth_eff})
    src = ProfiledCostModel(st)
    p_ana = PerformancePredictor(cl, LLAMA2_70B).predict(plan)
    p_pro = PerformancePredictor(cl, LLAMA2_70B, cost_source=src).predict(plan)
    assert abs(p_ana.iter_time - p_pro.iter_time) < 1e-9
    assert p_ana.peak_mem_gb == p_pro.peak_mem_gb
    assert src.hits > 0                      # the profile actually served


def test_calibration_not_double_applied_with_hlo_flops():
    """When the cost source serves HLO-derived flops (which already embed
    the remat factor), the predictor's scalar calibration knob must not
    multiply them a second time."""
    cl = C.paper_cluster_of_size(12)
    plan = _plan(cl)
    st = ProfileStore()
    lc = costmodel.layer_cost(LLAMA2_70B, plan.seq_len)
    st.put(CALIB_DEVICE, "layer_cost",
           {"arch": LLAMA2_70B.name, "seq_len": plan.seq_len},
           {"flops_fwd": lc.flops_fwd * 1.3})       # measured remat factor
    src = ProfiledCostModel(st)
    assert src.flops_calibrated(LLAMA2_70B, plan.seq_len)
    p1 = PerformancePredictor(cl, LLAMA2_70B, calibration=1.3,
                              cost_source=src).predict(plan)
    p2 = PerformancePredictor(cl, LLAMA2_70B, calibration=1.0,
                              cost_source=src).predict(plan)
    assert abs(p1.iter_time - p2.iter_time) < 1e-12  # knob ignored
    # and the analytic source still honours the knob
    a1 = PerformancePredictor(cl, LLAMA2_70B, calibration=1.3).predict(plan)
    a2 = PerformancePredictor(cl, LLAMA2_70B, calibration=1.0).predict(plan)
    assert a1.iter_time > a2.iter_time


def test_profiled_layer_time_changes_prediction():
    """Measured per-layer wall time overrides the FLOPs/TFLOPs path."""
    cl = C.paper_cluster_of_size(12)
    plan = _plan(cl)
    p_ana = PerformancePredictor(cl, LLAMA2_70B).predict(plan)
    st = ProfileStore()
    for g in cl.groups:
        for mbs in (1, 2, 4, 8, 16):
            st.put(g.device.name, "layer_step",
                   {"arch": LLAMA2_70B.name, "seq_len": plan.seq_len,
                    "micro_bs": mbs, "tp": 8},
                   {"fwd_s": 2e-3 * mbs, "bwd_s": 4e-3 * mbs})
    src = ProfiledCostModel(st)
    p_pro = PerformancePredictor(cl, LLAMA2_70B, cost_source=src).predict(plan)
    assert p_pro.iter_time != p_ana.iter_time
    assert p_pro.iter_time > 0


def test_planner_with_profiled_source():
    """End-to-end: planner searches against a measured profile, via a
    device_map from cluster device names to profiled device kinds (profile
    the sample, predict the cluster)."""
    cl = C.paper_cluster_of_size(12)
    st = ProfileStore()
    for mbs in (1, 2, 4, 8, 16, 32):
        # 'cpu' is the profiled sample device; amd measured 2x faster
        st.put("cpu", "layer_step",
               {"arch": LLAMA2_70B.name, "seq_len": 4096, "micro_bs": mbs,
                "tp": 8}, {"fwd_s": 1e-3 * mbs, "bwd_s": 2e-3 * mbs})
        st.put("cpu-fast", "layer_step",
               {"arch": LLAMA2_70B.name, "seq_len": 4096, "micro_bs": mbs,
                "tp": 8}, {"fwd_s": 0.5e-3 * mbs, "bwd_s": 1e-3 * mbs})
    src = ProfiledCostModel(st, device_map={"amd": "cpu-fast",
                                            "gpu-a": "cpu"})
    res = planner.search(cl, LLAMA2_70B, global_batch=96, seq_len=4096,
                         pp_options=[6], tp_options=[8],
                         micro_bs_options=[1], require_fit=False,
                         cost_source=src)
    assert res.prediction.iter_time > 0
    assert sum(res.plan.layers) == LLAMA2_70B.num_layers
    assert src.hits > 0
    # measured speed asymmetry shows up as non-uniform segmentation is
    # evaluated; the chosen plan must be feasible either way
    assert res.plan.pp == 6


# ------------------------------------------------- online refinement hook --
def test_trainer_folds_observed_steps(tmp_path):
    from repro.models import registry
    from repro.train.trainer import Trainer, TrainerConfig
    mesh = make_mesh((1, 1), ("data", "model"))
    b = registry.get_bundle("llama3-8b", smoke=True)
    store = ProfileStore(tmp_path / "online.json")
    t = Trainer(b, mesh, TrainerConfig(global_batch=4, seq_len=32,
                                       ckpt_dir=str(tmp_path / "ckpt"),
                                       ckpt_every=100),
                profile_store=store)
    t.run(4)
    obs = store.entries(op="observed_step")
    assert len(obs) == 1
    # first (compile) step excluded: 4 steps -> 3 folded observations
    assert obs[0].value["n"] == 3.0
    assert obs[0].value["time_s"] > 0
    assert (tmp_path / "online.json").exists()   # persisted at end of run


# ------------------------------------------------- profile-aware replan ----
def test_replan_uses_profiled_cost_source(tmp_path, monkeypatch):
    """ROADMAP item: once the online profile is dense enough, replan
    searches run against it (ProfiledCostModel) instead of the analytic
    model; an explicit cost_source from the caller always wins."""
    from repro.models import registry
    from repro.profile.runner import device_kind
    from repro.train import trainer as trainer_mod
    from repro.train.trainer import Trainer, TrainerConfig
    mesh = make_mesh((1, 1), ("data", "model"))
    b = registry.get_bundle("llama3-8b", smoke=True)
    store = ProfileStore(tmp_path / "online.json")
    t = Trainer(b, mesh, TrainerConfig(global_batch=4, seq_len=32,
                                       ckpt_dir=str(tmp_path / "ckpt"),
                                       ckpt_every=100,
                                       replan_profile_min_obs=8),
                profile_store=store)
    captured = {}

    def fake_search(cluster, cfg, **kw):
        captured.clear()
        captured.update(kw)

        class R:
            plan = None
        return R()

    monkeypatch.setattr(trainer_mod.planner_mod, "search", fake_search)
    cl = C.paper_cluster_of_size(12)
    # sparse store (below the density threshold): analytic replan
    t.replan(cl, global_batch=96, seq_len=32)
    assert "cost_source" not in captured
    # a dense profile for some OTHER model must not open the gate
    dev = device_kind()
    for _ in range(20):
        store.fold(dev, "observed_layer_step",
                   {"arch": "other-model", "seq_len": 32, "tp": 1},
                   "per_seq_s", 1e-4)
    t.replan(cl, global_batch=96, seq_len=32)
    assert "cost_source" not in captured
    # fold enough observed step times to cross the threshold
    shape = {"arch": b.cfg.name, "seq_len": 32, "tp": 1}
    for _ in range(8):
        store.fold(dev, "observed_layer_step", shape, "per_seq_s",
                   0.12 / (4 * max(b.cfg.num_layers, 1)))
    t.replan(cl, global_batch=96, seq_len=32)
    src = captured.get("cost_source")
    assert isinstance(src, ProfiledCostModel)
    # the observed entries serve layer times for every cluster device name,
    # scaled linearly to the queried microbatch size
    for g in cl.groups:
        lt = src.layer_time(g.device.name, b.cfg, 32, 4, 1)
        assert lt is not None and lt[0] > 0 and lt[1] == pytest.approx(
            2.0 * lt[0])
        lt2 = src.layer_time(g.device.name, b.cfg, 32, 8, 1)
        assert lt2[0] == pytest.approx(2.0 * lt[0])
    # caller-provided cost_source is never overridden
    t.replan(cl, global_batch=96, seq_len=32, cost_source=None)
    assert captured["cost_source"] is None


# -------------------------------------- telemetry store kinds (PR 4) -------
def _tick_shape(stage=0, sched="1f1b", layers=3, padded=3, mbs=2):
    return {"arch": "m", "seq_len": 32, "tp": 1, "schedule": sched,
            "stage": stage, "pp": 2, "vpp": 1, "layers": layers,
            "padded_layers": padded, "micro_bs": mbs}


def test_observed_stage_tick_fold_running_mean():
    """Weighted running-mean math of the telemetry kinds, same contract as
    every other folded entry: value converges to the weighted mean, n
    accumulates the weights."""
    st = ProfileStore()
    sh = _tick_shape()
    st.fold("cpu", "observed_stage_tick", sh, "tick_s", 1.0)
    st.fold("cpu", "observed_stage_tick", sh, "tick_s", 3.0)
    st.fold("cpu", "observed_stage_tick", sh, "tick_s", 8.0, weight=2.0)
    e = st.get("cpu", "observed_stage_tick", sh)
    assert e.value["n"] == 4.0
    assert e.value["tick_s"] == pytest.approx((1.0 + 3.0 + 2 * 8.0) / 4.0)
    bs = {"arch": "m", "schedule": "1f1b", "pp": 2, "vpp": 1, "m": 4}
    st.fold("cpu", "observed_bubble", bs, "bubble_frac", 0.2)
    st.fold("cpu", "observed_bubble", bs, "bubble_frac", 0.4)
    assert st.get("cpu", "observed_bubble", bs).value["bubble_frac"] == \
        pytest.approx(0.3)


def test_observed_kinds_provenance_versioning(tmp_path):
    """Telemetry entries round-trip through the versioned store with their
    provenance (schema version + telemetry mode marker) intact, and a
    newer-schema file still refuses to load."""
    p = tmp_path / "tele.json"
    st = ProfileStore(p)
    e = st.fold("cpu", "observed_stage_tick", _tick_shape(), "tick_s", 1e-3)
    e.meta.update({"telemetry": "callback"})
    st.fold("cpu", "observed_bubble",
            {"arch": "m", "schedule": "1f1b", "pp": 2, "vpp": 1, "m": 4},
            "bubble_frac", 0.25)
    st.save()
    st2 = ProfileStore.load(p)
    e2 = st2.get("cpu", "observed_stage_tick", _tick_shape())
    assert e2.meta["schema"] == 1 and e2.meta["telemetry"] == "callback"
    assert e2.value == pytest.approx(e.value)
    doc = json.loads(p.read_text())
    doc["version"] = 99
    p.write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="newer schema"):
        ProfileStore.load(p)


def test_observed_bubble_interpolation_and_pair_fallback():
    """observed_bubble interpolates over the numeric (pp, vpp, m) axes but
    returns None — analytic fallback — for a (device_kind, schedule) pair
    that was never observed."""
    from repro.models import registry
    cfg = registry.get_config("llama3-8b")
    st = ProfileStore()
    for m in (4, 8):
        st.fold("cpu", "observed_bubble",
                {"arch": cfg.name, "schedule": "1f1b", "pp": 2, "vpp": 1,
                 "m": m}, "bubble_frac", 0.4 if m == 4 else 0.2)
    src = ProfiledCostModel(st)
    assert src.observed_bubble("cpu", cfg, "1f1b", 2, 1, 4) == \
        pytest.approx(0.4)
    assert src.observed_bubble("cpu", cfg, "1f1b", 2, 1, 6) == \
        pytest.approx(0.3)          # interpolated between m=4 and m=8
    assert src.observed_bubble("cpu", cfg, "1f1b", 2, 1, 16) == \
        pytest.approx(0.2)          # clamped, not extrapolated
    # missing (device_kind, schedule) pairs -> None, caller falls back
    assert src.observed_bubble("cpu", cfg, "gpipe", 2, 1, 4) is None
    assert src.observed_bubble("tpu", cfg, "1f1b", 2, 1, 4) is None


def test_stage_tick_serves_layer_time_with_scale():
    """The serving hierarchy: observed_stage_tick aggregation outranks the
    whole-step observed_layer_step but yields to a measured layer_step
    sweep; time_scale multiplies profile-served times per queried device
    NAME (degrade projection) and never touches the analytic fallback."""
    from repro.models import registry
    cfg = registry.get_config("llama3-8b")
    st = ProfileStore()
    # two telemetry entries, padded depth 4, mbs 2: per-layer per-seq
    # forward = tick_s / (4 * 2)
    for stage, tick in ((0, 8e-3), (1, 8e-3)):
        st.fold("cpu", "observed_stage_tick",
                {"arch": cfg.name, "seq_len": 32, "tp": 1, "schedule": "1f1b",
                 "stage": stage, "pp": 2, "vpp": 1, "layers": 3,
                 "padded_layers": 4, "micro_bs": 2}, "tick_s", tick)
    # stale whole-step estimate that must be outranked
    st.fold("cpu", "observed_layer_step",
            {"arch": cfg.name, "seq_len": 32, "tp": 1}, "per_seq_s", 99.0)
    src = ProfiledCostModel(st, device_map={"amd": "cpu", "gpu-a": "cpu"})
    per_seq = 8e-3 / (4 * 2)
    fwd, bwd = src.layer_time("amd", cfg, 32, 2, 1)
    assert fwd == pytest.approx(per_seq * 2)
    assert bwd == pytest.approx(2 * per_seq * 2)
    # degrade projection: gpu-a observed on the same host but now 4x slower
    src4 = ProfiledCostModel(st, device_map={"amd": "cpu", "gpu-a": "cpu"},
                             time_scale={"gpu-a": 4.0})
    f_a, _ = src4.layer_time("amd", cfg, 32, 2, 1)
    f_g, b_g = src4.layer_time("gpu-a", cfg, 32, 2, 1)
    assert f_g == pytest.approx(4 * f_a) and b_g == pytest.approx(2 * f_g)
    # a measured layer_step sweep outranks telemetry (and is scaled too)
    for mbs in (1, 2, 4):
        st.put("cpu", "layer_step",
               {"arch": cfg.name, "seq_len": 32, "micro_bs": mbs, "tp": 1},
               {"fwd_s": 1e-3 * mbs, "bwd_s": 2e-3 * mbs})
    f_m, _ = src4.layer_time("gpu-a", cfg, 32, 2, 1)
    assert f_m == pytest.approx(4.0 * 2e-3)
    # a device kind with no profile at all falls through to the analytic
    # fallback, which time_scale never touches (the degraded spec's own
    # effective TFLOPs already model it)
    src5 = ProfiledCostModel(st, time_scale={"tpu": 4.0})
    assert src5.layer_time("tpu", cfg, 32, 2, 1) == \
        ProfiledCostModel(ProfileStore()).layer_time("tpu", cfg, 32, 2, 1)


# ----------------------------------------------------------------- runner --
def test_runner_quick_writes_profile(tmp_path):
    """The measured path end-to-end in-process: tiny sweep -> store ->
    ProfiledCostModel serves interpolated layer times."""
    from repro.profile import runner
    out = tmp_path / "host.json"
    store = runner.run(quick=True, out=str(out), verbose=False)
    assert out.exists() and len(store) > 0
    dev = runner.device_kind()
    assert store.entries(dev, "layer_step")
    lt = ProfiledCostModel(store).layer_time(
        dev, registry_cfg(), 96, 1, 1)
    assert lt is not None and lt[0] > 0 and lt[1] >= 0


def registry_cfg():
    from repro.models import registry
    return registry.get_config("llama3-8b")
