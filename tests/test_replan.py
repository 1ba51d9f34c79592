"""End-to-end online-replan lockdown suite (the HETHUB closed loop):

  train on a CPU mesh under a real pipeline plan with stage telemetry ->
  degrade one device kind (straggler injection) -> schedule-aware replan
  against the observed profile -> LIVE plan migration, bit-exact against
  a from-checkpoint restart -> keep training.

Plus the pieces in isolation: ClusterSpec.degrade, the telemetry
recorder, ckpt.migrate layout algebra (hypothesis round-trip), the
planner's incumbent-baseline scoring, and the AsyncCheckpointer
wait/save_async race regression.

The telemetry snapshot of the e2e scenario is always written to
``benchmarks/artifacts/telemetry_replan.json`` so CI can upload it as an
artifact when this suite fails.
"""
import tempfile
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.ckpt import checkpoint as ckpt
from repro.core import cluster as C
from repro.core import planner
from repro.core.plan import ParallelPlan, StagePlacement
from repro.core.predictor import PerformancePredictor
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.profile.model import ProfiledCostModel
from repro.profile.store import ProfileStore
from repro.telemetry import StageTelemetry
from repro.train.trainer import Trainer, TrainerConfig

TELEMETRY_ARTIFACT = (Path(__file__).resolve().parents[1] / "benchmarks"
                      / "artifacts" / "telemetry_replan.json")


# ----------------------------------------------------------- degrade hook --
def test_degrade_spec():
    cl = C.ClusterSpec(groups=(C.NodeGroup(C.AMD, 2),
                               C.NodeGroup(C.GPU_A, 2)))
    d = cl.degrade("gpu-a", 4.0)
    assert d.groups[1].device.effective_tflops == pytest.approx(
        cl.groups[1].device.effective_tflops / 4.0)
    assert d.groups[0].device == cl.groups[0].device      # untouched
    assert d.groups[1].device.name == "gpu-a"             # name preserved
    assert d.n_accel == cl.n_accel                        # topology intact
    with pytest.raises(ValueError, match="unknown device kind"):
        cl.degrade("h100", 2.0)
    with pytest.raises(ValueError, match="factor"):
        cl.degrade("amd", 0.0)


# ------------------------------------------------------ telemetry recorder --
def _feed_ticks(tele, durs):
    """Replay one step's tick marks through the real ``on_tick`` path with
    a controlled clock (``durs[t-1]`` elapses before mark t)."""
    import types
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


def test_recorder_sequencing_and_drop_first():
    tele = StageTelemetry(pp=2, vpp=1, m=4, mode="callback")
    assert tele.n_ticks == 5
    # torn sequence: tick 2 without tick 1 is discarded
    tele.on_tick(0)
    tele.on_tick(2)
    assert tele._marks == []
    # two full sequences: the first (compile) is dropped
    for _ in range(2):
        for t in range(tele.n_ticks + 1):
            tele.on_tick(t)
    assert tele.steps == 1
    assert len(tele.stage_ticks()) == 2


def test_recorder_bubble_matches_structural():
    """Uniform tick times -> the observed bubble equals the SPMD runtime's
    structural bubble 1 - m/(m + V - 1)."""
    for pp, vpp, m in [(2, 1, 4), (3, 2, 5), (4, 1, 2)]:
        tele = StageTelemetry(pp=pp, vpp=vpp, m=m, mode="callback",
                              drop_first=False)
        _feed_ticks(tele, [0.5] * (tele.n_ticks + 1))
        V = pp * vpp
        assert tele.bubble() == pytest.approx(1 - m / (m + V - 1), rel=1e-6)
        assert tele.stage_ticks() == pytest.approx([0.5 / V] * V)


def test_recorder_timer_mode_buckets():
    tele = StageTelemetry(pp=2, vpp=2, m=4, mode="timer",
                          drop_first=False, bucket_steps=3)
    tele.observe_step(0.9)
    tele.observe_step(1.1)
    assert tele.steps == 0          # bucket not full yet
    tele.observe_step(1.0)
    assert tele.steps == 1
    # fwd share (1/3) spread over n_ticks, equal per slot
    V, nt = 4, 4 + 4 - 1
    assert tele.stage_ticks() == pytest.approx([1.0 / 3 / nt / V] * V)
    st_ = ProfileStore()
    n = tele.fold_into(st_, ["cpu", "cpu"], arch="m", seq_len=32, tp=1,
                       schedule="interleaved-1f1b",
                       layers_per_vstage=[2, 1, 1, 1],
                       padded_per_stage=[4, 4],
                       micro_bs_per_stage=[2, 2])
    assert n == 1
    e = st_.get("cpu", "observed_stage_tick",
                {"arch": "m", "seq_len": 32, "tp": 1,
                 "schedule": "interleaved-1f1b", "stage": 0, "pp": 2,
                 "vpp": 2, "layers": 3, "padded_layers": 4, "micro_bs": 2})
    assert e is not None and e.meta["telemetry"] == "timer"
    assert st_.get("cpu", "observed_bubble",
                   {"arch": "m", "schedule": "interleaved-1f1b", "pp": 2,
                    "vpp": 2, "m": 4}) is not None


def test_recorder_rejects_bad_mode():
    with pytest.raises(ValueError, match="telemetry mode"):
        StageTelemetry(2, 1, 4, mode="sample")


def test_recorder_timer_mode_ignores_tick_marks():
    """Timer mode must not double-record: tick callbacks (if a caller
    wired them anyway) are ignored, only observe_step counts."""
    tele = StageTelemetry(pp=2, vpp=1, m=4, mode="timer", drop_first=False)
    for t in range(tele.n_ticks + 1):
        tele.on_tick(t)
    assert tele.steps == 0
    tele.observe_step(0.9)
    assert tele.steps == 1 and len(tele._fresh) == 1


def test_recorder_fresh_bounded_without_fold():
    """A trainer without a profile store never drains _fresh — the
    recorder must bound it itself."""
    tele = StageTelemetry(pp=2, vpp=1, m=2, mode="timer", drop_first=False)
    tele.MAX_FRESH = 8
    for _ in range(30):
        tele.observe_step(1.0)
    assert tele.steps == 30 and len(tele._fresh) == 8


def test_recorder_timer_bucket_one_with_drop_first():
    """bucket_steps=1 (the default): every bucket is a single step, so
    drop_first swallows exactly the first observe_step and every later
    step folds individually with bucketed provenance."""
    tele = StageTelemetry(pp=2, vpp=1, m=4, mode="timer", bucket_steps=1)
    tele.observe_step(3.0)                    # compile step: dropped
    assert tele.steps == 0 and tele._fresh == []
    for dt in (0.9, 1.2):
        tele.observe_step(dt)
    assert tele.steps == 2 and len(tele._fresh) == 2
    # each kept step is its own bucket: no averaging across steps
    nt = tele.n_ticks
    assert tele._fresh[0] == pytest.approx([0.9 / 3 / nt] * nt)
    assert tele._fresh[1] == pytest.approx([1.2 / 3 / nt] * nt)
    st_ = ProfileStore()
    n = tele.fold_into(st_, ["cpu", "cpu"], arch="m", seq_len=32, tp=1,
                       schedule="1f1b", layers_per_vstage=[2, 2],
                       padded_per_stage=[2, 2], micro_bs_per_stage=[2, 2])
    assert n == 2
    e = st_.get("cpu", "observed_stage_tick",
                {"arch": "m", "seq_len": 32, "tp": 1, "schedule": "1f1b",
                 "stage": 0, "pp": 2, "vpp": 1, "layers": 2,
                 "padded_layers": 2, "micro_bs": 2})
    assert e.value["n"] == 2 and e.meta["provenance"] == "bucketed"


def test_recorder_timer_partial_final_bucket_discarded():
    """A bucket still filling when the run ends must NEVER fold: a
    partial mean is not the bucket's statistic, and fold_into reports 0
    steps for it."""
    tele = StageTelemetry(pp=2, vpp=1, m=4, mode="timer",
                          drop_first=False, bucket_steps=3)
    tele.observe_step(1.0)
    tele.observe_step(1.0)                    # 2 of 3: bucket open
    assert tele.steps == 0 and tele._bucket == [1.0, 1.0]
    st_ = ProfileStore()
    n = tele.fold_into(st_, ["cpu", "cpu"], arch="m", seq_len=32, tp=1,
                       schedule="1f1b", layers_per_vstage=[2, 2],
                       padded_per_stage=[2, 2], micro_bs_per_stage=[2, 2])
    assert n == 0 and len(st_) == 0
    assert tele.bubble() is None and tele.stage_ticks() is None
    # completing the bucket afterwards folds exactly one observation
    tele.observe_step(1.0)
    assert tele.fold_into(
        st_, ["cpu", "cpu"], arch="m", seq_len=32, tp=1, schedule="1f1b",
        layers_per_vstage=[2, 2], padded_per_stage=[2, 2],
        micro_bs_per_stage=[2, 2]) == 1


def test_recorder_timer_drop_first_replan_mid_bucket():
    """A replan rebuilds the trainer's recorder (Trainer._build makes a
    fresh StageTelemetry): the half-filled bucket of the old recorder
    dies with it — never folded — and the NEW recorder's drop_first
    swallows its own first completed bucket again, because the rebuilt
    jit step pays compilation exactly like the first one did."""
    old = StageTelemetry(pp=2, vpp=1, m=4, mode="timer", bucket_steps=2)
    old.observe_step(5.0)
    old.observe_step(5.0)                      # first bucket: dropped
    old.observe_step(1.0)
    old.observe_step(1.0)                      # second bucket: kept
    old.observe_step(1.0)                      # third bucket half-full
    assert old.steps == 1 and len(old._bucket) == 1
    st_ = ProfileStore()
    kw = dict(arch="m", seq_len=32, tp=1, schedule="1f1b",
              layers_per_vstage=[2, 2], padded_per_stage=[2, 2],
              micro_bs_per_stage=[2, 2])
    assert old.fold_into(st_, ["cpu", "cpu"], **kw) == 1   # not the partial
    # --- replan: fresh recorder, same shape ---
    new = StageTelemetry(pp=2, vpp=1, m=4, mode="timer", bucket_steps=2)
    new.observe_step(9.0)
    new.observe_step(9.0)                      # recompile bucket: dropped
    assert new.steps == 0
    assert new.fold_into(st_, ["cpu", "cpu"], **kw) == 0
    new.observe_step(1.0)
    new.observe_step(1.0)
    assert new.steps == 1
    assert new.fold_into(st_, ["cpu", "cpu"], **kw) == 1
    e = st_.get("cpu", "observed_stage_tick",
                {"arch": "m", "seq_len": 32, "tp": 1, "schedule": "1f1b",
                 "stage": 0, "pp": 2, "vpp": 1, "layers": 2,
                 "padded_layers": 2, "micro_bs": 2})
    # both kept buckets were healthy 1.0s steps: the 9.0s recompile
    # bucket and the orphaned partials left no trace in the mean
    # (per slot: fwd third of the step, spread over n_ticks, shared by V)
    nt, V = 4 + 2 - 1, 2
    assert e.value["n"] == 2
    assert e.value["tick_s"] == pytest.approx(1.0 / 3 / nt / V)


# ------------------------------------------------- migrate layout algebra --
def _toy_state(L, extra_master=True):
    rng = np.random.RandomState(0)
    params = {"blocks": {"w": rng.randn(L, 3, 2).astype(np.float32),
                         "b": rng.randn(L, 4).astype(np.float32)},
              "embed": rng.randn(5, 2).astype(np.float32)}
    opt = {"m": {"blocks": {"w": rng.randn(L, 3, 2).astype(np.float32),
                            "b": rng.randn(L, 4).astype(np.float32)},
                 "embed": np.zeros((5, 2), np.float32)},
           "v": {"blocks": {"w": rng.randn(L, 3, 2).astype(np.float32),
                            "b": rng.randn(L, 4).astype(np.float32)},
                 "embed": np.zeros((5, 2), np.float32)},
           "count": np.zeros((), np.int32)}
    if extra_master:
        opt["master"] = {"blocks": {"w": params["blocks"]["w"] * 1.0,
                                    "b": params["blocks"]["b"] * 1.0},
                         "embed": params["embed"] * 1.0}
    return {"params": params, "opt": opt, "step": np.zeros((), np.int32)}


def _rand_layout(rng, L):
    pp = rng.randint(1, 4)
    vpp = rng.randint(1, 3)
    V = pp * vpp
    if L < V:
        return None
    cuts = sorted(rng.choice(range(1, L), size=V - 1, replace=False)) \
        if V > 1 else []
    vl = [b - a for a, b in zip([0] + list(cuts), list(cuts) + [L])]
    out = {"pp": pp, "vpp": vpp, "virtual_layers": vl}
    # most layouts pin per-stage tensor widths (asymmetric plans); the
    # rest keep the legacy manifest shape, which _norm_layout must
    # default to tp=1 everywhere
    if rng.rand() < 0.75:
        out["stage_tp"] = [int(rng.choice([1, 2, 4, 8]))
                           for _ in range(pp)]
    return out


def test_migrate_roundtrip_seeded():
    """canonical -> layout A -> layout B -> canonical is the identity on
    every real layer, for params and every optimizer moment tree."""
    rng = np.random.RandomState(7)
    for _ in range(25):
        L = rng.randint(2, 13)
        state = _toy_state(L)
        la = _rand_layout(rng, L)
        lb = _rand_layout(rng, L)
        if la is None or lb is None:
            continue
        a = ckpt.migrate(state, None, la)
        b = ckpt.migrate(a, la, lb)
        back = ckpt.migrate(b, lb, None)
        jax.tree.map(lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)), state, back)


@given(st.integers(2, 12), st.integers(0, 2 ** 30))
@settings(max_examples=40, deadline=None)
def test_migrate_roundtrip_property(L, seed):
    rng = np.random.RandomState(seed % (2 ** 31 - 1))
    la = _rand_layout(rng, L)
    lb = _rand_layout(rng, L)
    if la is None or lb is None:
        return
    state = _toy_state(L, extra_master=False)
    out = ckpt.migrate(ckpt.migrate(ckpt.migrate(state, None, la), la, lb),
                       lb, None)
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), state, out)
    # stacked shapes honour the layout
    stacked = ckpt.migrate(state, None, la)
    w = stacked["params"]["blocks"]["w"]
    lmax = max(la["virtual_layers"])
    want = ((la["pp"], lmax, 3, 2) if la["vpp"] == 1
            else (la["pp"], la["vpp"], lmax, 3, 2))
    assert w.shape == want


def test_migrate_tp_width_change_bit_exact_vs_checkpoint_restart(tmp_path):
    """A replan that changes per-stage tp re-PLACES shards but never
    rewrites content (state leaves are stored full): migrating the live
    state across a tp-width-changing layout equals restoring the
    pre-change checkpoint and migrating the restored state — bit for
    bit."""
    L = 6
    state = _toy_state(L)
    old = {"pp": 2, "vpp": 1, "virtual_layers": [3, 3], "stage_tp": [1, 1]}
    new = {"pp": 3, "vpp": 1, "virtual_layers": [2, 2, 2],
           "stage_tp": [4, 2, 1]}
    stacked = ckpt.migrate(state, None, old)
    ckpt.save(str(tmp_path), 1, stacked, extra={"layout": old})
    live = ckpt.migrate(stacked, old, new)
    restored, _ = ckpt.restore(str(tmp_path), 1, stacked)
    restarted = ckpt.migrate(restored, old, new)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), live, restarted)
    # round trip through the wider-tp layout is still the identity
    back = ckpt.migrate(live, new, None)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), state, back)


def test_migrate_tp_only_delta_and_legacy_default():
    """Layouts identical except ``stage_tp`` compare UNEQUAL (the
    migration machinery must run — the new widths need re-placement)
    yet migrate is the content identity; manifests predating per-stage
    tp normalize to tp=1 everywhere."""
    stacked = ckpt.migrate(_toy_state(4), None,
                           {"pp": 2, "vpp": 1, "virtual_layers": [2, 2],
                            "stage_tp": [1, 1]})
    la = {"pp": 2, "vpp": 1, "virtual_layers": [2, 2], "stage_tp": [1, 1]}
    lb = {"pp": 2, "vpp": 1, "virtual_layers": [2, 2], "stage_tp": [8, 2]}
    assert ckpt._norm_layout(la) != ckpt._norm_layout(lb)
    out = ckpt.migrate(stacked, la, lb)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), stacked, out)
    legacy = {"pp": 2, "vpp": 1, "virtual_layers": [2, 2]}
    assert ckpt._norm_layout(legacy)["stage_tp"] == [1, 1]
    assert ckpt._norm_layout(legacy) == ckpt._norm_layout(la)


# ------------------------------------------------ planner incumbent score --
def test_planner_baseline_plan_bounds_winner():
    cl = C.paper_cluster_of_size(12)
    from repro.configs.llama2_paper import LLAMA2_70B
    kw = dict(global_batch=96, seq_len=4096, pp_options=[6],
              tp_options=[8], micro_bs_options=[1], require_fit=False,
              include_tp_comm=False)
    base = planner.search(cl, LLAMA2_70B, **kw)
    res = planner.search(cl, LLAMA2_70B, baseline_plan=base.plan, **kw)
    scored = dict(res.log)
    key = f"baseline {base.plan.describe()}"
    assert key in scored
    assert res.prediction.iter_time <= scored[key] * (1 + 1e-12)
    # an incumbent that no longer maps onto the cluster is skipped, not
    # fatal (node loss removed its group)
    orphan = ParallelPlan(
        stages=(StagePlacement(5, 40, 1, 8, False),
                StagePlacement(5, 40, 1, 8, True)),
        micro_bs=1, global_batch=96, seq_len=4096)
    res2 = planner.search(cl, LLAMA2_70B, baseline_plan=orphan, **kw)
    assert res2.prediction.iter_time == pytest.approx(
        base.prediction.iter_time)


# ------------------------------------------- async checkpointer regression --
def _tiny_state():
    return {"w": np.arange(8, dtype=np.float32)}


def test_async_ckpt_error_raised_once_not_sticky(monkeypatch, tmp_path):
    ck = ckpt.AsyncCheckpointer(str(tmp_path), keep=2)
    real_save = ckpt.save
    boom = {"n": 0}

    def failing_save(*a, **k):
        boom["n"] += 1
        raise RuntimeError("disk on fire")

    monkeypatch.setattr(ckpt, "save", failing_save)
    ck.save_async(1, _tiny_state())
    with pytest.raises(RuntimeError, match="disk on fire"):
        ck.wait()
    ck.wait()                       # error consumed — must not re-raise
    monkeypatch.setattr(ckpt, "save", real_save)
    ck.save_async(2, _tiny_state())
    ck.wait()
    assert ckpt.latest_step(str(tmp_path)) == 2


def test_async_ckpt_concurrent_wait_save_keeps_window(monkeypatch, tmp_path):
    """The PR-4 race regression: wait() returning concurrently with a new
    save_async() must never leave a save unsupervised or let _gc act on a
    torn keep-window.  Hammer wait/save_async from threads around a
    slowed save; afterwards exactly the newest ``keep`` steps exist, no
    .tmp dirs remain, and no error surfaced."""
    real_save = ckpt.save

    def slow_save(*a, **k):
        time.sleep(0.01)
        return real_save(*a, **k)

    monkeypatch.setattr(ckpt, "save", slow_save)
    ck = ckpt.AsyncCheckpointer(str(tmp_path), keep=2)
    N = 12
    errs = []

    def writer(i):
        try:
            ck.save_async(i, _tiny_state())
        except BaseException as e:   # noqa: BLE001
            errs.append(e)

    def waiter():
        try:
            ck.wait()
        except BaseException as e:   # noqa: BLE001
            errs.append(e)

    threads = []
    for i in range(1, N + 1):
        threads.append(threading.Thread(target=writer, args=(i,)))
        threads.append(threading.Thread(target=waiter))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ck.wait()
    with ck._lock:
        ck._gc()                     # settle the window deterministically
    assert not errs
    steps = ckpt.all_steps(str(tmp_path))
    assert len(steps) == 2 and steps[-1] <= N
    assert not list(Path(tmp_path).glob("*.tmp"))
    for s in steps:                  # every survivor is complete
        d = Path(tmp_path) / f"step_{s:08d}"
        assert (d / "manifest.json").exists()
        state, _ = ckpt.restore(str(tmp_path), s, _tiny_state())
        np.testing.assert_array_equal(state["w"], _tiny_state()["w"])


def test_async_ckpt_gc_keep_window_sequential(tmp_path):
    ck = ckpt.AsyncCheckpointer(str(tmp_path), keep=2)
    for s in range(1, 6):
        ck.save_async(s, _tiny_state())
    ck.wait()
    assert ckpt.all_steps(str(tmp_path)) == [4, 5]


# --------------------------------------------------------- e2e closed loop --
@pytest.fixture(scope="module")
def e2e():
    """Shared scenario: pipeline trainer on a CPU mesh with telemetry ->
    degrade -> replan (migrate in memory) -> checkpoint round-trip."""
    tmp = Path(tempfile.mkdtemp())
    mesh = make_mesh((1, 1), ("data", "model"))
    bundle = registry.get_bundle("llama3-8b", smoke=True, num_layers=6)
    cl = C.ClusterSpec(groups=(C.NodeGroup(C.AMD, 1, accel_per_node=1),
                               C.NodeGroup(C.GPU_A, 1, accel_per_node=1)))
    old_plan = ParallelPlan(stages=(StagePlacement(0, 3, 1, 1, False),
                                    StagePlacement(1, 3, 1, 1, True)),
                            micro_bs=2, global_batch=8, seq_len=32)
    store = ProfileStore()
    t = Trainer(bundle, mesh,
                TrainerConfig(global_batch=8, seq_len=32,
                              ckpt_dir=str(tmp / "ckpt"), ckpt_every=100,
                              replan_profile_min_obs=4),
                cluster=cl, plan=old_plan, profile_store=store)
    r1 = t.run(4)
    if t.telemetry is not None:
        t.telemetry.dump(TELEMETRY_ARTIFACT)
    cl2 = cl.degrade("gpu-a", 4.0)
    src = t.profiled_cost_source(cl2)
    res = t.replan(cl2, global_batch=8, seq_len=32,
                   pp_options=[2], tp_options=[1], micro_bs_options=[1, 2],
                   require_fit=False, include_tp_comm=False)
    migrated = jax.device_get(t.state)
    # checkpoint round-trip: restore the pre-migration checkpoint (old
    # layout) and migrate it onto the new plan
    t._init_or_restore()
    restarted = jax.device_get(t.state)
    r2 = t.run(2)
    return dict(trainer=t, bundle=bundle, store=store, cl=cl, cl2=cl2,
                old_plan=old_plan, src=src, res=res, r1=r1, r2=r2,
                migrated=migrated, restarted=restarted)


def test_e2e_telemetry_observed(e2e):
    """Training under the plan records telemetry and folds the new store
    kinds."""
    t, store = e2e["trainer"], e2e["store"]
    ticks = store.entries(op="observed_stage_tick")
    assert {e.shape["stage"] for e in ticks} == {0, 1}
    assert all(e.value["tick_s"] > 0 and e.value["n"] >= 1 for e in ticks)
    # the pre-replan plan accumulated several folded steps
    assert any(e.value["n"] >= 2 for e in ticks)
    bub = store.entries(op="observed_bubble")
    assert bub and all(0.0 <= e.value["bubble_frac"] < 1.0 for e in bub)
    assert TELEMETRY_ARTIFACT.exists()
    health = t.schedule_health()
    assert health is not None and 0.0 <= health["observed_bubble"] < 1.0
    assert health["predicted_bubble"] > 0.0


def test_e2e_replan_picks_new_plan_off_degraded_kind(e2e):
    """degrade() must actually move layers: the replanned assignment gives
    the degraded kind strictly fewer layers than the incumbent did."""
    res, cl2, old_plan = e2e["res"], e2e["cl2"], e2e["old_plan"]
    new_plan = res.plan
    assert new_plan.layers != old_plan.layers

    def degraded_layers(plan):
        return sum(st_.n_layers for st_ in plan.stages
                   if cl2.groups[st_.group].device.name == "gpu-a")

    assert degraded_layers(new_plan) < degraded_layers(old_plan)
    # the search consumed the observed profile (schedule-aware replan)
    assert isinstance(e2e["src"], ProfiledCostModel)
    assert e2e["src"].time_scale == {"gpu-a": 4.0}


def test_e2e_new_plan_beats_degraded_old_plan(e2e):
    """The winner's predicted iter_time beats the incumbent scored under
    the SAME degraded cost source (the baseline the search logged)."""
    res, old_plan = e2e["res"], e2e["old_plan"]
    scored = dict(res.log)
    key = f"baseline {old_plan.describe()}"
    assert key in scored, "replan must score the incumbent as baseline"
    assert res.prediction.iter_time < scored[key]
    # independent check with a fresh predictor over the same source
    pred = PerformancePredictor(e2e["cl2"], e2e["bundle"].cfg,
                                include_tp_comm=False, cost_source=e2e["src"])
    assert res.prediction.iter_time < pred.predict(old_plan).iter_time


def test_e2e_migration_bit_exact_vs_checkpoint_restart(e2e):
    """In-memory migration == checkpoint-restart resharding, bit for bit,
    and the migrated state steps with finite loss."""
    t = e2e["trainer"]
    assert t.migrations["memory"] == 1
    assert t.migrations["checkpoint"] >= 1       # the round-trip we forced
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), e2e["migrated"], e2e["restarted"])
    assert all(np.isfinite(v) for v in e2e["r2"]["losses"])


def test_e2e_loss_and_grads_match_bit_exact(e2e):
    """One full train step from the migrated and the restarted state
    produces identical loss AND identical updated parameters (grads are
    applied by the step, so equal next-params == equal grads)."""
    t = e2e["trainer"]
    step_fn = jax.jit(t.train_step)      # fresh jit, no donation
    shardings = t._state_shardings(jax.eval_shape(lambda: e2e["migrated"]))
    batch = t._device_batch(t.data.batch_at(t.step))
    outs = []
    for state in (e2e["migrated"], e2e["restarted"]):
        placed = t._place(state, shardings)
        with jax.set_mesh(t.mesh):
            new_state, metrics = step_fn(placed, batch)
        outs.append((jax.device_get(new_state),
                     float(jax.device_get(metrics["loss"]))))
    (sa, la), (sb, lb) = outs
    assert la == lb
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), sa, sb)


def test_e2e_chunk_peak_memory_trace_exact(e2e):
    """Acceptance: ``peak_memory`` on an interleaved ragged-chunk plan is
    trace-exact — it equals the by-hand SimEvent accounting of the
    oracle's executed schedule (no mean-chunk approximation left)."""
    from repro.core import costmodel, simulator
    cfg = e2e["bundle"].cfg
    cl2 = e2e["cl2"]
    plan = ParallelPlan(
        stages=(StagePlacement(0, 4, 1, 1, False),
                StagePlacement(1, 2, 1, 1, True)),
        micro_bs=2, global_batch=8, seq_len=32,
        schedule="interleaved-1f1b", vpp=2, chunk_layers=(3, 1, 1, 1))
    pred = PerformancePredictor(cl2, cfg, include_tp_comm=False)
    mems = pred.peak_memory(plan)
    trace = []
    simulator.simulate(pred.virtual_timings(plan), plan.micro_batches,
                       "interleaved-1f1b", vpp=plan.vpp, trace=trace)
    peaks = simulator.trace_peak_layers(trace, plan.pp, plan.virtual_layers)
    lc = costmodel.layer_cost(cfg, plan.seq_len)
    for i, st_ in enumerate(plan.stages):
        params = lc.param_bytes * st_.n_layers / st_.tp
        opt = params * (6.0 + 2.0 / st_.dp)
        acts = (lc.act_bytes_per_token * plan.stage_micro_bs(i)
                * plan.seq_len / st_.tp) * peaks[i]
        assert mems[i] == pytest.approx((params + opt + acts) / 1e9,
                                        rel=1e-12)
