"""Observability lockdown suite (repro.obs — trace, metrics, flight
recorder, report):

  * run identity — plan digests are content-addressed (equal plans hash
    equal, any placement change rehashes), RunMeta round-trips;
  * metrics stream — counters are cumulative, gauges last-write-wins,
    flush emits only what changed, every record validates against
    tools/metrics_schema.json, and the Prometheus snapshot carries the
    run_id label with observe summaries;
  * trace — the predicted lane renders the simulator oracle's SimEvent
    trace with balanced flow arrows, the observed lane reconstructs the
    1F1B warmup/steady/drain shape from tick durations, and the artifact
    is valid Chrome trace JSON (tools/validate_obs.py);
  * simulator trace parity — non-interleaved schedules now record
    SimEvents (vs == stage) without changing the report, and the traced
    fastsim path delegates to the oracle bit-exactly;
  * flight recorder — bounded ring, schema'd dumps, numbered repeat
    dumps, SIGTERM handler chains;
  * off-by-default — no telemetry sink, no collective sink, inert
    Observability when no output path is given;
  * the instrumented e2e acceptance scenario on a CPU mesh: a pipelined
    trainer with obs on runs through an autonomous degrade -> replan ->
    migrate, producing a trace with BOTH lanes + the adapt:migrate
    instant, a schema-valid metrics stream, an events JSONL — and
    ``repro.obs.report`` reproduces ``Trainer.schedule_health()``
    bit-for-bit from the metrics artifact alone.
"""
import importlib.util
import json
import signal
import tempfile
from pathlib import Path

import numpy as np
import pytest

from repro.adapt import AdaptConfig, ReplanPolicy
from repro.adapt.policy import events_jsonl
from repro.core import cluster as C
from repro.core import fastsim, simulator
from repro.core.plan import ParallelPlan, StagePlacement
from repro.iccl import communicator
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.obs import (FlightRecorder, MetricsLog, Observability, RunMeta,
                       TraceBuilder, install_sigterm, plan_digest,
                       predicted_sim_events, read_jsonl, uninstall_sigterm)
from repro.obs.report import RunMismatch, build_report
from repro.profile.store import ProfileStore
from repro.telemetry import StageTelemetry
from repro.train.trainer import Trainer, TrainerConfig

ROOT = Path(__file__).resolve().parents[1]


def _load_validator():
    spec = importlib.util.spec_from_file_location(
        "validate_obs", ROOT / "tools" / "validate_obs.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


VAL = _load_validator()


def _plan():
    return ParallelPlan(stages=(StagePlacement(0, 3, 1, 1, False),
                                StagePlacement(1, 3, 1, 1, True)),
                        micro_bs=2, global_batch=8, seq_len=32)


def _cluster():
    return C.ClusterSpec(groups=(C.NodeGroup(C.AMD, 1, accel_per_node=1),
                                 C.NodeGroup(C.GPU_A, 1, accel_per_node=1)))


# ------------------------------------------------------------ run identity --
def test_plan_digest_content_addressed():
    a, b = _plan(), _plan()
    assert plan_digest(a) == plan_digest(b)        # equal plans hash equal
    assert len(plan_digest(a)) == 12
    int(plan_digest(a), 16)                        # hex
    moved = ParallelPlan(stages=(StagePlacement(0, 4, 1, 1, False),
                                 StagePlacement(1, 2, 1, 1, True)),
                         micro_bs=2, global_batch=8, seq_len=32)
    assert plan_digest(moved) != plan_digest(a)    # any change rehashes


def test_runmeta_roundtrip_and_uniqueness():
    r = RunMeta.new(plan=_plan(), arch="llama3-8b")
    assert r.plan_digest == plan_digest(_plan())
    assert RunMeta.from_dict(r.to_dict()) == r
    assert r.to_dict()["schema"] == 1
    assert RunMeta.new().run_id != RunMeta.new().run_id


# ---------------------------------------------------------- metrics stream --
def test_metrics_counters_cumulative_gauges_last():
    m = MetricsLog()                                # in-memory
    m.count("c", 2.0, op="x")
    m.count("c", 3.0, op="x")
    m.gauge("g", 1.0)
    m.gauge("g", 7.0)
    n = m.flush(step=5)
    assert n == 2                                   # one line per metric
    recs = {r["name"]: r for r in m.lines if r["kind"] != "header"}
    assert recs["c"]["value"] == 5.0                # cumulative
    assert recs["c"]["labels"] == {"op": "x"}
    assert recs["g"]["value"] == 7.0                # last write wins
    assert m.flush(step=6) == 0                     # nothing dirty -> silent


def test_metrics_stream_validates_against_schema(tmp_path):
    path = tmp_path / "metrics.jsonl"
    m = MetricsLog(path, run=RunMeta.new(plan=_plan(), arch="a"))
    m.count("iccl_bytes", 1024.0, op="iallreduce", transport="pod")
    m.gauge("tick_s", 0.25, stage=0, device="amd")
    m.observe("migration_wall_s", 1.5, ok="true")
    m.plan(0, plan_digest(_plan()), _plan().to_dict(),
           {"iter_time": 1.0, "bubble_frac": 0.2,
            "stage_times_fwd": [0.1, 0.2]})
    m.flush(step=0)
    m.close()
    errors, run_id = VAL.validate_metrics(path)
    assert errors == []
    assert run_id == m.run.run_id
    recs = read_jsonl(path)
    assert recs[0]["kind"] == "header"              # header leads the stream
    assert recs == m.lines                          # mirror is exact


def test_metrics_prometheus_snapshot(tmp_path):
    prom = tmp_path / "prom.txt"
    m = MetricsLog(tmp_path / "m.jsonl", prom_out=prom)
    m.count("replans")
    m.gauge("step_time_s", 0.5)
    m.observe("migration_wall_s", 2.0, ok="true")
    m.observe("migration_wall_s", 4.0, ok="true")
    m.close()
    text = prom.read_text()
    assert f'run_id="{m.run.run_id}"' in text
    assert "# TYPE replans counter" in text
    assert "# TYPE step_time_s gauge" in text
    for suffix, v in (("count", 2.0), ("sum", 6.0), ("min", 2.0),
                      ("max", 4.0)):
        assert f"migration_wall_s_{suffix}" in text
        line = next(l for l in text.splitlines()
                    if l.startswith(f"migration_wall_s_{suffix}"))
        assert float(line.split()[-1]) == v


# ------------------------------------------------------------------- trace --
def test_predicted_lane_renders_and_validates(tmp_path):
    plan = _plan()
    cfg = registry.get_bundle("llama3-8b", smoke=True, num_layers=6).cfg
    events, rep, pred = predicted_sim_events(plan, _cluster(), cfg)
    assert events and rep.iter_time > 0
    tb = TraceBuilder()
    n = tb.predicted_lane(plan, events, anchor_us=0.0,
                          kinds=["amd", "gpu-a"],
                          digest=plan_digest(plan))
    assert n > 0
    evs = tb.events
    slices = [e for e in evs if e["ph"] == "X"]
    assert len(slices) == len(events)               # one slice per sim op
    assert {e["tid"] for e in slices} <= set(range(plan.pp))
    # flow arrows are balanced and id-paired: every F hop mb crosses once
    starts = [e for e in evs if e["ph"] == "s"]
    finishes = [e for e in evs if e["ph"] == "f"]
    assert len(starts) == len(finishes) == plan.micro_batches  # pp=2: 1 hop
    assert {e["id"] for e in starts} == {e["id"] for e in finishes}
    # a predicted slice never starts before its anchor or ends after total
    for e in slices:
        assert 0.0 <= e["ts"] and e["ts"] + e["dur"] <= rep.iter_time * 1e6 + 1
    path = tb.save(tmp_path / "trace.json")
    errors, run_id = VAL.validate_trace(path)
    assert errors == []
    assert run_id == tb.run.run_id


def test_observed_lane_shape():
    tb = TraceBuilder(epoch=0.0)
    # pp=2, vpp=1, m=2 -> n_ticks=3; stage 0 active ticks {0,1}, stage 1
    # active {1,2}: the textbook warmup/steady/drain staircase
    durs = [0.1, 0.2, 0.3]
    tb.observed_step(step=3, start_abs=10.0, durs=durs, pp=2, vpp=1, m=2,
                     mode="callback", kinds=["amd", "gpu-a"])
    ticks = [e for e in tb.events if e["ph"] == "X"
             and e["name"].startswith("tick")]
    by_stage = {i: sorted(e["args"]["tick"] for e in ticks
                          if e["tid"] == i) for i in (0, 1)}
    assert by_stage == {0: [0, 1], 1: [1, 2]}
    t0 = next(e for e in ticks if e["tid"] == 0 and e["args"]["tick"] == 0)
    assert t0["ts"] == pytest.approx(10.0 * 1e6)    # wall-aligned
    assert t0["dur"] == pytest.approx(0.1 * 1e6)
    span = next(e for e in tb.events if e["name"] == "step 3")
    assert span["dur"] == pytest.approx(sum(durs) * 1e6)
    # timer mode carries no wall anchor: laid out ending "now", flagged
    tb2 = TraceBuilder()
    tb2.observed_step(step=0, start_abs=None, durs=durs, pp=2, vpp=1, m=2,
                      mode="timer", kinds=None)
    assert all(e["args"]["mode"] == "timer" for e in tb2.events
               if e["ph"] == "X" and e["name"].startswith("tick"))


# -------------------------------------------------- simulator trace parity --
def test_simulator_noninterleaved_trace_consistent():
    timings = [simulator.StageTiming(0.3, 0.6, 0.0),
               simulator.StageTiming(0.5, 1.0, 0.0)]
    trace = []
    rep = simulator.simulate(timings, 4, "1f1b", trace=trace)
    bare = simulator.simulate(timings, 4, "1f1b")
    assert rep.iter_time == bare.iter_time          # tracing changes nothing
    assert rep.bubble_frac == bare.bubble_frac
    assert len(trace) == 2 * 4 * 2                  # F+B per mb per stage
    assert all(e.vs == e.stage for e in trace)      # non-interleaved: vs==i
    assert all(e.finish <= rep.iter_time and e.start >= 0.0 for e in trace)
    for stage in (0, 1):
        evs = sorted((e for e in trace if e.stage == stage),
                     key=lambda e: e.start)
        assert all(a.finish <= b.start + 1e-12
                   for a, b in zip(evs, evs[1:]))   # a stage never overlaps


def test_fastsim_traced_call_delegates_to_oracle():
    timings = [simulator.StageTiming(0.3, 0.6, 0.0),
               simulator.StageTiming(0.5, 1.0, 0.0)]
    ft, ot = [], []
    f = fastsim.simulate(timings, 4, "1f1b", trace=ft)
    o = simulator.simulate(timings, 4, "1f1b", trace=ot)
    assert f == o                                   # bit-exact delegation
    assert [(e.start, e.finish, e.stage, e.dir) for e in ft] \
        == [(e.start, e.finish, e.stage, e.dir) for e in ot]
    # the planner hot path (untraced) is untouched: still the closed form
    assert fastsim.simulate(timings, 4, "1f1b").iter_time \
        == pytest.approx(o.iter_time)


# --------------------------------------------------------- flight recorder --
def test_flight_ring_bounded_and_dump_schema(tmp_path):
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.note("step", step=i, dt=0.1)
    assert len(fr) == 4
    assert [e["step"] for e in fr.ring] == [6, 7, 8, 9]   # oldest dropped
    p1 = fr.dump(tmp_path / "flight.json", reason="schedule-error")
    doc = json.loads(p1.read_text())
    assert doc["kind"] == "flight" and doc["schema"] == 1
    assert doc["reason"] == "schedule-error"
    assert doc["run"]["run_id"] == fr.run.run_id
    assert [e["step"] for e in doc["events"]] == [6, 7, 8, 9]
    # a second failure keeps BOTH snapshots (numbered suffix)
    p2 = fr.dump(tmp_path / "flight.json", reason="sigterm")
    assert p2.name == "flight.1.json" and p2.exists() and p1.exists()


def test_sigterm_handler_dumps_then_chains(tmp_path):
    fr = FlightRecorder(capacity=8)
    fr.note("step", step=1)
    chained = []
    prev = signal.getsignal(signal.SIGTERM)
    signal.signal(signal.SIGTERM, lambda s, f: chained.append(s))
    try:
        install_sigterm(fr, tmp_path / "flight.json")
        handler = signal.getsignal(signal.SIGTERM)
        handler(signal.SIGTERM, None)               # invoke, don't kill
    finally:
        signal.signal(signal.SIGTERM, prev)
    doc = json.loads((tmp_path / "flight.json").read_text())
    assert doc["reason"] == "sigterm"
    assert chained == [signal.SIGTERM]              # previous handler ran


def test_install_sigterm_idempotent_per_recorder_and_path(tmp_path):
    """Repeated Trainer runs in one process re-install the handler: the
    same (recorder, path) pair is a no-op, a DIFFERENT pair replaces our
    handler (chaining what preceded it, never itself) — the chain stays
    depth one, so one SIGTERM dumps exactly once."""
    chained = []
    prev = signal.getsignal(signal.SIGTERM)
    signal.signal(signal.SIGTERM, lambda s, f: chained.append(s))
    try:
        fr = FlightRecorder(capacity=8)
        fr.note("step", step=1)
        install_sigterm(fr, tmp_path / "a.json")
        h1 = signal.getsignal(signal.SIGTERM)
        install_sigterm(fr, tmp_path / "a.json")    # same pair: no-op
        assert signal.getsignal(signal.SIGTERM) is h1
        # different pair: REPLACES (a chain of our own handlers would
        # dump twice per signal); the foreign chained handler is kept
        fr2 = FlightRecorder(capacity=8)
        fr2.note("step", step=2)
        install_sigterm(fr2, tmp_path / "b.json")
        h2 = signal.getsignal(signal.SIGTERM)
        assert h2 is not h1
        h2(signal.SIGTERM, None)
        assert not (tmp_path / "a.json").exists()   # old pair is gone
        assert json.loads(
            (tmp_path / "b.json").read_text())["reason"] == "sigterm"
        assert chained == [signal.SIGTERM]          # foreign ran ONCE
    finally:
        signal.signal(signal.SIGTERM, prev)
        uninstall_sigterm()                         # clear bookkeeping


def test_uninstall_sigterm_restores_chain(tmp_path):
    prev = signal.getsignal(signal.SIGTERM)
    marker = lambda s, f: None                      # noqa: E731
    signal.signal(signal.SIGTERM, marker)
    try:
        assert uninstall_sigterm() is False         # nothing installed
        install_sigterm(FlightRecorder(capacity=2), tmp_path / "f.json")
        assert signal.getsignal(signal.SIGTERM) is not marker
        assert uninstall_sigterm() is True
        assert signal.getsignal(signal.SIGTERM) is marker  # chain intact
        # foreign code replaced our handler since: their chain to manage
        install_sigterm(FlightRecorder(capacity=2), tmp_path / "g.json")
        signal.signal(signal.SIGTERM, marker)
        assert uninstall_sigterm() is False
        assert signal.getsignal(signal.SIGTERM) is marker
    finally:
        signal.signal(signal.SIGTERM, prev)


# ----------------------------------------------------------- events / off --
def test_events_jsonl_header_and_validation(tmp_path):
    run = RunMeta.new(plan=_plan())
    policy = ReplanPolicy(AdaptConfig())
    # a real AdaptEvent, not a stub: ride the policy's own emission path
    from repro.adapt.policy import AdaptEvent
    evs = [AdaptEvent(step=4, action="trigger", reason="straggler",
                      detail={"stage": 1})]
    path = tmp_path / "events.jsonl"
    path.write_text(events_jsonl(evs, run=run))
    errors, run_id = VAL.validate_events(path)
    assert errors == []
    assert run_id == run.run_id
    recs = read_jsonl(path)
    assert recs[0]["kind"] == "header"
    assert recs[1] == {"kind": "adapt_event", **evs[0].to_dict()}
    assert policy is not None


def test_off_by_default_no_hooks():
    # the two host-side tap points observability rides stay dark unless
    # an Observability object is wired in: this IS the zero-overhead claim
    assert communicator._SINK is None
    tele = StageTelemetry(pp=2, vpp=1, m=4)
    assert tele.sink is None
    obs = Observability()                           # no output paths
    assert not obs.enabled
    assert obs.trace is None and obs.metrics is None and obs.flight is None
    obs.on_step(0, 0.1, {"observed_bubble": 0.1, "predicted_bubble": 0.2,
                         "ratio": 0.5})             # inert, never raises
    obs.close()


def test_store_inspector_cli(tmp_path, capsys):
    from repro.profile import store as store_mod
    s = ProfileStore()
    s.fold("gpu-a", "observed_stage_tick",
           dict(arch="m", seq_len=32, tp=1, schedule="1f1b", stage=1,
                pp=2, vpp=1, layers=3, padded_layers=3, micro_bs=2),
           "tick_s", 0.004, also={"obs_scale": 8.0})
    s.fold("amd", "observed_step", dict(arch="m", gb=8), "time_s", 0.01)
    path = tmp_path / "store.json"
    s.save(path)
    assert store_mod.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "observed_stage_tick" in out and "8.0000" in out   # obs_scale
    assert store_mod.main([str(path), "--kind", "observed_step"]) == 0
    out = capsys.readouterr().out
    assert "observed_step" in out and "observed_stage_tick" not in out
    with pytest.raises(SystemExit) as e:      # missing file: clean error
        store_mod.main([str(tmp_path / "missing.json")])
    assert e.value.code == 2


def test_report_refuses_mismatched_runs():
    a = MetricsLog()
    a.gauge("step_time_s", 1.0)
    a.flush(0)
    events = [{"kind": "header", "run_id": "someone-else"},
              {"kind": "adapt_event", "step": 0, "action": "skip",
               "reason": "", "detail": {}}]
    with pytest.raises(RunMismatch):
        build_report(a.lines, events=events)


# --------------------------------------------- e2e: instrumented autopilot --
@pytest.fixture(scope="module")
def obs_e2e():
    """The acceptance scenario of docs/observability.md: the autonomous
    adaptation loop runs with every pillar on; the artifacts must be
    valid, attributable, and bit-exact against the trainer's own
    numbers."""
    tmp = Path(tempfile.mkdtemp())
    mesh = make_mesh((1, 1), ("data", "model"))
    bundle = registry.get_bundle("llama3-8b", smoke=True, num_layers=6)
    plan = _plan()
    obs = Observability(
        trace_out=tmp / "trace.json", metrics_out=tmp / "metrics.jsonl",
        events_out=tmp / "events.jsonl", prom_out=tmp / "prom.txt",
        flight_out=tmp / "flight.json",
        run=RunMeta.new(plan=plan, arch=bundle.cfg.name))
    policy = ReplanPolicy(AdaptConfig(patience=2, cooldown=4,
                                      baseline_steps=2, ewma=1.0,
                                      min_gain=0.0))
    t = Trainer(bundle, mesh,
                TrainerConfig(global_batch=8, seq_len=32,
                              ckpt_dir=str(tmp / "ckpt"), ckpt_every=100,
                              replan_profile_min_obs=4),
                cluster=_cluster(), plan=plan,
                profile_store=ProfileStore(), policy=policy,
                adapt_search_kw=dict(pp_options=[2], tp_options=[1],
                                     micro_bs_options=[2],
                                     require_fit=False,
                                     include_tp_comm=False,
                                     schedule="1f1b",
                                     explore_orders=False),
                obs=obs)
    t.run(4)
    t.inject_degrade("gpu-a", 8.0)
    t.run(6)
    health = t.schedule_health()                   # post-run ground truth
    obs.write_events(t.adapt_log)
    obs.close()
    return dict(trainer=t, tmp=tmp, health=health, run=obs.run)


def test_e2e_trace_has_both_lanes_and_replan_instant(obs_e2e):
    t = obs_e2e["trainer"]
    assert t.replans == 1                           # the scenario happened
    errors, run_id = VAL.validate_trace(obs_e2e["tmp"] / "trace.json",
                                        expect_replan=True)
    assert errors == []
    assert run_id == obs_e2e["run"].run_id
    doc = json.loads((obs_e2e["tmp"] / "trace.json").read_text())
    evs = doc["traceEvents"]
    instants = [e["name"] for e in evs if e["ph"] == "i"]
    # launch plan + replan plan -> two predicted segments
    assert instants.count("plan-adopted") == 2
    for name in ("adapt:trigger", "adapt:replan", "adapt:migrate"):
        assert name in instants
    # both lanes actually carry slices, not just process names
    for pid in (1, 2):
        assert any(e["ph"] == "X" and e["pid"] == pid for e in evs)
    # observed steps cover the run: kept observations only (compile step
    # is dropped by the recorder), each wall-anchored in callback mode
    steps = [e for e in evs if e["ph"] == "X"
             and e["name"].startswith("step ")]
    assert len(steps) >= 6


def test_e2e_metrics_validate_and_carry_the_loop(obs_e2e):
    path = obs_e2e["tmp"] / "metrics.jsonl"
    errors, run_id = VAL.validate_metrics(path)
    assert errors == []
    assert run_id == obs_e2e["run"].run_id
    recs = read_jsonl(path)
    names = {r.get("name") for r in recs}
    for name in ("step_time_s", "input_s", "tick_s", "observed_bubble",
                 "predicted_bubble", "iccl_calls", "iccl_bytes",
                 "adapt_events", "replans", "store_folds"):
        assert name in names, f"metric {name} never emitted"
    plans = [r for r in recs if r["kind"] == "plan"]
    assert len(plans) == 2                          # launch + replan
    assert plans[0]["digest"] == obs_e2e["run"].plan_digest
    assert plans[1]["digest"] != plans[0]["digest"]
    assert plans[1]["predicted"]["stage_times_fwd"]
    prom = (obs_e2e["tmp"] / "prom.txt").read_text()
    assert f'run_id="{obs_e2e["run"].run_id}"' in prom


def test_e2e_report_bit_exact_vs_schedule_health(obs_e2e):
    health = obs_e2e["health"]
    rep = build_report(read_jsonl(obs_e2e["tmp"] / "metrics.jsonl"),
                       events=read_jsonl(obs_e2e["tmp"] / "events.jsonl"))
    sh = rep["schedule_health"]
    # the acceptance criterion: == on floats, not approx — the gauges
    # round-trip JSON exactly and the report reuses the literal formula
    assert sh["observed_bubble"] == health["observed_bubble"]
    assert sh["predicted_bubble"] == health["predicted_bubble"]
    assert sh["ratio"] == health["ratio"]
    # drift table names the degraded island as the slow stage
    t = obs_e2e["trainer"]
    stages = {s["stage"]: s for s in rep["stages"]}
    assert set(stages) == set(range(t.plan.pp))
    assert rep["collectives"], "iccl counters missing from report"
    assert rep["adapt_events"].get("migrate") == 1.0
    assert rep["replans"] == 1.0


def test_e2e_events_artifact_matches_trainer_log(obs_e2e):
    t = obs_e2e["trainer"]
    path = obs_e2e["tmp"] / "events.jsonl"
    errors, run_id = VAL.validate_events(path)
    assert errors == []
    assert run_id == obs_e2e["run"].run_id
    recs = [r for r in read_jsonl(path) if r["kind"] == "adapt_event"]
    assert recs == [{"kind": "adapt_event", **e.to_dict()}
                    for e in t.adapt_log]
    assert [r["action"] for r in recs].count("migrate") == 1


def test_e2e_close_uninstalls_collective_sink(obs_e2e):
    # obs.close() ran in the fixture: the trace-time hook is gone and a
    # post-run program build would count nothing
    assert communicator._SINK is None
    assert obs_e2e["trainer"].telemetry.sink is not None  # was wired
