"""CPU tests of the on-chip benchmark (no TPU is touched).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

- the FLOP counts against the param counts of the configurations as run;
- the trace reduction, on intervals and on a trace recorded on a v5e;
- the peak table, and the run's refusal to start without a TPU;
- files dropped into a copy of the benchmark are found by name, and a
  whole run (trace included) goes through at a tiny size;
- the control (the reference in fp8 in the program's place) and each fault
  of the timed path that a training cell can have come out not correct.

The harness's look for a chip is the only part of a run these tests skip.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
REPO = CHIP.parents[1]
DATA = CHIP / "tests" / "data"
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(REPO / "src"))

import harness  # noqa: E402
import trace_reduce  # noqa: E402

SEED = 3_000_000_019          # above 2**31: seeds need not fit in 32 bits
PIPELINE_READERS = ("stage_idle_max", "collective_exposed_ms",
                    "predictor_err", "predictor_mem_err")


def _bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())


# ------------------------------------------------------------- flops ----
MATMUL_LEAVES = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                 "unembed", "in_proj", "x_proj", "dt_proj", "out_proj"}


@pytest.mark.parametrize("config", [c["name"] for c in _bench()["configs"]])
def test_flops_count_the_matmul_params_the_program_holds(config):
    import jax
    from reference.core import leaf_names
    from repro.models import registry
    conf = next(c for c in _bench()["configs"] if c["name"] == config)
    cfg = json.loads((REPO / conf["file"]).read_text())
    reg = cfg["registry"]
    bundle = registry.get_bundle(reg["arch"], **reg.get("overrides", {}))
    shapes = jax.eval_shape(lambda k: bundle.init(k, bundle.cfg),
                            jax.random.PRNGKey(0))
    held = sum(x.size for n, x in zip(leaf_names(shapes),
                                      jax.tree.leaves(shapes))
               if n.split("/")[-1] in MATMUL_LEAVES)
    flops = harness.load_module(CHIP / "flops" / f"{cfg['family']}.py")
    assert flops.matmul_params(cfg) == held
    if config == "danube3-4b-2l":
        assert held == 432_537_600
        assert flops.per_token(cfg, 2048) == pytest.approx(2.6896e9, rel=1e-4)


def test_mean_keys_follow_the_causal_window():
    flops = harness.load_module(CHIP / "flops" / "dense.py")
    assert flops.mean_keys(4, None) == 2.5
    assert flops.mean_keys(4, 2) == (1 + 2 + 2 + 2) / 4


# ------------------------------------------------------- trace_reduce ----
def test_interval_algebra():
    merged = trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [[0, 3], [5, 8]]
    assert trace_reduce.length(merged) == 6
    assert trace_reduce.subtract([[0, 10]], merged) == [[3, 5], [8, 10]]
    assert trace_reduce.subtract([[0, 4], [6, 9]], [[1, 2], [3, 7]]) == \
        [[0, 1], [2, 3], [7, 9]]


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def test_reduce_busy_idle_and_exposed_collectives():
    """A loop whose body holds a fusion and an all-reduce that outlasts
    it, then a fusion after an idle gap."""
    host = _Plane("/host:CPU", [_Line("python", [
        _Ev("train", 0, 100), _Ev("$trainer.py:357 _run", 60, 40)])])
    dev = _Plane("/device:TPU:0", [_Line("XLA Ops", [
        _Ev("%while.1 = (s32[]) while(%t), body=%b", 0, 50),
        _Ev("%fusion.2 = f32[8] fusion(%p)", 0, 30),
        _Ev("%all-reduce.3 = f32[8] all-reduce(%f)", 25, 20),
        _Ev("%fusion.4 = f32[8] fusion(%a)", 70, 10)])])
    red = trace_reduce.reduce_profile(_Profile([host, dev]))
    d = red["devices"][0]
    assert red["window_ns"] == 100 and red["steps"] == 1
    assert d["busy_ns"] == 60                  # [0, 50) and [70, 80)
    assert d["collective_ns"] == 20
    assert d["collective_exposed_ns"] == 15    # [30, 45)
    assert d["ops_ns"] == {"while.1": 0, "fusion.2": 30, "all-reduce.3": 20,
                           "fusion.4": 10}
    assert d["gaps"] == [[50, 70], [80, 100]]
    assert trace_reduce.gap_spans(red, 0, 10) == [
        ["$trainer.py:357 _run", 20e-9], ["$trainer.py:357 _run", 20e-9]]


def test_reduce_a_trace_recorded_on_a_v5e():
    """Two traced steps of ``danube3-4b-2l.b4s2k`` on one v5e chip: the
    device plane's ops, nested loops and all, add up to the busy time, and
    the busy time and the gaps to the traced window."""
    red = trace_reduce.reduce_dir(DATA / "trace_v5e_1chip")
    assert sorted(red["devices"]) == [0] and red["steps"] == 2
    d = red["devices"][0]
    assert d["busy_ns"] == 531627549.0 and red["window_ns"] == 541489875.0
    assert sum(d["ops_ns"].values()) == pytest.approx(d["busy_ns"])
    assert max(d["ops_ns"], key=d["ops_ns"].get) == "fusion.1"
    assert d["collective_ns"] == 0          # one chip: nothing to exchange
    idle = trace_reduce.length(d["gaps"])
    assert idle + d["busy_ns"] == pytest.approx(red["window_ns"])
    assert trace_reduce.gap_spans(red, 0, 1) == [
        ["$api.py:3108 try_to_block", 0.005103753]]


# ------------------------------------------------------------- peaks ----
def test_peaks_know_the_v5e_and_refuse_an_unknown_kind():
    assert harness.peak_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        harness.peak_for("TPU v9 imaginary")


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "danube3-4b-2l.b4s2k", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    r = _run_cli(REPO, {"PYTHONPATH": str(REPO / "src")})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip")
    r = _run_cli(tmp_path, {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert r.stdout.strip() == ""


# ------------------------------------------- whole runs at a tiny size ----
SMOKE_LIMITS = {"loss_gap": 1e-3, "grad_gap": 6e-3, "change_gap": 3e-3}


def make_checkout(tmp: Path, cells, metric_src=None) -> Path:
    """A copy of the benchmark with the test data's files dropped in, and
    a BENCHMARK.json that names ``cells`` [(config, traffic, chips)]."""
    root = tmp / "benchmarks" / "chip"
    shutil.copytree(CHIP, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    for kind in ("configs", "traffic"):
        for f in (DATA / kind).glob("*.json"):
            shutil.copy(f, root / kind / f.name)
    bench = _bench()
    for config, traffic, chips in cells:
        name = f"{config}.{traffic}"
        bench["configs"].append({
            "name": config, "source": "registry smoke size",
            "file": f"benchmarks/chip/configs/{config}.json",
            "reduced": [], "why": "test"})
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": chips,
                                   "why": "test"})
        (root / "limits" / f"{name}.json").write_text(
            json.dumps({"limits": SMOKE_LIMITS}))
    if metric_src is not None:
        (root / "metrics" / "steps_traced.py").write_text(metric_src)
        bench["per_layer"].append({
            "name": "steps_traced", "unit": "steps", "better": "higher",
            "source": "device_trace", "layer": "test",
            "moves": "tokens_per_s"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def off_chip(monkeypatch):
    """Skips the look for a chip; everything else of a run is as is."""
    import jax
    monkeypatch.setattr(harness, "require_chips",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(harness, "peak_for",
                        lambda kind, root=None: {"bf16_flops": 197e12})
    monkeypatch.setattr(harness, "configure_jax", lambda: {
        "compiled": 0, "cached": 0, "cache_dir": "off"})


def run_cell(tmp, root, cell, capsys, trace=0):
    import run
    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                   "0.5", "--trace", str(trace)], checkout=tmp, root=root)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def test_files_dropped_into_a_copy_are_found_by_name(tmp_path, off_chip,
                                                      capsys):
    """A new configuration, traffic mix and per-layer metric, added as
    files and BENCHMARK.json entries only, are run; the result line has
    the contract's keys and the checks last."""
    root = make_checkout(tmp_path, [("smoke-dense", "b4s64", 1)],
                         "def read(rec):\n"
                         "    return 7.0 if rec['tokens_per_s'] else None\n")
    res = run_cell(tmp_path, root, "smoke-dense.b4s64", capsys, trace=1)
    assert res["correct"] is True
    assert list(res)[-1] == "checks"
    assert res["metrics"]["steps_traced"] == {"value": 7.0, "unit": "steps"}
    assert res["metrics"]["mfu"]["unit"] == "%"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(res["device"])
    res = run_cell(tmp_path, root, "smoke-dense.b4s64", capsys)
    assert set(res["metrics"]) == {"setup_s", "tokens_per_s", "peak_hbm_gb"}
    assert res["correct"] is True and res["failed"] == 0


@pytest.mark.parametrize("config", ["smoke-dense", "smoke-ssm"])
def test_control_in_the_programs_place_is_not_correct(tmp_path, off_chip,
                                                      config):
    """At a tiny size the fp8 control fails a limit the program passes."""
    import jax
    from check import gaps, judge
    make_checkout(tmp_path, [(config, "b4s64", 1)])
    spec = harness.load_spec(f"{config}.b4s64", tmp_path,
                             tmp_path / "benchmarks" / "chip")
    devs = jax.devices()[:1]
    cell = harness.Cell(spec, devs, SEED, log=lambda *a: None)
    prog = cell.check_steps()
    canon = cell.canon
    cell.free()
    ref = harness.reference_readings(spec, canon, devs, SEED)
    control = harness.reference_readings(spec, canon, devs, SEED, "fp8")
    assert judge(gaps(prog, ref), SMOKE_LIMITS)[0] is True
    assert judge(gaps(control, ref), SMOKE_LIMITS)[0] is False


def _unchanged_state(orig):
    def make(*a, **k):
        step = orig(*a, **k)

        def broken(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return broken
    return make


def _half_batch(orig):
    def make(*a, **k):
        loss = orig(*a, **k)

        def broken(params, batch):
            return loss(params, {n: v[: v.shape[0] // 2]
                                 for n, v in batch.items()})
        return broken
    return make


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_a_broken_step_is_not_correct(tmp_path, off_chip, capsys,
                                      monkeypatch, fault):
    from repro.train import steps
    if fault == "unchanged_state":
        monkeypatch.setattr(steps, "make_train_step",
                            _unchanged_state(steps.make_train_step))
    else:
        monkeypatch.setattr(steps, "make_loss_fn",
                            _half_batch(steps.make_loss_fn))
    root = make_checkout(tmp_path, [("smoke-dense", "b4s64", 1)])
    res = run_cell(tmp_path, root, "smoke-dense.b4s64", capsys)
    assert res["correct"] is False


PIPELINE_CHILD = """
import json, sys
from pathlib import Path
sys.path.insert(0, {chip!r}); sys.path.insert(0, {src!r})
import types
import jax, jax.numpy as jnp
import harness, run
from repro.parallel import pipeline
from repro.train import steps
harness.require_chips = lambda n: jax.devices()[:n]
harness.peak_for = lambda kind, root=None: {{"bf16_flops": 197e12}}
harness.configure_jax = lambda: {{"compiled": 0, "cached": 0,
                                  "cache_dir": "off"}}
fault = {fault!r}
if fault == "no_hops":
    # the stage-to-stage hop over the pod axis delivers nothing
    pipeline.jnp = types.SimpleNamespace(**dict(
        vars(jnp), roll=lambda x, s, axis=0: jnp.zeros_like(x)))
elif fault == "unchanged_state":
    make_step = steps.make_train_step

    def unchanged(*a, **k):
        step = make_step(*a, **k)
        return lambda state, batch: (state, step(state, batch)[1])
    steps.make_train_step = unchanged
elif fault == "half_batch":
    # the first half of the rows, twice: the mean over half of the batch
    make_loss = pipeline.make_pp_loss_fn

    def first_half(v):
        rows = v.reshape((-1,) + v.shape[2:])
        half = rows[: rows.shape[0] // 2]
        return jnp.concatenate([half, half]).reshape(v.shape)

    def halved(*a, **k):
        loss = make_loss(*a, **k)
        return lambda params, batch: loss(
            params, {{n: first_half(v) for n, v in batch.items()}})
    pipeline.make_pp_loss_fn = halved
tmp = Path({tmp!r})
sys.exit(run.main(["--workload", "smoke-dense.pp2-b8s64", "--seed",
                   "{seed}", "--seconds", "0.5", "--trace", "{trace}"],
                  checkout=tmp, root=tmp / "benchmarks" / "chip"))
"""


@pytest.mark.parametrize("fault", [None, "no_hops", "unchanged_state",
                                   "half_batch"],
                         ids=["pipeline", "pipeline_without_hops",
                              "pipeline_unchanged_state",
                              "pipeline_half_batch"])
def test_pipeline_cell_and_a_lost_exchange(tmp_path, fault):
    """The pp=2 plan on four CPU devices checks correct, traced, and its
    record gives the predictor's step time to its reader; with the
    pod-axis hop left out, the state returned unchanged, or half of the
    batch left out, it does not check correct."""
    make_checkout(tmp_path, [("smoke-dense", "pp2-b8s64", 4)])
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in PIPELINE_READERS:
            m["workloads"].append("smoke-dense.pp2-b8s64")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    code = PIPELINE_CHILD.format(chip=str(tmp_path / "benchmarks" / "chip"),
                                 src=str(REPO / "src"), tmp=str(tmp_path),
                                 fault=fault, seed=SEED,
                                 trace=int(fault is None))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"] is (fault is None), res["checks"]
    if fault is None:
        # a CPU trace has no device plane: only the host clock's reader
        assert res["metrics"]["predictor_err"]["value"] > 0
        assert not {"stage_idle_max", "collective_exposed_ms"} & \
            set(res["metrics"])
