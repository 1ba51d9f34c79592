"""CPU tests of the per-layer readers of named scopes and host spans
(``scope_time.py``, ``metrics/*_ms.py``; no TPU is touched).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

- each reader on a synthetic trace: the scoped and unscoped times add up
  to the busy time, and the input wait counts only idle time inside the
  Trainer's input spans;
- nothing to read without a trace, a scope table or the spans (as from a
  program that has none);
- two steps of ``danube3-4b-2l.b4s2k`` traced on a v5e, with the scope
  table the program kept for them;
- a whole tiny run with ``--trace 1`` reads every new metric from the
  table the program noted in the same process.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
REPO = CHIP.parents[1]
DATA = CHIP / "tests" / "data"
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(CHIP / "tests"))

import harness  # noqa: E402
import scope_time  # noqa: E402
import trace_reduce  # noqa: E402

SCOPED = ["embed_ms", "attn_ms", "mlp_ms", "ssm_block_ms", "ssm_scan_ms",
          "head_loss_ms", "optimizer_ms"]
NEW = SCOPED + ["unscoped_ms", "input_wait_ms"]
TRACE = DATA / "trace_v5e_scopes"
CELL = "danube3-4b-2l.b4s2k"


def reader(name):
    return harness.load_module(CHIP / "metrics" / f"{name}.py").read


def _rec():
    """Two steps of 100 ns on two devices; the first step waits 10 ns for
    its batch, the second 4 ns on its put; a gap of 6 ns lies outside
    the input spans."""
    host = [("train", 0, 100), ("trainer.batch", 0, 10),
            ("train", 100, 200), ("trainer.put", 100, 104),
            ("trainer.sync", 150, 156)]
    ops = {"fusion.1": 30.0, "fusion.2": 20.0, "while.3": 10.0,
           "fusion.4": 20.0, "copy.5": 10.0}
    gaps = [[0, 10], [100, 104], [150, 156]]
    dev = {"busy_ns": 90.0, "ops_ns": ops, "gaps": gaps,
           "collective_ns": 0, "collective_exposed_ns": 0}
    return {"trace": {"window_ns": 200, "steps": 2, "host_spans": host,
                      "devices": {0: dev, 1: dict(dev)}}}


TABLE = {"fusion.1": "attn", "fusion.2": "mlp", "while.3": "ssm_scan",
         "fusion.4": "ssm_scan", "unused.9": "embed"}


@pytest.fixture
def table(monkeypatch):
    monkeypatch.setattr(scope_time, "scope_table", lambda: TABLE)


@pytest.mark.parametrize("name, want", [
    ("attn_ms", 15e-6), ("mlp_ms", 10e-6), ("ssm_scan_ms", 15e-6),
    ("embed_ms", 0.0), ("unscoped_ms", 5e-6), ("input_wait_ms", 7e-6),
    ("ssm_block_ms", None), ("head_loss_ms", None), ("optimizer_ms", None),
])
def test_readers_on_a_synthetic_trace(table, name, want):
    got = reader(name)(_rec())
    assert got == (None if want is None else pytest.approx(want))


def test_scoped_and_unscoped_add_up_to_busy(table):
    rec = _rec()
    total = sum(v for v in (reader(n)(rec) for n in NEW[:-1]) if v)
    assert total == pytest.approx(90.0 / 2 / 1e6)


def test_input_wait_is_idle_inside_the_input_spans_only():
    rec = _rec()
    rec["trace"]["devices"][1]["gaps"] = [[150, 156]]
    # device 0 waits 10 + 4 ns, device 1 none: 7 ns per device, 2 steps
    assert scope_time.idle_in_spans_ms(rec) == pytest.approx(3.5e-6)
    assert scope_time.idle_in_spans_ms(rec, ("trainer.sync",)) == \
        pytest.approx(3e-6)


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_without_trace_table_or_spans(monkeypatch, name):
    assert reader(name)({"trace": None}) is None
    monkeypatch.setattr(scope_time, "scope_table", lambda: None)
    rec = _rec()
    rec["trace"]["host_spans"] = [s for s in rec["trace"]["host_spans"]
                                  if not s[0].startswith("trainer.")]
    assert reader(name)(rec) is None


def test_a_program_without_the_registry_has_no_table(monkeypatch):
    import repro.obs
    monkeypatch.setitem(sys.modules, "repro.obs.scopes", None)
    monkeypatch.delattr(repro.obs, "scopes", raising=False)
    assert scope_time.scope_table() is None


@pytest.fixture(scope="module")
def recorded():
    red = trace_reduce.reduce_dir(TRACE)
    table = json.loads((TRACE / f"{CELL}.scopes.json").read_text())
    return {"trace": red}, table


def test_readers_on_a_trace_recorded_on_a_v5e(recorded, monkeypatch):
    """Two traced steps of the dense cell on one v5e chip: every op's self
    time lands in a scope or in ``unscoped_ms``, which stays under a tenth
    of the busy time, and the input wait is part of the idle time."""
    rec, table = recorded
    monkeypatch.setattr(scope_time, "scope_table", lambda: table)
    tr = rec["trace"]
    d = tr["devices"][0]
    busy_ms = d["busy_ns"] / tr["steps"] / 1e6
    got = {n: reader(n)(rec) for n in NEW}
    assert got["ssm_block_ms"] is None and got["ssm_scan_ms"] is None
    scoped = [got[n] for n in SCOPED if got[n] is not None]
    assert len(scoped) == 5 and min(scoped) > 0
    assert sum(scoped) + got["unscoped_ms"] == pytest.approx(busy_ms,
                                                             rel=0.01)
    assert got["unscoped_ms"] <= 0.1 * busy_ms
    idle_ms = trace_reduce.length(d["gaps"]) / tr["steps"] / 1e6
    assert 0 < got["input_wait_ms"] <= idle_ms
    names = {n for n, _, _ in tr["host_spans"]}
    assert {"trainer.batch", "trainer.put", "trainer.dispatch",
            "trainer.sync", "trainer.after"} <= names


def test_whole_tiny_run_reads_the_new_metrics(tmp_path, capsys,
                                              monkeypatch):
    """The readers, listed for a smoke cell, read the table the program
    noted in the same run.  A CPU trace has no device plane, so the run's
    reduced trace gets one: an op of each scope, 1 ms each step, one op
    of none, and the device idle inside each ``trainer.batch`` span."""
    import jax
    from repro.obs import scopes
    from test_bench_chip import make_checkout, run_cell
    monkeypatch.setattr(harness, "require_chips",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(harness, "peak_for",
                        lambda kind, root=None: {"bf16_flops": 197e12})
    monkeypatch.setattr(harness, "configure_jax", lambda: {
        "compiled": 0, "cached": 0, "cache_dir": "off"})
    reduce_dir = trace_reduce.reduce_dir

    def with_device(tdir, step_name="train"):
        red = reduce_dir(tdir, step_name)
        firsts = {}
        for op, scope in scopes.table("train_step").items():
            firsts.setdefault(scope, op)
        ops = {op: 1e6 * red["steps"] for op in firsts.values()}
        ops["unscoped.0"] = 1e6 * red["steps"]
        red["devices"] = {0: {"busy_ns": sum(ops.values()), "ops_ns": ops,
                              "gaps": [[s, e] for n, s, e in
                                       red["host_spans"]
                                       if n == "trainer.batch"]}}
        return red

    monkeypatch.setattr(trace_reduce, "reduce_dir", with_device)
    root = make_checkout(tmp_path, [("smoke-dense", "b4s64", 1)])
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"].append("smoke-dense.b4s64")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    res = run_cell(tmp_path, root, "smoke-dense.b4s64", capsys, trace=1)
    assert res["correct"] is True
    got = {n: res["metrics"][n]["value"] for n in NEW if n in res["metrics"]}
    assert set(got) == set(NEW) - {"ssm_block_ms", "ssm_scan_ms"}
    assert all(got[n] == pytest.approx(1.0) for n in
               ("embed_ms", "attn_ms", "mlp_ms", "head_loss_ms",
                "optimizer_ms", "unscoped_ms"))
    assert got["input_wait_ms"] > 0
