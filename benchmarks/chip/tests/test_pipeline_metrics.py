"""CPU tests of the pipeline's and the planner's readers
(``metrics/stage_idle_max.py``, ``collective_exposed_ms.py``,
``predictor_err.py``, ``predictor_mem_err.py``; no TPU is touched).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

- two steps of ``danube3-4b-4l.pp2-b16s2k`` traced on a 2x2 v5e, with the
  record the run keeps beside them (the stages' chips, the planner's
  predictions, the measured peak): each reader reads a number, and the
  number follows from the trace; with the scope table the program kept,
  every op's time lands in a scope or in ``unscoped_ms``;
- on the one-chip trace of ``danube3-4b-2l.b4s2k``, with the record a
  one-chip run keeps, none of them reads anything;
- ``predictor_mem_err`` on a record with a plan and on one without.
"""
from __future__ import annotations

import gzip
import json
import shutil
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
DATA = CHIP / "tests" / "data"
sys.path.insert(0, str(CHIP))

import harness  # noqa: E402
import scope_time  # noqa: E402
import trace_reduce  # noqa: E402

READERS = ("stage_idle_max", "collective_exposed_ms", "predictor_err",
           "predictor_mem_err")
CELL = "danube3-4b-4l.pp2-b16s2k"
TRACE4 = DATA / "trace_v5e_4chip"


def reader(name):
    return harness.load_module(CHIP / "metrics" / f"{name}.py").read


@pytest.fixture(scope="module")
def four_chips(tmp_path_factory):
    """The reduced four-chip trace with the run's record around it; the
    window's seconds per step stand for the measured step time.  The trace
    is kept compressed."""
    tdir = tmp_path_factory.mktemp("trace_v5e_4chip")
    with gzip.open(TRACE4 / f"{CELL}.xplane.pb.gz") as src, \
            open(tdir / f"{CELL}.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    red = trace_reduce.reduce_dir(tdir)
    rec = json.loads((TRACE4 / f"{CELL}.record.json").read_text())
    rec["trace"] = red
    rec["step_s"] = red["window_ns"] / red["steps"] / 1e9
    return rec


def test_the_four_chip_trace_holds_both_stages(four_chips):
    tr = four_chips["trace"]
    assert tr["steps"] == 2 and sorted(tr["devices"]) == [0, 1, 2, 3]
    chips = sorted(d for devs in four_chips["pods"].values() for d in devs)
    assert chips == [0, 1, 2, 3] and len(four_chips["pods"]) == 2
    for d in tr["devices"].values():
        assert d["collective_ns"] > 0         # the hop and the exchange
        assert 0 < d["busy_ns"] < tr["window_ns"]


def test_stage_idle_max_is_the_idlest_stage(four_chips):
    tr = four_chips["trace"]
    idle = [1 - sum(tr["devices"][d]["busy_ns"] for d in devs)
            / len(devs) / tr["window_ns"]
            for devs in four_chips["pods"].values()]
    got = reader("stage_idle_max")(four_chips)
    assert got == pytest.approx(100 * max(idle))
    assert 0 < got < 100


def test_collective_exposed_is_part_of_the_collective_time(four_chips):
    tr = four_chips["trace"]
    got = reader("collective_exposed_ms")(four_chips)
    total = [d["collective_ns"] for d in tr["devices"].values()]
    assert 0 < got <= sum(total) / len(total) / tr["steps"] / 1e6


def test_predictor_errors_read_the_plan(four_chips):
    rec = four_chips
    step = reader("predictor_err")(rec)
    assert step == pytest.approx(
        100 * abs(rec["predicted_step_s"] / rec["step_s"] - 1))
    mem = reader("predictor_mem_err")(rec)
    assert mem == pytest.approx(
        100 * abs(rec["predicted_peak_gb"] / rec["peak_hbm_gb"] - 1))
    assert step > 0 and mem > 0


SCOPED = ("embed_ms", "attn_ms", "mlp_ms", "head_loss_ms", "optimizer_ms")


def test_scoped_readers_on_the_four_chip_trace(four_chips, monkeypatch):
    """Every scope the dense step has reads a number on four chips, and
    the scopes and ``unscoped_ms`` add up to the busy time per step, mean
    over the chips; the input wait is part of the idle time."""
    table = json.loads((TRACE4 / f"{CELL}.scopes.json").read_text())
    monkeypatch.setattr(scope_time, "scope_table", lambda: table)
    tr = four_chips["trace"]
    busy = [d["busy_ns"] for d in tr["devices"].values()]
    busy_ms = sum(busy) / len(busy) / tr["steps"] / 1e6
    got = {n: reader(n)(four_chips) for n in SCOPED + ("unscoped_ms",)}
    assert min(got.values()) > 0
    assert sum(got.values()) == pytest.approx(busy_ms, rel=0.01)
    idle_ms = tr["window_ns"] / tr["steps"] / 1e6 - busy_ms
    assert 0 < reader("input_wait_ms")(four_chips) <= idle_ms


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_on_one_chip(name):
    """A one-chip run's record: no stages, no prediction, one device in
    the trace."""
    red = trace_reduce.reduce_dir(DATA / "trace_v5e_1chip")
    rec = {"tokens_per_s": 30_000.0, "step_s": 0.27, "chips": 1,
           "pods": None, "predicted_step_s": None, "trace": red}
    assert reader(name)(rec) is None
    assert reader(name)(dict(rec, trace=None)) is None


def test_predictor_mem_err_with_and_without_a_plan():
    read = reader("predictor_mem_err")
    assert read({"predicted_peak_gb": 5.5, "peak_hbm_gb": 11.0}) == \
        pytest.approx(50.0)
    assert read({"predicted_peak_gb": 13.2, "peak_hbm_gb": 11.0}) == \
        pytest.approx(20.0)
    assert read({"predicted_step_s": None, "peak_hbm_gb": None}) is None
    assert read({"predicted_peak_gb": 5.5}) is None
