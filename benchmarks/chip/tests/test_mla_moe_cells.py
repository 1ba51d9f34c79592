"""CPU tests of the Moonlight cell's pieces (no TPU is touched).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

- the readers of the latent attention, attention core and expert scopes
  on a synthetic trace, and the experts' roofline share from a synthetic
  routed-row counter;
- nothing to read without the scopes or the counter, as from a program
  that has neither;
- the FLOP counts of a token and of the grouped matmuls, and the matmul
  parameters the program holds;
- a whole run of a smoke-size configuration through the program and the
  plain reference, traced, and the fp8 control in the program's place
  not correct.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
REPO = CHIP.parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(CHIP / "tests"))

import harness  # noqa: E402
import scope_time  # noqa: E402
from test_bench_chip import (SEED, make_checkout,  # noqa: E402,F401
                             off_chip, run_cell)

PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
# at the smoke width (256 tokens, top 4 of 16) the bf16 program's rounding
# flips a few tokens' choices, and with them the balancing bias each side
# computes from its own scores: over four seeds it read at most 2.71e-3,
# 1.10e-2 and 5.64e-3 (seed 12's change; this test's seed reads 2.71e-3,
# 7.84e-3, 3.91e-3), the fp8 control at least 2.03e-3, 4.81e-2, 1.88e-2
SMOKE_LIMITS = {"loss_gap": 3e-3, "grad_gap": 2e-2, "change_gap": 5e-3}
CELL = "smoke-mla-moe.b4s64"
NEW = ("mla_ms", "attn_core_ms", "moe_route_ms", "moe_experts_ms",
       "moe_experts_roofline")


def reader(name):
    return harness.load_module(CHIP / "metrics" / f"{name}.py").read


def _rec():
    """Two steps on one device: 40 ns of ``mla``, 20 of ``attn_core``, 10
    of ``moe_route``, 30 of ``moe_experts`` and 5 unscoped."""
    ops = {"fusion.1": 40.0, "fusion.2": 20.0, "sort.3": 10.0,
           "ragged-dot.4": 30.0, "copy.5": 5.0}
    dev = {"busy_ns": 105.0, "ops_ns": ops, "gaps": [],
           "collective_ns": 0, "collective_exposed_ns": 0}
    return {"trace": {"window_ns": 110, "steps": 2, "host_spans": [],
                      "devices": {0: dev}}, "peak": PEAK}


TABLE = {"fusion.1": "mla", "fusion.2": "attn_core", "sort.3": "moe_route",
         "ragged-dot.4": "moe_experts"}


@pytest.fixture
def table(monkeypatch):
    monkeypatch.setattr(scope_time, "scope_table", lambda: TABLE)


@pytest.mark.parametrize("name, want", [
    ("mla_ms", 20e-6), ("attn_core_ms", 10e-6), ("moe_route_ms", 5e-6),
    ("moe_experts_ms", 15e-6)])
def test_scope_readers_on_a_synthetic_trace(table, name, want):
    assert reader(name)(_rec()) == pytest.approx(want)


def _geometry(rows):
    return {"moe_rows": rows, "moe_rows_max": rows // 8, "d_model": 2048,
            "d_expert": 1408, "experts_held": 8, "moe_layers": 4,
            "passes": 4}


def test_roofline_share_from_the_routed_rows(table, monkeypatch):
    from repro.obs import counters
    fl = harness.load_module(CHIP / "flops" / "mla_moe.py")
    monkeypatch.setattr(counters, "_STEPS", [_geometry(24_000),
                                            _geometry(25_000)])
    rec = _rec()
    rec["trace"]["devices"][0]["ops_ns"]["ragged-dot.4"] = 2 * 30e6  # 30 ms
    flops = [fl.grouped_matmul_flops(r, 2048, 1408, 4)
             for r in (24_000, 25_000)]
    bytes_ = [fl.grouped_matmul_bytes(r, 2048, 1408, 8, 4)
              for r in (24_000, 25_000)]
    assert flops[0] == 2 * 24_000 * 2048 * 1408 * 12
    least = [max(f / PEAK["bf16_flops"], b / PEAK["hbm_bytes_per_s"])
             for f, b in zip(flops, bytes_)]
    got = reader("moe_experts_roofline")(rec)
    assert got == pytest.approx(100 * sum(least) / 2 / 30e-3)
    assert 0 < got < 100


def test_nothing_to_read_without_scopes_or_counters(monkeypatch):
    from repro.obs import counters
    monkeypatch.setattr(scope_time, "scope_table",
                        lambda: {"fusion.1": "attn"})
    for name in NEW:
        assert reader(name)(_rec()) is None
    monkeypatch.setattr(scope_time, "scope_table", lambda: TABLE)
    monkeypatch.setattr(counters, "_STEPS", [])
    assert reader("moe_experts_roofline")(_rec()) is None
    assert reader("moe_experts_roofline")({"trace": None, "peak": PEAK}) \
        is None


def test_flops_of_a_token_at_the_chips_share():
    fl = harness.load_module(CHIP / "flops" / "mla_moe.py")
    cfg = json.loads((CHIP / "configs" / "moonlight-16b-a3b-5l.json")
                     .read_text())
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    expert = 3 * 2048 * 1408
    moe = 2048 * 64 + 2 * expert + 6 * 8 / 64 * expert
    want = 5 * attn + 3 * 2048 * 11264 + 4 * moe + 2048 * 20480
    assert fl.matmul_params(cfg) == pytest.approx(want)
    assert fl.per_token(cfg, 8192) == pytest.approx(
        6 * want + 6 * 5 * 16 * 320 * 8193 / 2)


def test_held_flops_count_the_matmul_params_the_program_holds():
    """The program's matmul leaves (latent attention's projections, the
    router and every held expert whole) add up to ``held_matmul_params``;
    a token's count takes 6 x 8 / 64 of one expert for the 8 held."""
    import jax
    from reference.core import leaf_names
    from repro.models import registry
    fl = harness.load_module(CHIP / "flops" / "mla_moe.py")
    cfg = json.loads((CHIP / "configs" / "moonlight-16b-a3b-5l.json")
                     .read_text())
    reg = cfg["registry"]
    bundle = registry.get_bundle(reg["arch"], **reg["overrides"])
    shapes = jax.eval_shape(lambda k: bundle.init(k, bundle.cfg),
                            jax.random.PRNGKey(0))
    leaves = {"wq", "wkv_a", "wkv_b", "wo", "w_gate", "w_up", "w_down",
              "router", "unembed"}
    held = sum(x.size for n, x in zip(leaf_names(shapes),
                                      jax.tree.leaves(shapes))
               if n.split("/")[-1] in leaves)
    assert fl.held_matmul_params(cfg) == held
    expert = 3 * 2048 * 1408
    assert held - fl.matmul_params(cfg) == pytest.approx(
        4 * (8 - 6 * 8 / 64) * expert)


def _checkout(tmp_path):
    root = make_checkout(tmp_path, [("smoke-mla-moe", "b4s64", 1)])
    (root / "limits" / f"{CELL}.json").write_text(
        json.dumps({"limits": SMOKE_LIMITS}))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"].append(CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_whole_smoke_run_checks_correct_and_reads_the_new_metrics(
        tmp_path, off_chip, capsys):
    root = _checkout(tmp_path)
    res = run_cell(tmp_path, root, CELL, capsys, trace=1)
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["mfu"]["unit"] == "%"
    res = run_cell(tmp_path, root, CELL, capsys)
    assert res["correct"] is True and res["failed"] == 0


def test_control_in_the_programs_place_is_not_correct(tmp_path, off_chip):
    import jax
    from check import gaps, judge
    spec = harness.load_spec(CELL, tmp_path, _checkout(tmp_path))
    devs = jax.devices()[:1]
    cell = harness.Cell(spec, devs, SEED, log=lambda *a: None)
    prog = cell.check_steps()
    canon = cell.canon
    cell.free()
    ref = harness.reference_readings(spec, canon, devs, SEED)
    control = harness.reference_readings(spec, canon, devs, SEED, "fp8")
    assert judge(gaps(prog, ref), SMOKE_LIMITS)[0] is True, gaps(prog, ref)
    assert judge(gaps(control, ref), SMOKE_LIMITS)[0] is False
