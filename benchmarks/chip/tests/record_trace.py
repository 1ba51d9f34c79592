"""Records a few traced steps of one cell on the chip, with the scope table
the program keeps for its train step, as test data for the readers.

    python3 benchmarks/chip/tests/record_trace.py --workload <cell> \
        --seed <n> --steps 2 --out <dir>

Writes ``<dir>/<cell>.xplane.pb`` (the profiler's trace of ``--steps``
steps, each in a ``train`` step span, after the cell's check steps) and
``<dir>/<cell>.scopes.json`` (``repro.obs.scopes.table("train_step")``)
and ``<dir>/<cell>.record.json`` (the pipeline stages' chips, the
planner's predicted step time and peak memory, and the fullest chip's
measured peak, as a run's record has them).  Needs the chips the cell
asks for.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    harness.add_paths()
    spec = harness.load_spec(args.workload)
    devices = harness.require_chips(spec.chips)
    harness.configure_jax()
    from repro.obs import scopes

    cell = harness.Cell(spec, devices, args.seed,
                        log=lambda *a: print(*a, file=sys.stderr))
    cell.check_steps()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    tdir = tempfile.mkdtemp(prefix="record_trace_")
    try:
        cell.traced(args.steps, tdir)
        pb = sorted(Path(tdir).rglob("*.xplane.pb"))[-1]
        shutil.copy(pb, out / f"{spec.name}.xplane.pb")
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    record = {"pods": cell.pods, "peak_hbm_gb": cell.memory_peak() / 1e9}
    if cell.prediction is not None:
        record["predicted_step_s"] = cell.prediction.iter_time
        record["predicted_peak_gb"] = max(cell.prediction.peak_mem_gb)
    (out / f"{spec.name}.record.json").write_text(
        json.dumps(record, indent=1, sort_keys=True) + "\n")
    table = scopes.table("train_step")
    (out / f"{spec.name}.scopes.json").write_text(
        json.dumps(table, indent=0, sort_keys=True) + "\n")
    print(json.dumps({"trace": str(out / f"{spec.name}.xplane.pb"),
                      "instructions": len(table)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
