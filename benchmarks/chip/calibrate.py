"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11 12 ... [--control-seeds 3] [--out file.jsonl]

For every seed, in one process: the program's first steps through the
cell's normal path (no measured window), the plain reference, and the
gaps between them.  For the first ``--control-seeds`` seeds also the
control (the reference in fp8 put in the program's place) and the planted
faults (half of the batch left out; on several chips, the gradient
exchange left out), each against the reference.  The benchmark's own runs
never run this.  One JSON line per seed and reading.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def readings(spec, devices, seed, controls: bool):
    from check import gaps
    t0 = time.perf_counter()
    cell = harness.Cell(spec, devices, seed, log=lambda *a: None)
    prog = cell.check_steps()
    canon = cell.canon
    cell.free()
    del cell
    t1 = time.perf_counter()
    ref = harness.reference_readings(spec, canon, devices, seed)
    t2 = time.perf_counter()
    out = [{"seed": seed, "side": "program", **gaps(prog, ref),
            "losses": prog["losses"], "ref_losses": ref["losses"],
            "program_s": t1 - t0, "reference_s": t2 - t1}]
    if controls:
        faults = [("control_fp8", "fp8", None)]
        if spec.traffic["global_batch"] > 1:
            faults.append(("half_batch", "fp32", "half_batch"))
        if len(devices) > 1:
            faults.append(("no_exchange", "fp32", "no_exchange"))
        for side, precision, fault in faults:
            other = harness.reference_readings(spec, canon, devices, seed,
                                               precision, fault)
            out.append({"seed": seed, "side": side, **gaps(other, ref),
                        "losses": other["losses"]})
    return out


def main(argv=None, checkout=harness.CHECKOUT, root=harness.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    harness.add_paths(checkout, root)
    spec = harness.load_spec(args.workload, checkout, root)
    devices = harness.require_chips(spec.chips)
    harness.configure_jax()
    sink = open(args.out, "a") if args.out else None
    for i, seed in enumerate(args.seeds):
        for line in readings(spec, devices, seed, i < args.control_seeds):
            text = json.dumps(line)
            print(text, flush=True)
            if sink:
                sink.write(text + "\n")
                sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
