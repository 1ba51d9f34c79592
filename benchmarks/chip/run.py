"""The on-chip benchmark: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up (imports, the plan search, the Trainer, the benchmark's weights,
the first ``check_steps`` steps, which compile or load the step from the
checkout's compile cache) is timed as ``setup_s``.  Then ``Trainer.run(1)``
runs until ``--seconds`` have passed.  With ``--trace 1`` a few more steps
run under the profiler, and the per-layer metrics are read from the trace
and the run's record.  Last, the training state is freed and the plain
reference repeats the first steps from the same seed; the comparison
decides ``correct``.

The last stdout line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``breakdown`` with ``--trace 1``) and
``checks`` (each number compared, with its limit).  Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import trace_reduce  # noqa: E402


def log(*a):
    print("[bench]", *a, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _breakdown(reduced):
    """The ops with the most device time (self time, seconds per device)
    and the longest idle gaps of the first device, by host span."""
    devs = reduced["devices"]
    ops = {}
    for d in devs.values():
        for name, ns in d["ops_ns"].items():
            ops[name] = ops.get(name, 0.0) + ns / 1e9 / len(devs)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    first = min(devs)
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": trace_reduce.gap_spans(reduced, first, 10)}


def main(argv=None, checkout=harness.CHECKOUT, root=harness.ROOT,
         t_start=T_START) -> int:
    args = parse(argv)
    harness.add_paths(checkout, root)
    spec = harness.load_spec(args.workload, checkout, root)
    devices = harness.require_chips(spec.chips)
    import jax
    programs = harness.configure_jax()
    dev0 = devices[0]
    peak = harness.peak_for(dev0.device_kind, root)
    limits = json.loads(spec.limits_path.read_text())["limits"]
    log(f"{spec.name}: {dev0.device_kind} x {len(devices)}, seed "
        f"{args.seed}, compile cache {programs['cache_dir']}")

    cell = harness.Cell(spec, devices, args.seed, log=log)
    prog = cell.check_steps()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s!r} s; check-step losses {prog['losses']!r}")

    before = dict(programs)
    steps, elapsed, losses = cell.window(args.seconds)
    tr = spec.traffic
    tokens = steps * tr["global_batch"] * tr["seq_len"]
    rec = {"tokens_per_s": tokens / elapsed, "step_s": elapsed / steps,
           "chips": spec.chips, "peak": peak, "pods": cell.pods,
           "flops_per_token": spec.module("flops", spec.family).per_token(
               spec.cfg, tr["seq_len"]),
           "predicted_step_s": (cell.prediction.iter_time
                                if cell.prediction is not None else None),
           "trace": None}
    log(f"window: {steps} steps in {elapsed!r} s; programs compiled and "
        f"loaded in set-up {before['compiled']}, {before['cached']}; in the "
        f"window {programs['compiled'] - before['compiled']}, "
        f"{programs['cached'] - before['cached']}")
    if args.trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            losses += cell.traced(tr["trace_steps"], tdir)
            rec["trace"] = trace_reduce.reduce_dir(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    mem_peak = cell.memory_peak()
    if cell.prediction is not None:
        rec["predicted_peak_gb"] = max(cell.prediction.peak_mem_gb)
        rec["peak_hbm_gb"] = mem_peak / 1e9
    log(f"memory_stats of the first chip {devices[0].memory_stats()}")
    canon = cell.canon
    cell.free()
    del cell

    ref = harness.reference_readings(spec, canon, devices, args.seed)
    from check import gaps, judge
    values = gaps(prog, ref)
    n_losses = len(losses) + len(prog["losses"])
    failed = n_losses - harness.finite(losses + prog["losses"])
    correct, checks = judge(values, limits)
    checks["non_finite_losses"] = {"value": failed, "limit": 0}
    correct = correct and failed == 0

    if args.trace:
        metrics = {}
        for m in spec.per_layer:
            v = spec.module("metrics", m["name"]).read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"setup_s": setup_s, "tokens_per_s": rec["tokens_per_s"],
               "peak_hbm_gb": mem_peak / 1e9}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    out = {"correct": correct, "attempted": n_losses, "failed": failed,
           "metrics": metrics, "device": device}
    if rec["trace"] is not None and rec["trace"]["devices"]:
        red = rec["trace"]
        busy = [d["busy_ns"] for d in red["devices"].values()]
        device["busy_s"] = sum(busy) / len(busy) / 1e9
        device["window_s"] = red["window_ns"] / 1e9
        out["breakdown"] = _breakdown(red)
    out["checks"] = checks
    for k, v in checks.items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
