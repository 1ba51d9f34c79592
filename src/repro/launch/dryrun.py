import os
# A CPU tool: 512 host devices stand in for the pods, and JAX_PLATFORMS=cpu
# keeps this process and the per-cell children it spawns off any TPU.
# MUST precede any jax import: jax locks platform and device count on init.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input-shape x mesh) cell:
    lowered  = jax.jit(step, in_shardings=..., out_shardings=...).lower(SDS...)
    compiled = lowered.compile()
    print(compiled.memory_analysis())   # proves it fits 16 GB/chip
    print(compiled.cost_analysis())     # FLOPs/bytes for the roofline
plus collective-volume parsing of the partitioned HLO.

Artifacts: benchmarks/artifacts/dryrun/<arch>__<shape>__<mesh>.json
Usage:
    python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all [--skip-existing]
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs.shapes import SHAPES
from repro.launch import cells as cells_mod
from repro.launch import compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.utils import hlo as hlo_util
from repro.utils.roofline import Roofline

ART = Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts" / "dryrun"


def record_profile(rec, cell, mesh_kind: str, n_chips: int):
    """Write HLO-derived costs into the profile database as a calibration
    source for the ProfiledCostModel (device_kind 'hlo' = device-independent
    compiled-counts, distinct from wall-time measurements)."""
    from repro.core import costmodel
    from repro.profile.model import CALIB_DEVICE
    from repro.profile.store import ProfileStore

    cfg, shp = cell.cfg, cell.shape
    # open/save per cell, not per run: --all isolates every cell in its own
    # subprocess (SPMD CHECK failures are C++ aborts), so this process may
    # only ever see one cell and the file is the merge point
    store = ProfileStore.for_device(CALIB_DEVICE)
    key = {"arch": cfg.name, "shape": rec["shape"], "mesh": mesh_kind}
    store.put(CALIB_DEVICE, "hlo_cost", key,
              {"flops_per_device": rec["cost"]["flops_per_device"],
               "bytes_per_device": rec["cost"]["bytes_per_device"],
               "traffic_per_device":
                   rec["cost"]["traffic_per_device_corrected"]})
    if shp.step == "train":
        tokens = shp.global_batch * shp.seq_len
        per_tok = rec["cost"]["flops_per_device"] * n_chips / tokens
        ratio = costmodel.calibrate(cfg, shp.seq_len, per_tok)
        store.put(CALIB_DEVICE, "calibration",
                  {"arch": cfg.name, "seq_len": shp.seq_len},
                  {"hlo_flops_per_token": per_tok, "ratio": ratio})
        # per-layer fwd FLOPs/token: strip embedding, undo the 3x fwd+bwd
        layer_f = ((per_tok / 3.0 - costmodel.embedding_flops(cfg))
                   / max(cfg.num_layers, 1))
        if layer_f > 0:
            store.put(CALIB_DEVICE, "layer_cost",
                      {"arch": cfg.name, "seq_len": shp.seq_len},
                      {"flops_fwd": layer_f})
    store.save()


def model_flops_total(cfg, shape) -> float:
    """6*N*D yardstick: fwd+bwd for train (3x fwd), fwd for serving."""
    if shape.step == "train":
        per_tok = cfg.flops_per_token(shape.seq_len) * 3.0
        tokens = shape.global_batch * shape.seq_len
    elif shape.step == "prefill":
        per_tok = cfg.flops_per_token(shape.seq_len)
        tokens = shape.global_batch * shape.seq_len
    else:  # decode: one token per sequence
        per_tok = cfg.flops_per_token(shape.seq_len)
        tokens = shape.global_batch * 1
    return per_tok * tokens


def _probe_costs(arch, shape_name, mesh, n_layers_probe, strategy="tp",
                 extra_overrides=None):
    """Compile an UNROLLED probe with n_layers_probe layers; return
    (flops, bytes, traffic) per device.  Two probes (L=1, L=2) give exact
    per-layer costs: XLA's cost_analysis counts scan bodies once, so the
    full-depth cell under-reports; corrected(L) = 2*T1 - T2 + L*(T2 - T1).
    This is the paper's own 'profile small, predict big' methodology applied
    to compiled HLO (DESIGN.md §2)."""
    ov = dict(extra_overrides or {})
    ov.update({"num_layers": n_layers_probe, "scan_layers": False,
               "attn_chunk": 0})
    cfg0 = registry.get_config(arch)
    if cfg0.family == "encdec":
        ov["n_encoder_layers"] = n_layers_probe
    cell = cells_mod.build_cell(arch, shape_name, False,
                                extra_overrides=ov, strategy=strategy)
    compiled = cell.lower(mesh).compile()
    cost = compiled.cost_analysis()
    stats = hlo_util.collective_stats(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            stats.total_traffic)


def probe_corrected(arch, shape_name, mesh, L, strategy="tp",
                    extra_overrides=None):
    """corrected(L) = base + L*per_layer, solved from two unrolled probes at
    depths (a, 2a) — a = pattern length for hybrid archs so every probe sees
    a full block cycle."""
    cfg0 = registry.get_config(arch)
    a = len(cfg0.block_pattern) if cfg0.block_pattern else 1
    pa = _probe_costs(arch, shape_name, mesh, a, strategy, extra_overrides)
    pb = _probe_costs(arch, shape_name, mesh, 2 * a, strategy,
                      extra_overrides)
    out = []
    for x, y in zip(pa, pb):
        per = (y - x) / a
        base = x - a * per
        out.append(base + L * per)
    return tuple(out)


def run_cell(arch: str, shape_name: str, mesh_kind: str, mesh, verbose=True,
             strategy: str = "tp", extra_overrides=None, grad_accum: int = 1):
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "ok": False,
           "strategy": strategy}
    try:
        cell = cells_mod.build_cell(arch, shape_name, mesh_kind == "multi",
                                    extra_overrides=extra_overrides,
                                    strategy=strategy, grad_accum=grad_accum)
        if cell is None:
            rec.update(skipped=True, reason="shape inapplicable (quadratic "
                       "attention for long_500k) — see DESIGN.md §4")
            return rec
        rec["parallelism"] = cell.meta.get("parallelism", "")
        lowered = cell.lower(mesh)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo_text = compiled.as_text()
        # scan trip count: collectives inside while bodies replay per layer
        # (hybrid stacks scan over full pattern cycles)
        if cell.cfg.block_pattern:
            trip = cell.cfg.num_layers // len(cell.cfg.block_pattern)
        else:
            trip = cell.cfg.num_layers
        stats_raw = hlo_util.collective_stats(hlo_text)
        stats = hlo_util.collective_stats(
            hlo_text, body_scale=(trip if cell.cfg.scan_layers else 1.0))
        n_chips = 512 if mesh_kind == "multi" else 256
        raw = (float(cost.get("flops", 0.0)),
               float(cost.get("bytes accessed", 0.0)),
               stats_raw.total_traffic)
        corrected = (raw[0], raw[1], stats.total_traffic)
        if mesh_kind == "single" and cell.cfg.scan_layers:
            try:
                # probes fix scan-body undercounting of FLOPs/bytes (traffic
                # comes from body-scaled attribution on the real cell HLO —
                # unrolled probes can hit GSPMD resharding pathologies the
                # scanned cell doesn't have)
                corr = probe_corrected(arch, shape_name, mesh,
                                       cell.cfg.num_layers,
                                       strategy=strategy,
                                       extra_overrides=extra_overrides)
                corrected = (max(corr[0], raw[0]), max(corr[1], raw[1]),
                             corrected[2])
            except Exception as pe:  # noqa: BLE001
                rec["probe_error"] = f"{type(pe).__name__}: {pe}"
        rl = Roofline(
            flops_per_device=corrected[0],
            bytes_per_device=corrected[1],
            collective_traffic_per_device=corrected[2],
            n_chips=n_chips,
            model_flops_total=model_flops_total(cell.cfg, cell.shape))
        rec.update(
            ok=True,
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            mem_per_device={
                "argument_gb": round(mem.argument_size_in_bytes / 1e9, 3),
                "output_gb": round(mem.output_size_in_bytes / 1e9, 3),
                "temp_gb": round(mem.temp_size_in_bytes / 1e9, 3),
                "peak_gb": round((mem.argument_size_in_bytes
                                  + mem.temp_size_in_bytes) / 1e9, 3),
            },
            cost={"flops_per_device": corrected[0],
                  "bytes_per_device": corrected[1],
                  "flops_per_device_raw": raw[0],
                  "bytes_per_device_raw": raw[1],
                  "traffic_per_device_corrected": corrected[2]},
            collectives={
                "bytes_by_op": {k: round(v) for k, v in
                                stats.bytes_by_op.items()},
                "traffic_per_device": round(stats.total_traffic),
                "count_by_op": stats.count_by_op,
            },
            roofline=rl.row(),
            model_flops_total=rl.model_flops_total,
        )
        if verbose:
            print(f"  memory_analysis: args={rec['mem_per_device']['argument_gb']}GB "
                  f"temp={rec['mem_per_device']['temp_gb']}GB "
                  f"peak={rec['mem_per_device']['peak_gb']}GB")
            print(f"  cost_analysis: flops/dev={rec['cost']['flops_per_device']:.3e} "
                  f"bytes/dev={rec['cost']['bytes_per_device']:.3e}")
            print(f"  collectives: {rec['collectives']['count_by_op']} "
                  f"traffic/dev={stats.total_traffic/1e9:.3f}GB")
            print(f"  roofline: {rec['roofline']}")
        try:
            record_profile(rec, cell, mesh_kind, n_chips)
        except Exception as pe:  # noqa: BLE001 — profiling must not fail runs
            rec["profile_error"] = f"{type(pe).__name__}: {pe}"
    except Exception as e:  # noqa: BLE001 — record, continue the matrix
        rec.update(error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
        if verbose:
            print(f"  FAILED: {rec['error']}")
    rec["wall_s"] = round(time.time() - t0, 1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--strategy", default="tp", choices=["tp", "fsdp"],
                    help="tp = paper-faithful Megatron TP baseline; "
                         "fsdp = beyond-paper ZeRO-3 (§Perf)")
    ap.add_argument("--override", action="append", default=[],
                    help="ModelConfig override key=val (int/float/str)")
    ap.add_argument("--tag", default=None,
                    help="artifact suffix for perf-iteration variants")
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="microbatch the train step (activation memory)")
    args = ap.parse_args()
    compile_cache.enable()

    def parse_overrides():
        out = {}
        for kv in args.override:
            k, v = kv.split("=", 1)
            if v in ("True", "False"):
                out[k] = v == "True"
                continue
            try:
                out[k] = int(v)
            except ValueError:
                try:
                    out[k] = float(v)
                except ValueError:
                    out[k] = v
        return out or None

    ART.mkdir(parents=True, exist_ok=True)
    meshes = {}

    def get_mesh(kind):
        if kind not in meshes:
            meshes[kind] = make_production_mesh(multi_pod=(kind == "multi"))
        return meshes[kind]

    if args.all:
        # one subprocess per cell: an XLA SPMD-partitioner CHECK failure is a
        # C++ abort and would kill the whole matrix otherwise
        import subprocess
        import sys
        n_ok = n_skip = n_fail = 0
        for arch in registry.ARCH_IDS:
            for shape in SHAPES:
                for mk in ("single", "multi"):
                    out = ART / f"{arch}__{shape}__{mk}.json"
                    if args.skip_existing and out.exists():
                        prev = json.loads(out.read_text())
                        if prev.get("ok") or prev.get("skipped"):
                            n_ok += prev.get("ok", False)
                            n_skip += prev.get("skipped", False)
                            continue
                    print(f"[dryrun] {arch} x {shape} x {mk}", flush=True)
                    r = subprocess.run(
                        [sys.executable, "-m", "repro.launch.dryrun",
                         "--arch", arch, "--shape", shape, "--mesh", mk],
                        capture_output=True, text=True, timeout=3600)
                    if r.returncode != 0 and not out.exists():
                        out.write_text(json.dumps(
                            {"arch": arch, "shape": shape, "mesh": mk,
                             "ok": False,
                             "error": f"subprocess rc={r.returncode} "
                                      f"(compiler crash)",
                             "stderr_tail": r.stderr[-1500:]}, indent=1))
                    rec = json.loads(out.read_text())
                    for line in (r.stdout or "").splitlines():
                        if line.startswith("  "):
                            print(line, flush=True)
                    n_ok += rec.get("ok", False)
                    n_skip += rec.get("skipped", False)
                    n_fail += bool(rec.get("error"))
        print(f"[dryrun] done: ok={n_ok} skipped={n_skip} failed={n_fail}")
        return 0 if n_fail == 0 else 1

    arch, shape, mk = args.arch, args.shape, args.mesh
    suffix = f"__{args.tag}" if args.tag else ""
    out = ART / f"{arch}__{shape}__{mk}{suffix}.json"
    print(f"[dryrun] {arch} x {shape} x {mk} strategy={args.strategy}"
          + (f" tag={args.tag}" if args.tag else ""))
    rec = run_cell(arch, shape, mk, get_mesh(mk), strategy=args.strategy,
                   extra_overrides=parse_overrides(),
                   grad_accum=args.grad_accum)
    out.write_text(json.dumps(rec, indent=1))
    return 0 if not rec.get("error") else 1


if __name__ == "__main__":
    raise SystemExit(main())
