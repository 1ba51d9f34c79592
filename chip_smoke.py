"""Smoke run of the training path on TPU: proves the system starts on the chip.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the pod-axis pipeline on one 4-chip host

One chip: h2o-danube-3-4b at its published widths, cut to 2 layers, trains
a few steps through the normal entry points (registry.get_bundle ->
Trainer -> Trainer.run) at global batch 4 x seq 2048.  Every loss must be
finite, and step 0's loss must agree with the same loss function run
unjitted at the highest matmul precision on the same params and batch.

Four chips (--four-chips, that phase only): the planner searches a pp=2 plan
over two 2-chip v5e islands for the same model at 4 layers, the Trainer
executes it on a ("pod", "data", "model") = (2, 2, 1) mesh, and its losses
must track the plain data-parallel step on a (4, 1) mesh; each stage's block
params must live on its own pod's chips.

The last stdout line is one JSON object naming the device.  Without a TPU
the script exits non-zero before it prints anything.  Step times are a
smoke reading, not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import statistics
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import cluster as cluster_mod, planner  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.mesh import make_train_mesh  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.train import steps as steps_mod  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig  # noqa: E402

ARCH = "h2o-danube-3-4b"

# Step 0 vs the unjitted loss.  Both runs hold params and activations in
# bf16; they differ only in XLA's fusion (which may skip intermediate bf16
# roundings) and reduction order.  One bf16 rounding (2^-8 relative) per
# logit moves a token's logsumexp - gold by about two such ulps, and the
# mean over tokens cannot move further: 2^-7 of the loss.
STEP0_RTOL = 2.0 ** -7
# Pipeline vs plain step, per step: the repo's bf16 contract for a
# re-grouped execution of the same step (docs/schedules.md, the cp ring).
PP_RTOL = 2e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _trainer(bundle, mesh, global_batch, seq_len, steps, ckpt_dir,
             cluster=None, plan=None) -> Trainer:
    # ckpt_every past the last step: no save is written
    return Trainer(bundle, mesh,
                   TrainerConfig(global_batch=global_batch, seq_len=seq_len,
                                 ckpt_dir=ckpt_dir, ckpt_every=steps + 1),
                   cluster=cluster, plan=plan)


def _run_steps(t: Trainer, steps: int):
    """One Trainer.run call per step: (losses, wall seconds per step)."""
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses += t.run(1)["losses"]
        secs.append(time.perf_counter() - t0)
    return losses, secs


def _peak_bytes(device):
    stats = device.memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def run_one_chip(bundle, *, global_batch: int, seq_len: int, steps: int,
                 device=None) -> dict:
    """Trains ``steps`` steps on one device; checks finite losses and step
    0's loss against the unjitted loss function."""
    device = device or jax.devices()[0]
    mesh = make_train_mesh(devices=[device])
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt_dir:
        t = _trainer(bundle, mesh, global_batch, seq_len, steps, ckpt_dir)
        batch = t.data.batch_at(t.step)
        loss_fn = steps_mod.make_loss_fn(bundle, t.rules)
        with jax.set_mesh(mesh), jax.default_matmul_precision("highest"):
            ref = float(loss_fn(t.state["params"], batch)[0])
        losses, secs = _run_steps(t, steps)
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    err = abs(losses[0] - ref)
    check(err <= STEP0_RTOL * abs(ref),
          f"step-0 loss {losses[0]!r} vs unjitted {ref!r}: |diff| {err!r} "
          f"> {STEP0_RTOL} x |ref|")
    return {"losses": losses, "step_s": secs, "step0_ref": ref,
            "step0_abs_err": err, "peak_bytes": _peak_bytes(device)}


def _two_island_cluster(chips_per_island: int):
    v5e = cluster_mod.TPU_V5E
    return cluster_mod.ClusterSpec(groups=tuple(
        cluster_mod.NodeGroup(dataclasses.replace(v5e, name=f"{v5e.name}-{i}"),
                              1, accel_per_node=chips_per_island)
        for i in range(2)))


def stage_placement(t: Trainer) -> dict:
    """{stage: set of device ids holding it}, read from every block
    param's addressable shards; raises if a shard spans stages or sits
    off its pod's chips."""
    pods = [{d.id for d in t.mesh.devices[s].flat}
            for s in range(t.mesh.shape["pod"])]
    held = {s: set() for s in range(len(pods))}
    for leaf in jax.tree.leaves(t.state["params"]["blocks"]):
        for shard in leaf.addressable_shards:
            rows = range(leaf.shape[0])[shard.index[0]]
            check(len(rows) == 1,
                  f"a {leaf.shape} block shard holds stages {list(rows)}")
            s = rows[0]
            check(shard.device.id in pods[s],
                  f"stage {s} block shard on device {shard.device.id}, "
                  f"outside its pod {sorted(pods[s])}")
            held[s].add(shard.device.id)
    check(all(held[s] == pods[s] for s in held),
          f"stages not on all their pod's chips: {held} vs {pods}")
    return {s: sorted(ids) for s, ids in held.items()}


def run_pipeline(bundle, *, global_batch: int, seq_len: int, steps: int,
                 devices) -> dict:
    """The planner's pp=2 plan over two islands of len(devices)/2 chips,
    executed on a (pod, data, model) mesh, against the plain step on a
    (len(devices), 1) mesh: same init seed and batches."""
    cl = _two_island_cluster(len(devices) // 2)
    plan = planner.search(cl, bundle.cfg, global_batch=global_batch,
                          seq_len=seq_len, pp_options=[2],
                          tp_options=[1]).plan
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt_dir:
        t = _trainer(bundle, make_train_mesh(devices=devices), global_batch,
                     seq_len, steps, ckpt_dir)
        ref_losses, ref_secs = _run_steps(t, steps)
        del t
        gc.collect()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt_dir:
        t = _trainer(bundle, make_train_mesh(plan, devices), global_batch,
                     seq_len, steps, ckpt_dir, cluster=cl, plan=plan)
        placement = stage_placement(t)
        losses, secs = _run_steps(t, steps)
    check(all(np.isfinite(losses + ref_losses)),
          f"non-finite loss: pp {losses}, plain {ref_losses}")
    for i, (a, b) in enumerate(zip(losses, ref_losses)):
        check(abs(a - b) <= PP_RTOL * abs(b),
              f"step {i}: pipeline loss {a!r} vs plain {b!r}")
    return {"plan": plan.describe(), "mesh": dict(t.mesh.shape),
            "placement": placement, "losses": losses,
            "ref_losses": ref_losses, "step_s": secs, "ref_step_s": ref_secs,
            "peak_bytes": [_peak_bytes(d) for d in devices]}


def _widths(cfg) -> str:
    return (f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
            f"head_dim={cfg.hd} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
            f"layers={cfg.num_layers}")


def _print_times(secs):
    later = secs[1:] or secs
    print(f"  first step (compile + run): {secs[0]!r} s; compile ~ "
          f"{secs[0] - statistics.median(later)!r} s")
    print(f"  per-step seconds (smoke, not a benchmark): {later!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the pp=2 pipeline on 4 chips and its "
                         "plain data-parallel reference")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    cache = Path(compile_cache.enable())
    warm = cache.is_dir() and any(cache.iterdir())
    print(f"[chip_smoke] device {dev.device_kind!r} x {len(devices)}; "
          f"compile cache {cache} ({'warm' if warm else 'cold'})")
    if args.four_chips:
        check(len(devices) >= 4, f"--four-chips needs 4 chips, "
                                 f"found {len(devices)}")
        devices = devices[:4]
        bundle = registry.get_bundle(ARCH, num_layers=4)
        print(f"[chip_smoke] pipeline: {ARCH} {_widths(bundle.cfg)} "
              f"batch=8 seq=2048")
        r = run_pipeline(bundle, global_batch=8, seq_len=2048, steps=3,
                         devices=devices)
        print(f"  plan {r['plan']} on mesh {r['mesh']}")
        print(f"  stage -> devices {r['placement']}")
        print(f"  pipeline losses {r['losses']!r}")
        print(f"  plain (4,1) losses {r['ref_losses']!r} (rtol {PP_RTOL})")
        print("  pipeline:")
        _print_times(r["step_s"])
        print("  plain:")
        _print_times(r["ref_step_s"])
        print(f"  peak_bytes_in_use per chip {r['peak_bytes']}")
    else:
        bundle = registry.get_bundle(ARCH, num_layers=2)
        print(f"[chip_smoke] one chip: {ARCH} {_widths(bundle.cfg)} "
              f"batch=4 seq=2048")
        r = run_one_chip(bundle, global_batch=4, seq_len=2048, steps=5,
                         device=dev)
        print(f"  losses {r['losses']!r}")
        print(f"  step-0 unjitted reference {r['step0_ref']!r}, |diff| "
              f"{r['step0_abs_err']!r} (rtol {STEP0_RTOL})")
        _print_times(r["step_s"])
        print(f"  peak_bytes_in_use {r['peak_bytes']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
