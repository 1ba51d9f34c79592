"""End-to-end driver (deliverable b): train a ~100M-param llama-family model.
Full run: PYTHONPATH=src python examples/train_100m.py --steps 300
(CPU: ~5-10 s/step; pass --steps 20 for a quick check.)
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import dataclasses  # noqa: E402

import jax  # noqa: E402

from repro.configs.llama3_8b import CONFIG  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    cfg = dataclasses.replace(
        CONFIG, name="llama-100m", num_layers=12, d_model=768, n_heads=12,
        n_kv_heads=4, d_ff=2048, vocab_size=32000,
        param_dtype="float32", dtype="float32")
    bundle = registry.bundle_for(cfg)
    mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    t = Trainer(bundle, mesh,
                TrainerConfig(global_batch=args.global_batch,
                              seq_len=args.seq,
                              ckpt_dir="/tmp/repro_100m", ckpt_every=50),
                opt_cfg=AdamWConfig(lr=6e-4, warmup_steps=50))
    n = sum(x.size for x in jax.tree.leaves(t.state["params"]))
    print(f"params: {n/1e6:.1f}M  steps: {args.steps}")
    while t.step < args.steps:
        r = t.run(min(10, args.steps - t.step))
        print(f"step {t.step:4d}  loss {r['losses'][-1]:.4f}")
    print("done")


if __name__ == "__main__":
    main()
