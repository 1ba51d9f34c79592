"""Quickstart: train a tiny llama on CPU with the full production loop
(sharded init, AdamW, async checkpointing), then serve it.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402

from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig  # noqa: E402


def main():
    mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    bundle = registry.get_bundle("llama3-8b", smoke=True)
    t = Trainer(bundle, mesh, TrainerConfig(
        global_batch=8, seq_len=64, ckpt_dir="/tmp/repro_quickstart",
        ckpt_every=10))
    r = t.run(20)
    print(f"loss: {r['losses'][0]:.3f} -> {r['losses'][-1]:.3f} "
          f"over {len(r['losses'])} steps")

    # serve the trained weights: prefill + 8 decode steps
    cfg = bundle.cfg
    params = t.state["params"]
    batch = registry.make_batch(cfg, batch=2, seq=16, with_labels=False)
    logits, cache = bundle.prefill(params, batch, cfg, max_len=32)
    tok = logits.argmax(-1)[:, None].astype("int32")
    out = [int(tok[0, 0])]
    for _ in range(8):
        logits, cache = bundle.decode_step(params, tok, cache, cfg)
        tok = logits.argmax(-1)[:, None].astype("int32")
        out.append(int(tok[0, 0]))
    print("greedy continuation:", out)


if __name__ == "__main__":
    main()
