"""Device time per step in the program's ``mla`` scope, in ms: each block's
latent attention branch with its pre-norm and residual add (projections,
latent norm, RoPE), less the attention core where it runs in ``attn_core``;
forward, recompute and backward, averaged over the cell's devices
(``scope_time.py``)."""
import scope_time


def read(rec):
    return scope_time.scope_ms(rec, "mla")
