"""Device time per step in the program's ``attn`` scope, in ms: each block's
attention branch, with its pre-norm and residual add; forward, recompute and
backward, averaged over the cell's devices (``scope_time.py``)."""
import scope_time


def read(rec):
    return scope_time.scope_ms(rec, "attn")
