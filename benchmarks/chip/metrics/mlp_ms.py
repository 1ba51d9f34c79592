"""Device time per step in the program's ``mlp`` scope, in ms: each block's
MLP (or MoE) branch, with its pre-norm and residual add; forward, recompute
and backward, averaged over the cell's devices (``scope_time.py``)."""
import scope_time


def read(rec):
    return scope_time.scope_ms(rec, "mlp")
