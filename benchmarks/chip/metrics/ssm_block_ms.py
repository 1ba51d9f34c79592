"""Device time per step in the program's ``ssm_block`` scope, in ms: each
block's Mamba branch outside the selective scan: pre-norm, projections,
convolution, dt/B/C; forward, recompute and backward, averaged over the
cell's devices (``scope_time.py``)."""
import scope_time


def read(rec):
    return scope_time.scope_ms(rec, "ssm_block")
