"""Device time per step in the program's ``moe_experts`` scope, in ms: the
held experts' grouped matmuls and their SwiGLU; forward, recompute and
backward, averaged over the cell's devices (``scope_time.py``)."""
import scope_time


def read(rec):
    return scope_time.scope_ms(rec, "moe_experts")
