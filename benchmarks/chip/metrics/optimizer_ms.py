"""Device time per step in the program's ``optimizer`` scope, in ms: the
AdamW update (``optim/adamw.py::adamw_update``); forward, recompute and
backward, averaged over the cell's devices (``scope_time.py``)."""
import scope_time


def read(rec):
    return scope_time.scope_ms(rec, "optimizer")
