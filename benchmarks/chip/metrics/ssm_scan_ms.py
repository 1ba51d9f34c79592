"""Device time per step in the program's ``ssm_scan`` scope, in ms: the
selective scan (``models/mamba.py::selective_scan``); forward, recompute and
backward, averaged over the cell's devices (``scope_time.py``)."""
import scope_time


def read(rec):
    return scope_time.scope_ms(rec, "ssm_scan")
