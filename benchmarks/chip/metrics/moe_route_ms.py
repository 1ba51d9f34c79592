"""Device time per step in the program's ``moe_route`` scope, in ms: the
sigmoid router's scores, the selection, the sort and gather of the routed
rows, and the weighted combine back to tokens (``models/moe.py::
moe_share``); forward, recompute and backward, averaged over the cell's
devices (``scope_time.py``)."""
import scope_time


def read(rec):
    return scope_time.scope_ms(rec, "moe_route")
