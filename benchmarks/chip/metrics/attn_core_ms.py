"""Device time per step in the program's ``attn_core`` scope, in ms: the
attention core run over chunks of queries, each rematerialised
(``models/layers.py::attention_core``), where the whole sequence's scores
would not fit; forward, recompute and backward, averaged over the cell's
devices (``scope_time.py``)."""
import scope_time


def read(rec):
    return scope_time.scope_ms(rec, "attn_core")
