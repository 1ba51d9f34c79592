"""Device time per step in the program's ``head_loss`` scope, in ms: the
final norm, the unembedding and the cross-entropy; forward, recompute and
backward, averaged over the cell's devices (``scope_time.py``)."""
import scope_time


def read(rec):
    return scope_time.scope_ms(rec, "head_loss")
