"""Device time per step in the program's ``embed`` scope, in ms: the token
embedding (``_embed_tokens``) and the scatter-add of its gradient; forward,
recompute and backward, averaged over the cell's devices
(``scope_time.py``)."""
import scope_time


def read(rec):
    return scope_time.scope_ms(rec, "embed")
