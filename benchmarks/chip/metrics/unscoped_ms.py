"""Device time per step in ops of no named scope, in ms (``scope_time.py``):
the step's glue, and whatever a refactor left out of its scope."""
import scope_time


def read(rec):
    return scope_time.scope_ms(rec, None)
