"""Device idle time per step while the Trainer makes the batch and puts it
on the device (host spans ``trainer.batch`` and ``trainer.put``), in ms,
averaged over the cell's devices (``scope_time.py``)."""
import scope_time


def read(rec):
    return scope_time.idle_in_spans_ms(rec)
