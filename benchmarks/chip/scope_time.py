"""Device time per step by the program's named scopes, and device idle
time inside the Trainer's named host spans.

The program runs each layer of its train step under a named scope
(``repro.obs.scopes.SCOPES``) and keeps the table from the compiled
step's HLO instructions to those scopes (``scopes.table``); a device
trace names each op by its instruction.  So an op's self time
(``trace_reduce``'s ``ops_ns``) counts for the innermost scope it ran
in, or for none.  The table is built on its first request, in the run's
own process, after the reference.  A program without the registry, or
without the named host spans, has nothing to read: the readers return
None.
"""
from __future__ import annotations

import sys
import time

import trace_reduce

PROGRAM = "train_step"
INPUT_SPANS = ("trainer.batch", "trainer.put")
_LOGGED = set()


def scope_table():
    """Instruction name -> scope of the program's train step, or None."""
    try:
        from repro.obs import scopes
    except ImportError:         # a program without named scopes
        return None
    t0 = time.perf_counter()
    table = scopes.table(PROGRAM)
    if table is not None and PROGRAM not in _LOGGED:
        _LOGGED.add(PROGRAM)
        print(f"[bench] scope table of {PROGRAM}: {len(table)} instructions "
              f"in {time.perf_counter() - t0!r} s", file=sys.stderr,
              flush=True)
    return table


def per_step_ms(rec, table=None):
    """{scope, or None for ops of no scope: device ms per step}, summed
    over the ops' self times and averaged over the devices; None where
    there is no trace or no table."""
    tr = rec.get("trace")
    if not tr or not tr["devices"]:
        return None
    table = scope_table() if table is None else table
    if not table:
        return None
    out = {}
    for dev in tr["devices"].values():
        for op, ns in dev["ops_ns"].items():
            s = table.get(op)
            out[s] = out.get(s, 0.0) + ns
    n = len(tr["devices"]) * tr["steps"]
    return {s: ns / n / 1e6 for s, ns in out.items()}


def scope_ms(rec, scope, table=None):
    """Device ms per step in ``scope`` (None for ops of no scope); None
    where the program has no such scope."""
    table = scope_table() if table is None else table
    if not table or (scope is not None and scope not in table.values()):
        return None
    ms = per_step_ms(rec, table)
    return None if ms is None else ms.get(scope, 0.0)


def idle_in_spans_ms(rec, names=INPUT_SPANS):
    """Device idle ms per step that falls inside host spans named
    ``names``, averaged over the devices; None without such spans."""
    tr = rec.get("trace")
    if not tr or not tr["devices"]:
        return None
    spans = trace_reduce.union([(s, e) for n, s, e in tr["host_spans"]
                                if n in names])
    if not spans:
        return None
    idle = []
    for dev in tr["devices"].values():
        gaps = trace_reduce.union(dev["gaps"])
        inside = trace_reduce.subtract(gaps,
                                       trace_reduce.subtract(gaps, spans))
        idle.append(trace_reduce.length(inside))
    return sum(idle) / len(idle) / tr["steps"] / 1e6
