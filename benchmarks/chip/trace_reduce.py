"""Profiler trace (``.xplane.pb``) -> per-device busy intervals, op times,
collective exposure, and the host spans that idle gaps fall in.

Only the process that holds the chips can trace them, so the run itself
calls ``reduce_dir`` on the directory ``jax.profiler`` wrote.  Device
planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
executed HLO op, a loop's event enclosing its body's.  The traced window runs from the first to the end of the
last host span named ``step_name`` (the run wraps each traced step in a
``StepTraceAnnotation``).
"""
from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|\bsend\b|\brecv\b|"
                        r"allreduce|allgather")


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """Merged intervals a minus merged intervals b."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def op_name(name: str) -> str:
    """An op event's HLO name: ``%fusion.12 = bf16[...] fusion(...)`` ->
    ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line):
    return [(op_name(ev.name), float(ev.start_ns),
             float(ev.start_ns + ev.duration_ns)) for ev in line.events]


def self_times(ops):
    """[(name, start, end)] of one device, nested (a loop's event spans its
    body's events) -> [(name, start, end, self ns, is_leaf)]: each event
    with its time less its children's."""
    out, stack = [], []

    def close():
        name, s, e, children, leaf = stack.pop()
        out.append((name, s, e, (e - s) - children, leaf))

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        # an event that ends past the open one is not inside it
        while stack and (stack[-1][2] <= s or stack[-1][2] < e):
            close()
        if stack:
            stack[-1][3] += e - s
            stack[-1][4] = False
        stack.append([name, s, e, 0.0, True])
    while stack:
        close()
    return out


def reduce_profile(pd, step_name: str = "train") -> dict:
    """Reduces a ``jax.profiler.ProfileData`` to plain numbers (ns)."""
    host, devices = [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = [e for line in plane.lines if line.name == OPS_LINE
                   for e in _events(line)]
            devices[int(m.group(2))] = ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend(_events(line))
    steps = [(s, e) for n, s, e in host if n == step_name]
    if not steps:
        raise ValueError(f"no host span named {step_name!r} in the trace")
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    out = {"window_ns": hi - lo, "steps": len(steps), "devices": {},
           "host_spans": [(n, s, e) for n, s, e in host
                          if e > lo and s < hi and e > s]}
    for dev, ops in sorted(devices.items()):
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
               if e > lo and s < hi]
        busy = union([(s, e) for _, s, e in ops])
        timed = self_times(ops)
        # a collective counts as exposed where no other op runs: loop
        # events enclose their bodies, so only ops without children count
        coll = union([(s, e) for n, s, e, _, leaf in timed
                      if leaf and COLLECTIVE.search(n)])
        comp = union([(s, e) for n, s, e, _, leaf in timed
                      if leaf and not COLLECTIVE.search(n)])
        per_op = {}
        for n, _, _, t, _ in timed:
            per_op[n] = per_op.get(n, 0.0) + t
        out["devices"][dev] = {
            "busy_ns": length(busy),
            "collective_ns": length(coll),
            "collective_exposed_ns": length(subtract(coll, comp)),
            "ops_ns": per_op,
            "gaps": subtract([[lo, hi]], busy)}
    return out


def reduce_dir(trace_dir, step_name: str = "train") -> dict:
    from jax.profiler import ProfileData
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_profile(ProfileData.from_file(str(paths[-1])), step_name)


def gap_spans(reduced: dict, dev: int, top: int = 10):
    """The longest idle gaps of one device, each named by the innermost
    host span that covers its middle (the step span when no other does)."""
    gaps = sorted(reduced["devices"][dev]["gaps"], key=lambda g: g[0] - g[1])
    spans = reduced["host_spans"]
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        cover = [(n, a, b) for n, a, b in spans if a <= mid <= b]
        name = min(cover, key=lambda x: x[2] - x[1])[0] if cover else "none"
        out.append([name, (e - s) / 1e9])
    return out
