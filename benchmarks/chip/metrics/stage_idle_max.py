"""The highest idle share of any pipeline stage, in %: for each stage, the
traced window's idle share averaged over the chips of its pod; the largest
over the stages.  Nothing to read in a cell without pipeline stages, or
where the trace lacks a stage's chips."""


def read(rec):
    tr, pods = rec.get("trace"), rec.get("pods")
    if not tr or not pods or len(pods) < 2:
        return None
    idle = []
    for devs in pods.values():
        if any(d not in tr["devices"] for d in devs):
            return None
        busy = [tr["devices"][d]["busy_ns"] for d in devs]
        idle.append(1.0 - sum(busy) / len(busy) / tr["window_ns"])
    return 100.0 * max(idle)
