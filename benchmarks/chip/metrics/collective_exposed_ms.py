"""Device time per step, in ms, spent in collective ops while no other op
ran on that device, averaged over the cell's devices.  Nothing to read on
one chip."""


def read(rec):
    tr = rec.get("trace")
    if not tr or len(tr["devices"]) < 2:
        return None
    exposed = [d["collective_exposed_ns"] for d in tr["devices"].values()]
    return sum(exposed) / len(exposed) / tr["steps"] / 1e6
