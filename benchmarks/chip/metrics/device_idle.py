"""Share of the traced window in which no operation ran on a device, in %,
averaged over the cell's devices."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["devices"]:
        return None
    busy = [d["busy_ns"] for d in tr["devices"].values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / tr["window_ns"])
