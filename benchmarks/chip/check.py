"""The comparison that decides ``correct`` for a training cell.

Both sides give readings (``reference.core.train_readings`` and the
program's, taken by ``harness.program_readings``): each step's loss, each
leaf's norm of the first step's gradient as the optimizer gets it, and
each leaf's norm of the master's change over the checked steps.  Three
numbers are compared, each against its limit in ``limits/<cell>.json``:

- ``loss_gap``: the largest |loss - reference| / |reference| over the
  steps;
- ``grad_gap``: the worst leaf's |norm - reference norm|, over the larger
  of that leaf's reference norm and the median leaf's;
- ``change_gap``: the same for the change, over the leaves whose reference
  gradient is at least a thousandth of the median leaf's (a leaf with no
  gradient moves under Adam by round-off alone).
"""
from __future__ import annotations

import statistics

MOVED = 1e-3


def _worst_leaf(prog: dict, ref: dict, leaves) -> float:
    med = statistics.median(ref[k] for k in ref)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in leaves)


def gaps(prog: dict, ref: dict) -> dict:
    if set(prog["grad_norms"]) != set(ref["grad_norms"]):
        raise ValueError("the program's leaves are not the reference's: "
                         f"{sorted(set(prog['grad_norms']) ^ set(ref['grad_norms']))}")
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                   ref["losses"]))
    g_med = statistics.median(ref["grad_norms"].values())
    moved = [k for k, g in ref["grad_norms"].items() if g >= MOVED * g_med]
    return {"loss_gap": loss,
            "grad_gap": _worst_leaf(prog["grad_norms"], ref["grad_norms"],
                                    ref["grad_norms"]),
            "change_gap": _worst_leaf(
                prog["change_norms"],
                {k: ref["change_norms"][k] for k in moved}, moved)}


def judge(values: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}); a number that is not finite
    fails."""
    out = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(v["value"] <= v["limit"] for v in out.values())
    return ok, out
