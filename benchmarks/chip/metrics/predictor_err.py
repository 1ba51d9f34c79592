"""How far the planner's predicted step time for the plan it chose lies
from the measured one, in %: |predicted / measured - 1|, the measured time
being the window's seconds per step.  Nothing to read without a plan."""


def read(rec):
    pred, meas = rec.get("predicted_step_s"), rec.get("step_s")
    if not pred or not meas:
        return None
    return 100.0 * abs(pred / meas - 1.0)
