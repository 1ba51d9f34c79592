"""How far the planner's predicted peak memory for the plan it chose lies
from the measured one, in %: |predicted / measured - 1|, the prediction
being the largest ``peak_mem_gb`` over the plan's stages and the
measurement the fullest chip's peak (``peak_hbm_gb``).  Nothing to read
without a plan."""


def read(rec):
    pred, meas = rec.get("predicted_peak_gb"), rec.get("peak_hbm_gb")
    if not pred or not meas:
        return None
    return 100.0 * abs(pred / meas - 1.0)
