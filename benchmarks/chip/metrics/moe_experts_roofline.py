"""Share of the held experts' grouped matmuls' roofline, in %: the least
time their work could take on the chip, the larger of their FLOPs at the
bf16 peak and their bytes at the HBM bandwidth (``flops/mla_moe.py``,
from the rows the program routed to held experts in the traced steps,
``repro.obs.counters``), over their device time (``moe_experts_ms``).
Nothing to read without that scope or those counters."""
from pathlib import Path

import harness
import scope_time

FLOPS = Path(__file__).resolve().parents[1] / "flops" / "mla_moe.py"


def read(rec):
    tr = rec.get("trace")
    ms = scope_time.scope_ms(rec, "moe_experts")
    if not tr or not ms:
        return None
    try:
        from repro.obs import counters
    except ImportError:         # a program without the counters
        return None
    steps = counters.recent(tr["steps"])
    if not steps:
        return None
    fl = harness.load_module(FLOPS)
    least = 0.0
    for s in steps:
        work = (s["moe_rows"], s["d_model"], s["d_expert"])
        least += max(
            fl.grouped_matmul_flops(*work, s["passes"])
            / rec["peak"]["bf16_flops"],
            fl.grouped_matmul_bytes(*work, s["experts_held"], s["passes"])
            / rec["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / len(steps) / (ms / 1e3)
