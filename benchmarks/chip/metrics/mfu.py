"""Model FLOP/s utilization of the train step, in % of the chips' bf16
peak: model FLOPs per token (``flops/<family>.py``) x the window's
tokens/s, over chips x peak FLOP/s (``peaks.json``)."""


def read(rec):
    if not rec.get("tokens_per_s"):
        return None
    return (100.0 * rec["flops_per_token"] * rec["tokens_per_s"]
            / (rec["chips"] * rec["peak"]["bf16_flops"]))
