"""Counters the train step returns, kept per step for readers in the same
process.

A step of a model with sigmoid-routed expert layers returns, beside its
loss, the (token, choice) rows routed to the experts this chip holds,
summed over the expert layers (``moe_rows``), and the largest held
expert's rows in any layer (``moe_rows_max``).  The Trainer fetches them
with the loss and ``record``s them with the geometry that prices them;
``recent`` gives the last steps' records.
"""
from __future__ import annotations

import collections
from typing import Any, Dict, List

from repro.models.config import ModelConfig

NAMES = ("moe_rows", "moe_rows_max")
KEPT = 64

_STEPS: collections.deque = collections.deque(maxlen=KEPT)


def record(cfg: ModelConfig, values: Dict[str, Any]) -> None:
    """Keeps one step's counters (those of ``NAMES`` in ``values``) with
    the expert layers' geometry: model and expert widths, experts held,
    expert layers, and how many times the step runs each grouped matmul
    (forward, its recompute under remat, and two backward products)."""
    got = {k: int(values[k]) for k in NAMES if k in values}
    if not got:
        return
    got.update(d_model=cfg.d_model, d_expert=cfg.d_expert_,
               experts_held=cfg.experts_held_,
               moe_layers=sum(cfg.is_moe_layer(i)
                              for i in range(cfg.num_layers)),
               passes=4 if cfg.remat else 3)
    _STEPS.append(got)


def recent(n: int) -> List[Dict[str, Any]]:
    """The last ``n`` steps' records, oldest first."""
    return list(_STEPS)[-n:] if n > 0 else []
