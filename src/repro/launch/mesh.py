"""Every mesh the program and its tests build comes from here.

All axes are ``AxisType.Auto``: the sharding rules and the
``with_sharding_constraint`` hints are written for GSPMD's auto-sharded
propagation, while ``jax.make_mesh`` defaults to Explicit axes, under which
e.g. the embedding gather needs an explicit ``out_sharding``.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single-pod: (16, 16) ("data", "model") = 256 chips.
Multi-pod: (2, 16, 16) ("pod", "data", "model") = 512 chips — the ``pod``
axis is HETHUB's heterogeneous boundary (pipeline stages / slow links).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_train_mesh(plan=None, devices: Optional[Sequence] = None):
    """The Trainer's mesh over ``devices`` (default: all).  A pipelined
    plan (pp > 1) gets a leading ``pod`` axis of size pp, so each stage's
    blocks shard onto their own chips; otherwise a (n, 1) data/model mesh.
    With fewer devices than stages the pipeline runs pod-less (every stage
    on every device), the single-device CPU mode."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if plan is not None and plan.pp > 1 and n >= plan.pp:
        if n % plan.pp:
            raise ValueError(f"{n} devices do not split into {plan.pp} "
                             f"pipeline stages")
        return make_mesh((plan.pp, n // plan.pp, 1),
                         ("pod", "data", "model"), devices=devices)
    return make_mesh((n, 1), ("data", "model"), devices=devices)


# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (≈ per-chip usable)
