"""AdamW with fp32 master weights and ZeRO-1-style sharded moments.

Pure-pytree implementation (no optax dependency): the optimizer state is
{master?, m, v, count}.  Master weights exist only when params are low
precision (bf16); moments are always fp32.  Sharding of the moments over the
``data`` axis (ZeRO-1) is decided by parallel/sharding.py, not here — this
module is sharding-agnostic.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100


def init_opt_state(params: Any, keep_master: bool = True) -> dict:
    f32 = lambda p: jnp.zeros(p.shape, jnp.float32)
    state = {
        "m": jax.tree.map(f32, params),
        "v": jax.tree.map(f32, params),
        "count": jnp.zeros((), jnp.int32),
    }
    if keep_master:
        # the master starts at the params' own bf16 values.  Without the
        # barrier, XLA on TPU fuses a jitted init and hands the master the
        # unrounded f32 draw (excess precision), so two programs that
        # place the same init differently start from different masters.
        state["master"] = jax.tree.map(
            lambda p: p.astype(jnp.float32),
            jax.lax.optimization_barrier(params))
    return state


def _schedule(cfg: AdamWConfig, count):
    warm = jnp.minimum(count.astype(jnp.float32) / max(cfg.warmup_steps, 1),
                       1.0)
    return cfg.lr * warm


def global_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


@jax.named_scope("optimizer")
def adamw_update(params: Any, grads: Any, state: dict, cfg: AdamWConfig):
    """Returns (new_params, new_state, metrics)."""
    count = state["count"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))
    lr = _schedule(cfg, count)
    bc1 = 1.0 - cfg.b1 ** count.astype(jnp.float32)
    bc2 = 1.0 - cfg.b2 ** count.astype(jnp.float32)
    masters = state.get("master", params)

    def upd(p, g, m, v, master):
        g = g.astype(jnp.float32) * scale
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
        step = (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps)
        master = master.astype(jnp.float32)
        master = master - lr * (step + cfg.weight_decay * master)
        return master.astype(p.dtype), m, v, master

    out = jax.tree.map(upd, params, grads, state["m"], state["v"], masters)
    new_params = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda t: isinstance(t, tuple))
    new_state = {
        "m": jax.tree.map(lambda t: t[1], out,
                          is_leaf=lambda t: isinstance(t, tuple)),
        "v": jax.tree.map(lambda t: t[2], out,
                          is_leaf=lambda t: isinstance(t, tuple)),
        "count": count,
    }
    if "master" in state:
        new_state["master"] = jax.tree.map(
            lambda t: t[3], out, is_leaf=lambda t: isinstance(t, tuple))
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
