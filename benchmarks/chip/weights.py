"""The model's weights, made from ``--seed`` on the device in one jitted
call, in the type each leaf is served in.

Every leaf is drawn by its name: the family's reference module lists the
leaves that are not plain matrices (``INIT``); every other leaf is a
normal draw scaled by 1/sqrt(fan-in), the fan-in being the leaf's
second-to-last dim.  The same seed gives the same weights, whatever the
program's own initialisation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference.core import leaf_names


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number, 64 bits and more."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words))


def _leaf(rule, key, shape, dtype):
    f32 = jnp.float32
    if rule == "ones":
        x = jnp.ones(shape, f32)
    elif rule == "zeros":
        x = jnp.zeros(shape, f32)
    elif rule == "log_arange":      # Mamba's S4D-real A: log(1..d_state)
        x = jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1, dtype=f32)),
                             shape)
    elif isinstance(rule, list) and rule[0] == "const":
        x = jnp.full(shape, rule[1], f32)
    else:
        x = jax.random.normal(key, shape, f32) / np.sqrt(shape[-2])
    return x.astype(dtype)


def make_params(key, shapes, init: dict):
    """Traceable: params with the tree, shapes and dtypes of ``shapes``."""
    names = leaf_names(shapes)
    leaves = jax.tree.leaves(shapes)
    keys = jax.random.split(key, len(leaves))
    out = [_leaf(init.get(n.split("/")[-1]), keys[i], x.shape, x.dtype)
           for i, (n, x) in enumerate(zip(names, leaves))]
    # the barrier keeps each leaf at its stored type's values: without it
    # the TPU compiler may fuse a later float32 copy with the draw and
    # skip the rounding (excess precision), and two programs that make
    # the same weights would then disagree
    out = jax.lax.optimization_barrier(out)
    return jax.tree.unflatten(jax.tree.structure(shapes), out)
