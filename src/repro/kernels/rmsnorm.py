"""Pallas TPU fused RMSNorm: one HBM read, fp32 statistics in-register,
scaled write — removes the separate mean-square / rsqrt / mul round trips."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                 # (br, D)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x, scale, eps: float = 1e-5, block_rows: int = 256,
            interpret: bool = False) -> jax.Array:
    """x: (..., D); scale: (D,)."""
    shape = x.shape
    D = shape[-1]
    xf = x.reshape(-1, D)
    R = xf.shape[0]
    br = min(block_rows, R)
    pad = (-R) % br
    if pad:
        xf = jnp.concatenate(
            [xf, jnp.zeros((pad, D), xf.dtype)], axis=0)
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(xf.shape[0] // br,),
        in_specs=[pl.BlockSpec((br, D), lambda i: (i, 0)),
                  pl.BlockSpec((1, D), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        interpret=interpret,
    )(xf, scale.reshape(1, D))
    if pad:
        out = out[:R]
    return out.reshape(shape)
