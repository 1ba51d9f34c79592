"""Pallas TPU fused SwiGLU gate: silu(g) * u in one VMEM pass (the XLA
unfused path writes silu(g) back to HBM between the two elementwise ops)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# bytes of one (rows, F) block: g, u and out are double-buffered and the
# body holds two fp32 temporaries, ~10 blocks in all, under the 16 MiB
# default scoped VMEM
BLOCK_BYTES = 1 << 20


def _kernel(g_ref, u_ref, o_ref):
    g = g_ref[...].astype(jnp.float32)
    o_ref[...] = (g * jax.nn.sigmoid(g)
                  * u_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _rows_for(F: int, itemsize: int, block_rows: int) -> int:
    """Largest power of two <= block_rows whose (rows, F) block fits
    BLOCK_BYTES, and at least 16 (one packed bf16 sublane tile)."""
    fit = max(BLOCK_BYTES // (F * itemsize), 16)
    br = 16
    while br * 2 <= min(fit, block_rows):
        br *= 2
    return min(br, block_rows)


def swiglu(g, u, block_rows: int = 256, interpret: bool = False
           ) -> jax.Array:
    shape = g.shape
    F = shape[-1]
    gf, uf = g.reshape(-1, F), u.reshape(-1, F)
    R = gf.shape[0]
    br = min(_rows_for(F, g.dtype.itemsize, block_rows), R)
    pad = (-R) % br
    if pad:
        z = jnp.zeros((pad, F), gf.dtype)
        gf = jnp.concatenate([gf, z], axis=0)
        uf = jnp.concatenate([uf, z], axis=0)
    out = pl.pallas_call(
        _kernel,
        grid=(gf.shape[0] // br,),
        in_specs=[pl.BlockSpec((br, F), lambda i: (i, 0)),
                  pl.BlockSpec((br, F), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, F), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(gf.shape, g.dtype),
        interpret=interpret,
    )(gf, uf)
    if pad:
        out = out[:R]
    return out.reshape(shape)
