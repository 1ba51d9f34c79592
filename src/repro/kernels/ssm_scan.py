"""Pallas TPU chunked selective scan (Mamba-1, diagonal A).

TPU adaptation of the CUDA fused selective-scan: the recurrent state
(d_state x d_inner_block) lives in VMEM scratch and persists across the
sequential chunk grid dim; inputs stream chunk-by-chunk.  d_inner is tiled
over the grid (it is TP-sharded anyway), so the working set stays far under
VMEM.  Inside a chunk the recurrence is a fori_loop over time steps on the
VPU — (d_state, di_block) elementwise ops per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# time steps per aligned row group: one packed bf16 sublane tile
GROUP = 16


def _kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, h_scr, *,
            chunk: int):
    """State h is (d_state, di_block): d_inner on the lanes.  The TPU
    lowering slices neither a loaded value nor a ref at an unaligned
    dynamic row, so u/dt/y move in aligned GROUP-row blocks through their
    refs and the group's steps are unrolled; the (d_state, 1) columns of
    B/C are picked out of the chunk's transposed (d_state, chunk) block
    with a lane mask."""
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[...].astype(jnp.float32)                   # (ds, di_b)
    bt = b_ref[0].astype(jnp.float32)                    # (ds, chunk)
    ct = c_ref[0].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (GROUP, a.shape[1]), 0)

    def group(g, h):
        r0 = pl.multiple_of(g * GROUP, GROUP)
        u = u_ref[0, pl.ds(r0, GROUP), :].astype(jnp.float32)  # (G, di_b)
        dt = dt_ref[0, pl.ds(r0, GROUP), :].astype(jnp.float32)
        y = jnp.zeros(rows.shape, jnp.float32)
        for s in range(GROUP):
            at_t = lane == r0 + s
            b = jnp.sum(jnp.where(at_t, bt, 0.0), axis=1, keepdims=True)
            c = jnp.sum(jnp.where(at_t, ct, 0.0), axis=1, keepdims=True)
            dt_s = dt[s:s + 1]                               # (1, di_b)
            h = jnp.exp(dt_s * a) * h + (dt_s * u[s:s + 1]) * b
            y = jnp.where(rows == s,
                          jnp.sum(h * c, axis=0, keepdims=True), y)
        y_ref[0, pl.ds(r0, GROUP), :] = y.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk // GROUP, group, h_scr[...])


def ssm_scan(u, dt, Bc, Cc, A, *, chunk: int = 128, di_block: int = 512,
             interpret: bool = False) -> jax.Array:
    """u,dt: (B,S,di); Bc,Cc: (B,S,ds); A: (di,ds) -> y (B,S,di) fp32-acc.
    Matches kernels.ref.ssm_scan_ref."""
    B, S, di = u.shape
    ds = Bc.shape[-1]
    chunk = min(chunk, S)
    di_block = min(di_block, di)
    assert S % chunk == 0 and di % di_block == 0
    assert chunk % GROUP == 0, f"chunk {chunk} is not a multiple of {GROUP}"
    nc, nd = S // chunk, di // di_block

    grid = (B, nd, nc)           # chunks innermost: sequential carry
    y = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, di_block), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, di_block), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, ds, chunk), lambda b, d, c: (b, 0, c)),
            pl.BlockSpec((1, ds, chunk), lambda b, d, c: (b, 0, c)),
            pl.BlockSpec((ds, di_block), lambda b, d, c: (0, d)),
        ],
        out_specs=pl.BlockSpec((1, chunk, di_block), lambda b, d, c: (b, c, d)),
        out_shape=jax.ShapeDtypeStruct((B, S, di), jnp.float32),
        scratch_shapes=[pltpu.VMEM((ds, di_block), jnp.float32)],
        interpret=interpret,
    )(u, dt, Bc.swapaxes(1, 2), Cc.swapaxes(1, 2), A.T)
    return y
