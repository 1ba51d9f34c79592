"""Pallas TPU chunked selective scan (Mamba-1, diagonal A), forward and
backward under one ``jax.custom_vjp``.

TPU adaptation of the CUDA fused selective-scan: the recurrent state
(d_state x d_inner_block) lives in VMEM scratch and persists across the
sequential chunk grid dim; inputs stream chunk-by-chunk.  d_inner is tiled
over the grid (it is TP-sharded anyway), so the working set stays far under
VMEM.  Inside a chunk the recurrence is a fori_loop over time steps on the
VPU — (d_state, di_block) elementwise ops per step.

The forward also writes the state at the start of each chunk, the only
residual the backward needs besides the inputs.  The backward walks the
chunks in reverse: it recomputes the chunk's states from its start state
into VMEM, then runs the adjoint recurrence
``g_t = dy_t C_t + exp(dt_{t+1} A) g_{t+1}`` with g carried in VMEM across
chunks.  Everything is fp32; nothing is approximated.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# time steps per aligned row group: one packed bf16 sublane tile
GROUP = 16


def _column(mat, at_t):
    """Column t of a (d_state, chunk) block as (d_state, 1)."""
    return jnp.sum(jnp.where(at_t, mat, 0.0), axis=1, keepdims=True)


def _fwd_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, h0_ref, h_scr, *,
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

    h0_ref[0, 0] = h_scr[...]
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
            b, c = _column(bt, at_t), _column(ct, at_t)
            dt_s = dt[s:s + 1]                               # (1, di_b)
            h = jnp.exp(dt_s * a) * h + (dt_s * u[s:s + 1]) * b
            y = jnp.where(rows == s,
                          jnp.sum(h * c, axis=0, keepdims=True), y)
        y_ref[0, pl.ds(r0, GROUP), :] = y.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk // GROUP, group, h_scr[...])


def _bwd_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref, dy_ref,
                du_ref, ddt_ref, db_ref, dc_ref, da_ref, hs_scr, g_scr, *,
                chunk: int):
    """One chunk of the backward, chunks visited last to first.
    ``hs_scr[t]`` holds the state before step t of the chunk (t = 0 is the
    chunk's start state, t = chunk its end state).  ``g_scr`` carries
    exp(dt_t A) g_t of the chunk's first step to the previous chunk's last
    step.  dB and dC go out as (d_state, chunk) blocks of this d_inner
    block's partial sums, dA as this batch row's partial sum."""
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        g_scr[...] = jnp.zeros_like(g_scr)
        da_ref[...] = jnp.zeros_like(da_ref)

    a = a_ref[...].astype(jnp.float32)                   # (ds, di_b)
    bt = b_ref[0].astype(jnp.float32)                    # (ds, chunk)
    ct = c_ref[0].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (GROUP, a.shape[1]), 0)
    groups = chunk // GROUP

    def rows_of(ref, r0):
        return ref[0, pl.ds(r0, GROUP), :].astype(jnp.float32)

    def recompute(g, h):
        r0 = pl.multiple_of(g * GROUP, GROUP)
        u, dt = rows_of(u_ref, r0), rows_of(dt_ref, r0)
        for s in range(GROUP):
            b = _column(bt, lane == r0 + s)
            dt_s = dt[s:s + 1]
            h = jnp.exp(dt_s * a) * h + (dt_s * u[s:s + 1]) * b
            hs_scr[r0 + s + 1] = h
        return h

    h0 = h0_ref[0, 0]
    hs_scr[0] = h0
    jax.lax.fori_loop(0, groups, recompute, h0)

    def adjoint(i, carry):
        ga, da, dbt, dct = carry
        r0 = pl.multiple_of((groups - 1 - i) * GROUP, GROUP)
        u, dt, dy = rows_of(u_ref, r0), rows_of(dt_ref, r0), rows_of(dy_ref, r0)
        du = jnp.zeros(rows.shape, jnp.float32)
        ddt = jnp.zeros(rows.shape, jnp.float32)
        for s in reversed(range(GROUP)):
            t = r0 + s
            at_t = lane == t
            b, c = _column(bt, at_t), _column(ct, at_t)
            dt_s, u_s, dy_s = dt[s:s + 1], u[s:s + 1], dy[s:s + 1]
            g = dy_s * c + ga                                # dL/dh_t
            decay = jnp.exp(dt_s * a)
            q = g * decay * hs_scr[t]                        # via h_{t-1}
            gb = jnp.sum(g * b, axis=0, keepdims=True)       # (1, di_b)
            dct = jnp.where(at_t, jnp.sum(hs_scr[t + 1] * dy_s, axis=1,
                                          keepdims=True), dct)
            dbt = jnp.where(at_t, jnp.sum(g * (dt_s * u_s), axis=1,
                                          keepdims=True), dbt)
            du = jnp.where(rows == s, dt_s * gb, du)
            ddt = jnp.where(rows == s, u_s * gb + jnp.sum(
                q * a, axis=0, keepdims=True), ddt)
            da = da + q * dt_s
            ga = decay * g
        du_ref[0, pl.ds(r0, GROUP), :] = du.astype(du_ref.dtype)
        ddt_ref[0, pl.ds(r0, GROUP), :] = ddt.astype(ddt_ref.dtype)
        return ga, da, dbt, dct

    zeros = jnp.zeros(bt.shape, jnp.float32)
    ga, da, dbt, dct = jax.lax.fori_loop(
        0, groups, adjoint, (g_scr[...], jnp.zeros_like(a), zeros, zeros))
    g_scr[...] = ga
    da_ref[0] += da
    db_ref[0, 0] = dbt
    dc_ref[0, 0] = dct


def _forward(u, dt, bt, ct, at, chunk, di_block, interpret):
    """u,dt: (B,S,di); bt,ct: (B,ds,S); at: (ds,di) -> y (B,S,di) fp32 and
    the state at the start of each chunk, (B, S // chunk, ds, di) fp32."""
    B, S, di = u.shape
    ds = bt.shape[1]
    nc, nd = S // chunk, di // di_block
    seq = pl.BlockSpec((1, chunk, di_block), lambda b, d, c: (b, c, d))
    state = pl.BlockSpec((1, 1, ds, di_block), lambda b, d, c: (b, c, 0, d))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk),
        grid=(B, nd, nc),           # chunks innermost: sequential carry
        in_specs=[
            seq, seq,
            pl.BlockSpec((1, ds, chunk), lambda b, d, c: (b, 0, c)),
            pl.BlockSpec((1, ds, chunk), lambda b, d, c: (b, 0, c)),
            pl.BlockSpec((ds, di_block), lambda b, d, c: (0, d)),
        ],
        out_specs=[seq, state],
        out_shape=[jax.ShapeDtypeStruct((B, S, di), jnp.float32),
                   jax.ShapeDtypeStruct((B, nc, ds, di), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((ds, di_block), jnp.float32)],
        interpret=interpret,
        name="ssm_scan_fwd",
    )(u, dt, bt, ct, at)


def _backward(u, dt, bt, ct, at, h0, dy, chunk, di_block, interpret):
    """-> du and ddt (in u's and dt's dtypes), and the fp32 partial sums
    of dB^T (nd,B,ds,S), dC^T (nd,B,ds,S) and dA^T (B,ds,di)."""
    B, S, di = u.shape
    ds = bt.shape[1]
    nc, nd = S // chunk, di // di_block
    last = nc - 1                # grid step c visits chunk last - c
    seq = pl.BlockSpec((1, chunk, di_block),
                       lambda b, d, c: (b, last - c, d))
    cols = pl.BlockSpec((1, ds, chunk), lambda b, d, c: (b, 0, last - c))
    part = pl.BlockSpec((1, 1, ds, chunk),
                        lambda b, d, c: (d, b, 0, last - c))
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        grid=(B, nd, nc),
        in_specs=[
            seq, seq, cols, cols,
            pl.BlockSpec((ds, di_block), lambda b, d, c: (0, d)),
            pl.BlockSpec((1, 1, ds, di_block),
                         lambda b, d, c: (b, last - c, 0, d)),
            seq,
        ],
        out_specs=[seq, seq, part, part,
                   pl.BlockSpec((1, ds, di_block), lambda b, d, c: (b, 0, d))],
        out_shape=[jax.ShapeDtypeStruct((B, S, di), u.dtype),
                   jax.ShapeDtypeStruct((B, S, di), dt.dtype),
                   jax.ShapeDtypeStruct((nd, B, ds, S), f32),
                   jax.ShapeDtypeStruct((nd, B, ds, S), f32),
                   jax.ShapeDtypeStruct((B, ds, di), f32)],
        scratch_shapes=[pltpu.VMEM((chunk + 1, ds, di_block), f32),
                        pltpu.VMEM((ds, di_block), f32)],
        interpret=interpret,
        name="ssm_scan_bwd",
    )(u, dt, bt, ct, at, h0, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _scan(chunk, di_block, interpret, u, dt, Bc, Cc, A):
    y, _ = _forward(u, dt, Bc.swapaxes(1, 2), Cc.swapaxes(1, 2), A.T,
                    chunk, di_block, interpret)
    return y


def _scan_fwd(chunk, di_block, interpret, u, dt, Bc, Cc, A):
    y, h0 = _forward(u, dt, Bc.swapaxes(1, 2), Cc.swapaxes(1, 2), A.T,
                     chunk, di_block, interpret)
    return y, (u, dt, Bc, Cc, A, h0)


def _scan_bwd(chunk, di_block, interpret, res, dy):
    u, dt, Bc, Cc, A, h0 = res
    # the backward is traced outside the forward's scopes: name it so the
    # device time of its kernel counts for the scan
    with jax.named_scope("ssm_scan"):
        du, ddt, dbt, dct, dat = _backward(
            u, dt, Bc.swapaxes(1, 2), Cc.swapaxes(1, 2), A.T, h0, dy,
            chunk, di_block, interpret)
        dB = dbt.sum(0).swapaxes(1, 2).astype(Bc.dtype)
        dC = dct.sum(0).swapaxes(1, 2).astype(Cc.dtype)
        dA = dat.sum(0).T.astype(A.dtype)
    return du, ddt, dB, dC, dA


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssm_scan(u, dt, Bc, Cc, A, *, chunk: int = 128, di_block: int = 1024,
             interpret: bool = False) -> jax.Array:
    """u,dt: (B,S,di); Bc,Cc: (B,S,ds); A: (di,ds) -> y (B,S,di) fp32-acc.
    Matches kernels.ref.ssm_scan_ref, and so does its gradient.  S must be
    a multiple of GROUP; a sequence longer than ``chunk`` and not a
    multiple of it is padded at the end with dt = 0 steps, which leave the
    state as it is."""
    B, S, di = u.shape
    assert S % GROUP == 0, f"sequence {S} is not a multiple of {GROUP}"
    chunk = min(chunk, S)
    assert chunk % GROUP == 0, f"chunk {chunk} is not a multiple of {GROUP}"
    di_block = math.gcd(di_block, di)
    pad = -S % chunk
    if pad:
        u, dt, Bc, Cc = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                         for x in (u, dt, Bc, Cc))
    y = _scan(chunk, di_block, interpret, u, dt, Bc, Cc, A)
    return y[:, :S] if pad else y
