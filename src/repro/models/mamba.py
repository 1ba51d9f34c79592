"""Mamba-1 block (falcon-mamba-7b): selective state-space model.

The selective scan runs the Pallas kernel pair of kernels/ssm_scan.py on
the TPU (forward and backward, state in VMEM), per shard under
``jax.shard_map`` over a mesh of more than one device.  Where the backend
or the shapes do not suit the kernel it runs a chunked scan in jnp:
sequential ``lax.scan`` over chunks with a parallel ``associative_scan``
inside each chunk.

Decode path is the O(1) recurrence (no KV cache — the reason long_500k runs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ops, ssm_scan as ss
from repro.models.config import ModelConfig
from repro.models.layers import _he

CHUNK = 128


def init_mamba(key, cfg: ModelConfig) -> dict:
    D, di, ds, dr = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank_
    ks = jax.random.split(key, 6)
    a = jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32)[None, :], (di, 1))
    return {
        "in_proj": _he(ks[0], (D, 2 * di), cfg.pdtype),
        "conv_w": _he(ks[1], (cfg.ssm_conv, di), cfg.pdtype, fan_in=cfg.ssm_conv),
        "conv_b": jnp.zeros((di,), cfg.pdtype),
        "x_proj": _he(ks[2], (di, dr + 2 * ds), cfg.pdtype),
        "dt_proj": _he(ks[3], (dr, di), cfg.pdtype),
        "dt_bias": jnp.full((di,), -4.6, cfg.pdtype),  # softplus^-1(0.01)
        "A_log": jnp.log(a),                            # (di, ds) fp32
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": _he(ks[4], (di, D), cfg.pdtype),
    }


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv1d.  x: (B,S,di), w: (K,di).
    state: (B,K-1,di) trailing context for decode; returns (y, new_state)."""
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)                # (B, S+K-1, di)
    y = sum(xp[:, i:i + x.shape[1]] * w[i][None, None] for i in range(K))
    new_state = xp[:, -(K - 1):]
    return (y + b[None, None]).astype(x.dtype), new_state


def _ssm_params(p, u, cfg: ModelConfig):
    """u: (B,S,di) post-conv activations -> dt,(B,S,di) Bc,Cc (B,S,ds)."""
    ds, dr = cfg.ssm_state, cfg.dt_rank_
    proj = jnp.einsum("bsd,de->bse", u, p["x_proj"],
                      preferred_element_type=jnp.float32)
    dt, Bc, Cc = jnp.split(proj, [dr, dr + ds], axis=-1)
    dt = jnp.einsum("bsr,rd->bsd", dt, p["dt_proj"].astype(jnp.float32))
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    return dt, Bc, Cc


def _chunked_scan(u, dt, Bc, Cc, A, chunk: int):
    """The jnp scan: u,dt: (B,S,di); Bc,Cc: (B,S,ds); A: (di,ds) -> y
    (B,S,di) fp32, before the D skip and the gate."""
    B, S, di = u.shape
    ds = Bc.shape[-1]
    nc = max(1, S // chunk)
    chunk = S // nc

    # per-step decay exponent and input: (B,S,di,ds)
    def chunk_body(h, xs):
        dt_c, u_c, B_c, C_c = xs                       # (B,chunk,…)
        la = dt_c[..., None] * A[None, None]           # log-decay (B,c,di,ds)
        b = (dt_c * u_c)[..., None] * B_c[:, :, None, :]

        def comb(l, r):
            (la1, b1), (la2, b2) = l, r
            return la1 + la2, jnp.exp(la2) * b1 + b2

        la_cum, b_cum = jax.lax.associative_scan(comb, (la, b), axis=1)
        h_contrib = jnp.exp(la_cum) * h[:, None]       # carry-in propagated
        h_all = h_contrib + b_cum                      # (B,c,di,ds)
        y = jnp.sum(h_all * C_c[:, :, None, :], axis=-1)
        return h_all[:, -1], y

    xs = tuple(a.reshape(B, nc, chunk, *a.shape[2:]).swapaxes(0, 1)
               for a in (dt.astype(jnp.float32), u.astype(jnp.float32),
                         Bc.astype(jnp.float32), Cc.astype(jnp.float32)))
    h0 = jnp.zeros((B, di, ds), jnp.float32)
    _, ys = jax.lax.scan(chunk_body, h0, xs)
    return ys.swapaxes(0, 1).reshape(B, S, di)


def _kernel_scan(u, dt, Bc, Cc, A, chunk: int):
    """The Pallas scan, or None where the sequence is not a multiple of
    the kernel's row group or d_inner not a multiple of 128 lanes.

    The compiler does not partition a Mosaic kernel, so over a mesh of
    more than one device the kernel runs per shard under ``jax.shard_map``
    over every axis of the mesh in scope: batch split over the other axes,
    as many as divide it, innermost first, and d_inner over ``model`` where
    that leaves whole 128-lane blocks; an axis that splits neither holds a
    replica.  The scan is independent per (batch, d_inner), so the shards
    need no exchange.  A step over several devices runs inside
    ``jax.set_mesh``, as the Trainer's does."""
    B, S, di = u.shape
    if S % ss.GROUP or di % 128:
        return None
    scan = functools.partial(ss.ssm_scan, chunk=chunk)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return scan(u, dt, Bc, Cc, A)
    n_tp = mesh.shape.get("model", 1)
    tp = "model" if n_tp > 1 and di % (128 * n_tp) == 0 else None
    dp, n_dp = (), 1
    for a in reversed(mesh.axis_names):
        if a != "model" and B % (n_dp * mesh.shape[a]) == 0:
            dp, n_dp = (a,) + dp, n_dp * mesh.shape[a]
    P = jax.sharding.PartitionSpec
    seq, rows = P(dp, None, tp), P(dp, None, None)
    return jax.shard_map(scan, in_specs=(seq, seq, rows, rows, P(tp, None)),
                         out_specs=seq, check_vma=False)(u, dt, Bc, Cc, A)


@jax.named_scope("ssm_scan")
def selective_scan(u, dt, Bc, Cc, A, D, z, chunk: int = CHUNK):
    """u,dt,z: (B,S,di); Bc,Cc: (B,S,ds); A: (di,ds) -> y: (B,S,di)."""
    y = _kernel_scan(u, dt, Bc, Cc, A, chunk) if ops.use_pallas() else None
    if y is None:
        y = _chunked_scan(u, dt, Bc, Cc, A, chunk)
    uf = u.astype(jnp.float32)
    y = y + uf * D[None, None]
    return (y * jax.nn.silu(z.astype(jnp.float32))).astype(u.dtype)


def mamba_block(p, x, cfg: ModelConfig):
    """x: (B,S,D) -> (B,S,D)  (training / prefill, no state returned)."""
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"],
                    preferred_element_type=jnp.float32).astype(x.dtype)
    u, z = jnp.split(xz, 2, axis=-1)
    u, _ = _causal_conv(u, p["conv_w"], p["conv_b"])
    u = jax.nn.silu(u.astype(jnp.float32)).astype(x.dtype)
    dt, Bc, Cc = _ssm_params(p, u, cfg)
    A = -jnp.exp(p["A_log"])
    y = selective_scan(u, dt, Bc, Cc, A, p["D"], z)
    return jnp.einsum("bsd,de->bse", y, p["out_proj"],
                      preferred_element_type=jnp.float32).astype(x.dtype)


def init_mamba_state(cfg: ModelConfig, batch: int, n_layers: int) -> dict:
    di, ds, K = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    return {"h": jnp.zeros((n_layers, batch, di, ds), jnp.float32),
            "conv": jnp.zeros((n_layers, batch, K - 1, di), cfg.adtype)}


def mamba_decode(p, x, h, conv_state, cfg: ModelConfig):
    """One-step recurrence.  x: (B,1,D); h: (B,di,ds); conv: (B,K-1,di)."""
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"],
                    preferred_element_type=jnp.float32).astype(x.dtype)
    u, z = jnp.split(xz, 2, axis=-1)
    u, conv_state = _causal_conv(u, p["conv_w"], p["conv_b"], conv_state)
    u = jax.nn.silu(u.astype(jnp.float32)).astype(x.dtype)
    dt, Bc, Cc = _ssm_params(p, u, cfg)                  # (B,1,·)
    A = -jnp.exp(p["A_log"])
    dt0, B0, C0, u0 = dt[:, 0], Bc[:, 0], Cc[:, 0], u[:, 0].astype(jnp.float32)
    decay = jnp.exp(dt0[..., None] * A[None])            # (B,di,ds)
    h = decay * h + (dt0 * u0)[..., None] * B0[:, None, :]
    y = jnp.sum(h * C0[:, None, :], axis=-1) + u0 * p["D"][None]
    y = (y * jax.nn.silu(z[:, 0].astype(jnp.float32)))[:, None].astype(x.dtype)
    out = jnp.einsum("bsd,de->bse", y, p["out_proj"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    return out, h, conv_state
