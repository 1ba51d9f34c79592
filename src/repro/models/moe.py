"""Mixture-of-Experts layer: top-k router + capacity-based dispatch.

TPU-idiomatic: static shapes throughout (capacity buckets instead of ragged
dispatch) and **per-row dispatch** — each batch row dispatches its own tokens
with per-row expert capacity.  The scatter/gather then carries the batch dim,
which GSPMD partitions cleanly over the ``data`` axis (no cross-shard
dispatch traffic; expert weights are TP-sharded over ``model``).  Compute is
proportional to ``top_k * capacity_factor`` — only *active* expert FLOPs, so
the roofline useful-work ratio stays honest.

EP-MoE (experts sharded over ``model`` with all-to-all dispatch) is provided
in parallel/ep_moe.py for n_experts % tp == 0 (phi3.5-moe).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import _he


def init_moe(key, cfg: ModelConfig) -> dict:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": _he(ks[0], (D, E), jnp.float32),
        "w_gate": _he(ks[1], (E, D, F), cfg.pdtype, fan_in=D),
        "w_up": _he(ks[2], (E, D, F), cfg.pdtype, fan_in=D),
        "w_down": _he(ks[3], (E, F, D), cfg.pdtype, fan_in=F),
    }


def row_capacity(seq: int, cfg: ModelConfig) -> int:
    c = int(seq * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(1, -(-c // 8) * 8) if seq >= 8 else max(1, c)


def _constrain(x, spec_parts):
    """Sharding constraint that no-ops without a mesh (CPU smoke tests)."""
    try:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.PartitionSpec(*spec_parts))
    except RuntimeError:
        return x


def moe_mlp(p, x, cfg: ModelConfig):
    """x: (B,S,D) -> (B,S,D), plus Switch-style aux load-balance loss."""
    if cfg.moe_impl == "shard_map" and cfg.mesh_axes:
        return moe_mlp_manual(p, x, cfg)
    return _moe_mlp_gspmd(p, x, cfg)


def _moe_mlp_gspmd(p, x, cfg: ModelConfig):
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = row_capacity(S, cfg)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    gates = jax.nn.softmax(logits, axis=-1)                       # (B,S,E)
    gval, gidx = jax.lax.top_k(gates, K)                          # (B,S,K)
    gval = gval / jnp.sum(gval, axis=-1, keepdims=True)

    me = jnp.mean(gates, axis=(0, 1))
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(gidx, E, dtype=jnp.float32),
                          axis=2), axis=(0, 1))
    aux = E * jnp.sum(me * ce)

    buf = jnp.zeros((B, E, C, D), x.dtype)
    b_idx = jnp.arange(B)[:, None]
    keep_w, pos_k, idx_k = [], [], []
    fill = jnp.zeros((B, E), jnp.int32)
    for k in range(K):
        e = gidx[..., k]                                          # (B,S)
        oh = jax.nn.one_hot(e, E, dtype=jnp.int32)                # (B,S,E)
        rank = jnp.cumsum(oh, axis=1) - oh                        # rank in row
        pos = jnp.take_along_axis(rank, e[..., None], axis=2)[..., 0] \
            + jnp.take_along_axis(fill, e, axis=1)                # (B,S)
        keep = pos < C
        buf = buf.at[b_idx, e, jnp.where(keep, pos, C - 1)].add(
            jnp.where(keep[..., None], x, 0).astype(buf.dtype),
            mode="drop")
        fill = fill + jnp.sum(oh, axis=1)
        keep_w.append(jnp.where(keep, gval[..., k], 0.0))
        pos_k.append(jnp.where(keep, pos, 0))
        idx_k.append(e)

    # Sharding shape under TP (GSPMD hints — crucial: without them the
    # partitioner all-reduces the full (B,E,C,D) capacity buffer, ~8 GB/dev
    # per layer):
    #   buf    (B,E,C,D)  dp, -, -, -      dispatch local to each data shard
    #   h      (B,E,C,F)  dp, -, -, tp     expert FFN dim TP-sharded
    #   y      (B,E,C,D)  dp, -, -, tp     => contraction over sharded F
    #                                         lowers to a REDUCE-SCATTER
    #   out    (B,S,D)    dp, -, tp        gather along (b,e,c); D untouched
    if cfg.mesh_axes:
        dp, tpax = cfg.mesh_axes
        buf = _constrain(buf, (dp, None, None, None))
    g = jnp.einsum("becd,edf->becf", buf, p["w_gate"],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("becd,edf->becf", buf, p["w_up"],
                   preferred_element_type=jnp.float32)
    h = (jnp.square(jax.nn.relu(g + u)) if cfg.act == "sq_relu"
         else jax.nn.silu(g) * u).astype(buf.dtype)
    if cfg.mesh_axes:
        h = _constrain(h, (dp, None, None, tpax))
    y = jnp.einsum("becf,efd->becd", h, p["w_down"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if cfg.mesh_axes:
        y = _constrain(y, (dp, None, None, tpax))

    # NOTE: no constraint on `out` — it must stay free so GSPMD aligns it
    # with the (sequence-sharded) residual carry; pinning it D-sharded makes
    # the attention backward reshard scores through an involuntary full
    # rematerialization (34 GB/layer all-gathers).
    out = jnp.zeros((B, S, D), jnp.float32)
    for k in range(K):
        out = out + keep_w[k][..., None] * \
            y[b_idx, idx_k[k], pos_k[k]].astype(jnp.float32)
    return out.astype(x.dtype), aux


# ------------------------------------------------- manual shard_map MoE ----
def _moe_core_local(p_loc, x, cfg: ModelConfig, e_offset=None, e_per=None):
    """All-local MoE math on a full-sequence block.

    TP-MoE (default): F-SHARDED expert weights; output is PARTIAL over the F
    contraction.  EP-MoE (e_offset/e_per given): this shard owns ``e_per``
    full-width experts starting at ``e_offset``; tokens routed elsewhere are
    masked out.  Either way the caller's psum_scatter over the model axis
    completes the sum (F partials or expert contributions) and re-shards the
    sequence."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    E_loc = e_per if e_per is not None else E
    off = e_offset if e_offset is not None else 0
    C = row_capacity(S, cfg)
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        p_loc["router"])
    gates = jax.nn.softmax(logits, axis=-1)
    gval, gidx = jax.lax.top_k(gates, K)
    gval = gval / jnp.sum(gval, axis=-1, keepdims=True)
    me = jnp.mean(gates, axis=(0, 1))
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(gidx, E, dtype=jnp.float32),
                          axis=2), axis=(0, 1))
    aux = E * jnp.sum(me * ce)

    buf = jnp.zeros((B, E_loc, C, D), x.dtype)
    b_idx = jnp.arange(B)[:, None]
    keep_w, pos_k, idx_k = [], [], []
    fill = jnp.zeros((B, E), jnp.int32)
    for k in range(K):
        e = gidx[..., k]
        oh = jax.nn.one_hot(e, E, dtype=jnp.int32)
        rank = jnp.cumsum(oh, axis=1) - oh
        pos = jnp.take_along_axis(rank, e[..., None], axis=2)[..., 0] \
            + jnp.take_along_axis(fill, e, axis=1)
        e_loc = e - off
        mine = (e_loc >= 0) & (e_loc < E_loc)
        keep = (pos < C) & mine
        buf = buf.at[b_idx, jnp.where(mine, e_loc, 0),
                     jnp.where(keep, pos, C - 1)].add(
            jnp.where(keep[..., None], x, 0).astype(buf.dtype), mode="drop")
        fill = fill + jnp.sum(oh, axis=1)
        keep_w.append(jnp.where(keep, gval[..., k], 0.0))
        pos_k.append(jnp.where(keep, pos, 0))
        idx_k.append(jnp.where(mine, e_loc, 0))

    g = jnp.einsum("becd,edf->becf", buf, p_loc["w_gate"],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("becd,edf->becf", buf, p_loc["w_up"],
                   preferred_element_type=jnp.float32)
    h = (jnp.square(jax.nn.relu(g + u)) if cfg.act == "sq_relu"
         else jax.nn.silu(g) * u).astype(buf.dtype)
    y = jnp.einsum("becf,efd->becd", h, p_loc["w_down"],
                   preferred_element_type=jnp.float32)
    out = jnp.zeros((B, S, D), jnp.float32)
    for k in range(K):
        out = out + keep_w[k][..., None] * y[b_idx, idx_k[k], pos_k[k]]
    return out, aux


def moe_mlp_manual(p, x, cfg: ModelConfig):
    """Manual SP-boundary MoE (the §Perf fix for the collective-bound MoE
    cells): ICCL all-gather of the seq-sharded activations in, fully LOCAL
    dispatch + expert FFN, one psum_scatter out — which simultaneously
    completes the partial sum and re-shards the sequence.  Per-layer traffic
    is O(B*S*D) like a dense TP layer, instead of the O(B*E*C*D)
    capacity-buffer reductions GSPMD emits.

    Two expert layouts (cfg.moe_impl):
      shard_map     TP-MoE: every shard holds all experts at F/tp width
                    (partial sum over F)
      shard_map_ep  EP-MoE (n_experts % tp == 0, e.g. phi3.5's 16/16):
                    each shard owns full-width experts; the psum merges
                    expert contributions.  Full-width FFNs keep the MXU
                    dimension at d_ff instead of d_ff/16."""
    dp, tpax = cfg.mesh_axes
    P = jax.sharding.PartitionSpec
    ep = cfg.moe_impl == "shard_map_ep"

    def body(xs, router, wg, wu, wd):
        xg = jax.lax.all_gather(xs, tpax, axis=1, tiled=True)
        if ep:
            n = jax.lax.axis_size(tpax)
            e_per = cfg.n_experts // n
            off = jax.lax.axis_index(tpax) * e_per
            out, aux = _moe_core_local(
                {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd},
                xg, cfg, e_offset=off, e_per=e_per)
        else:
            out, aux = _moe_core_local(
                {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd},
                xg, cfg)
        out = jax.lax.psum_scatter(out.astype(xs.dtype), tpax,
                                   scatter_dimension=1, tiled=True)
        aux = jax.lax.pmean(aux, dp)
        return out, aux

    if ep:
        w_specs = (P(tpax, None, None),) * 3
    else:
        w_specs = (P(None, None, tpax), P(None, None, tpax),
                   P(None, tpax, None))
    return jax.shard_map(
        body, in_specs=(P(dp, tpax, None), P()) + w_specs,
        out_specs=(P(dp, tpax, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
