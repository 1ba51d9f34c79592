"""Mixture-of-Experts layers.

Softmax-routed (mixtral, phi3.5-moe): top-k router + capacity-based
dispatch.

TPU-idiomatic: static shapes throughout (capacity buckets instead of ragged
dispatch) and **per-row dispatch** — each batch row dispatches its own tokens
with per-row expert capacity.  The scatter/gather then carries the batch dim,
which GSPMD partitions cleanly over the ``data`` axis (no cross-shard
dispatch traffic; expert weights are TP-sharded over ``model``).  Compute is
proportional to ``top_k * capacity_factor`` — only *active* expert FLOPs, so
the roofline useful-work ratio stays honest.

EP-MoE without an all-to-all: ``moe_impl="shard_map_ep"`` (below) gives each
``model`` shard n_experts / tp full-width experts and sums their parts.

Sigmoid-routed (DeepSeek-V3's ``noaux_tc``, Moonlight): ``moe_share`` is an
expert layer that holds the first ``experts_held`` experts.  It routes over
all experts, sorts the (token, choice) rows by expert and runs grouped
matmuls over the held experts only, dropping no row; rows routed to experts
held elsewhere add nothing here.  Shared experts are added by the block (a
plain MLP).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from repro.kernels import ops
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


# ------------------------------------------ sigmoid-routed held share ------
def init_moe_share(key, cfg: ModelConfig) -> dict:
    """Router over all ``n_experts`` (fp32) and the ``experts_held_``
    experts this chip holds."""
    D, F, E, Eh = cfg.d_model, cfg.d_expert_, cfg.n_experts, cfg.experts_held_
    ks = jax.random.split(key, 4)
    return {
        "router": _he(ks[0], (D, E), jnp.float32),
        "w_gate": _he(ks[1], (Eh, D, F), cfg.pdtype, fan_in=D),
        "w_up": _he(ks[2], (Eh, D, F), cfg.pdtype, fan_in=D),
        "w_down": _he(ks[3], (Eh, F, D), cfg.pdtype, fan_in=F),
    }


BALANCE_STEPS = 12


def balance_bias(scores, k: int):
    """scores: (T, E) -> the selection bias (E,) under which each expert
    takes about T*k/E of the tokens' top-k picks.  It is the aux-free
    update, bias += gamma * sign(mean load - expert's load), run to
    balance on the step's own scores: from each expert's mean score
    subtracted, ``BALANCE_STEPS`` times, gamma a fifth of the scores'
    spread shrinking by 0.7 a time.  It takes no gradient."""
    s = jax.lax.stop_gradient(scores)
    T, E = s.shape
    target = T * k / E
    gamma = 0.2 * jnp.std(s)

    def step(i, b):
        _, idx = jax.lax.top_k(s + b, k)
        load = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=(0, 1))
        return b + gamma * 0.7 ** i * jnp.sign(target - load)

    return jax.lax.fori_loop(0, BALANCE_STEPS, step, -jnp.mean(s, axis=0))


GMM_ROWS = 256      # the Pallas grouped matmul's row tile


def _gmm_tiling(m: int, k: int, n: int):
    """Tiles of the Pallas grouped matmul, for each of its problems
    (forward, and the transposed ones of the backward pass): 512 along a
    dim that 512 divides, else the whole dim (1,408, an expert's width)."""
    return (GMM_ROWS, 512 if k % 512 == 0 else k, 512 if n % 512 == 0 else n)


def grouped_matmul(a, w, sizes):
    """a: (M, K) rows sorted by group, w: (G, K, N), sizes: (G,) ->
    (M, N) float32; rows past sum(sizes) are left undefined.  On one TPU
    chip the Pallas grouped matmul (megablox), which visits only the
    groups' row tiles: forward and backward at the Moonlight cell's shapes
    (49,152 rows, 8 experts of 2,048 x 1,408) it took 8.6 ms on a TPU v5e
    against ``jax.lax.ragged_dot``'s 15.1 ms.  Elsewhere ``ragged_dot``."""
    if (ops.use_pallas() and a.shape[0] % GMM_ROWS == 0
            and jax.sharding.get_abstract_mesh().size <= 1):
        return megablox.gmm(a, w, sizes, jnp.float32, _gmm_tiling)
    return jax.lax.ragged_dot(a, w, sizes, preferred_element_type=jnp.float32)


def moe_share(p, x, cfg: ModelConfig):
    """x: (B,S,D) -> (this chip's experts' part of the routed experts'
    output (B,S,D), {"rows": rows routed to held experts, "rows_max": the
    largest held expert's rows}).

    Scores are sigmoid(x W_router) in fp32; each token takes the top_k of
    score + ``balance_bias(scores)`` (the bias only selects), and weighs
    its choices by their scores, normalised to sum 1, times
    ``routed_scale``.  The T*top_k (token, choice) rows are sorted by held
    expert (rows for experts held elsewhere last) and run through grouped
    matmuls whose static row bound is T*top_k."""
    B, S, D = x.shape
    K, Eh = cfg.top_k, cfg.experts_held_
    T = B * S
    xt = x.reshape(T, D)
    with jax.named_scope("moe_route"):
        scores = jax.nn.sigmoid(jnp.einsum(
            "td,de->te", xt.astype(jnp.float32), p["router"],
            precision=jax.lax.Precision.HIGHEST))
        _, idx = jax.lax.top_k(scores + balance_bias(scores, K), K)  # (T,K)
        w = jnp.take_along_axis(scores, idx, axis=1)
        w = w / jnp.sum(w, axis=-1, keepdims=True) * cfg.routed_scale
        e_row = jnp.where(idx < Eh, idx, Eh).reshape(-1)          # (T*K,)
        order = jnp.argsort(e_row, stable=True)
        sizes = jnp.sum(e_row[:, None] == jnp.arange(Eh), axis=0,
                        dtype=jnp.int32)
        # a grouped matmul leaves its rows past the held groups undefined,
        # forward and in its transposes, so every such row of its inputs,
        # outputs and their cotangents is masked (a select, as the
        # undefined values may not be finite)
        valid = (jnp.arange(T * K) < jnp.sum(sizes))[:, None]
        xs = jnp.where(valid, xt[order // K], 0)
    with jax.named_scope("moe_experts"):
        def gmm(a, wt):
            return jnp.where(valid, grouped_matmul(a, wt, sizes), 0.0)

        h = jax.nn.silu(gmm(xs, p["w_gate"])) * gmm(xs, p["w_up"])
        ys = gmm(h.astype(x.dtype), p["w_down"])
    with jax.named_scope("moe_route"):
        # back to (token, choice) order, where the rows of experts held
        # elsewhere are the masked ones
        inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * K))
        out = jnp.einsum("tk,tkd->td", w, ys[inv].reshape(T, K, D))
    stats = {"rows": jnp.sum(sizes), "rows_max": jnp.max(sizes)}
    return out.astype(x.dtype).reshape(B, S, D), stats
