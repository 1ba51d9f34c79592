"""Decoder-only LM covering dense / MoE / SSM / hybrid / VLM families.

Uniform-kind stacks (dense, moe, ssm) are ``lax.scan``-ed over a stacked
layer dim so the lowered HLO is O(1) in depth (critical for the 512-device
dry-run compile); leading dense layers before an expert stack
(``cfg.first_dense``, Moonlight's layer 0) run unrolled ahead of the scan.
Hybrid stacks (recurrentgemma) are unrolled because the block kind
alternates.

Public entry points:
  init_lm(key, cfg)                          -> params
  lm_forward(params, batch, cfg)             -> (logits, aux_loss)
  lm_prefill(params, batch, cfg, max_len)    -> (last_logits, cache)
  lm_decode_step(params, token, cache, pos, cfg) -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import griffin, mamba, moe
from repro.models.config import ModelConfig
from repro.models.layers import (_he, attention, decode_attention, init_attention,
                                 init_kv_cache, init_mla, init_mlp,
                                 init_rmsnorm, mla, mlp, rmsnorm)


# ------------------------------------------------------------------ init ---
def _init_block(key, cfg: ModelConfig, kind: str, experts: bool = True) -> dict:
    D = cfg.d_model
    ks = jax.random.split(key, 2)
    p: Dict[str, Any] = {"ln1": init_rmsnorm(D, cfg.pdtype)}
    if kind == "attn":
        if cfg.mla:
            p["mla"] = init_mla(ks[0], cfg)
        else:
            p["attn"] = init_attention(ks[0], cfg)
        p["ln2"] = init_rmsnorm(D, cfg.pdtype)
        if not (cfg.n_experts and experts):
            p["mlp"] = init_mlp(ks[1], cfg)
        elif cfg.sigmoid_moe:
            k_moe, k_shared = jax.random.split(ks[1])
            p["moe"] = moe.init_moe_share(k_moe, cfg)
            if cfg.n_shared_experts:
                p["shared"] = init_mlp(
                    k_shared, cfg, d_ff=cfg.d_expert_ * cfg.n_shared_experts)
        else:
            p["moe"] = moe.init_moe(ks[1], cfg)
    elif kind == "ssm":
        p["ssm"] = mamba.init_mamba(ks[0], cfg)
    elif kind == "rec":
        p["rec"] = griffin.init_rglru_block(ks[0], cfg)
        p["ln2"] = init_rmsnorm(D, cfg.pdtype)
        p["mlp"] = init_mlp(ks[1], cfg)
    else:
        raise ValueError(kind)
    return p


def _hybrid_layout(cfg: ModelConfig):
    """(pattern, n_full_groups, tail_kinds) — hybrid stacks scan over full
    pattern cycles (e.g. 38 = 12 x (rec,rec,attn) + 2 tail rec layers)."""
    pat = cfg.block_pattern or ("rec", "rec", "attn")
    n_groups = cfg.num_layers // len(pat)
    kinds = cfg.layer_kinds()
    return pat, n_groups, kinds[n_groups * len(pat):]


def init_lm(key, cfg: ModelConfig) -> dict:
    kinds = cfg.layer_kinds()
    k_emb, k_blocks, k_out = jax.random.split(key, 3)
    params: Dict[str, Any] = {
        "embed": _he(k_emb, (cfg.vocab_size, cfg.d_model), cfg.pdtype),
        "final_norm": init_rmsnorm(cfg.d_model, cfg.pdtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = _he(k_out, (cfg.d_model, cfg.vocab_size),
                                cfg.pdtype)
    if len(set(kinds)) == 1 and cfg.scan_layers:
        keys = jax.random.split(k_blocks, cfg.num_layers)
        n_dense = cfg.first_dense
        if n_dense:
            params["dense"] = [_init_block(keys[i], cfg, kinds[0],
                                           experts=False)
                               for i in range(n_dense)]
        params["blocks"] = jax.vmap(
            lambda k: _init_block(k, cfg, kinds[0]))(keys[n_dense:])
        params["_stacked"] = jnp.zeros(())  # marker (scalar keeps pytree sane)
    elif cfg.family == "hybrid" and cfg.scan_layers:
        pat, n_groups, tail_kinds = _hybrid_layout(cfg)

        def init_group(k):
            ks = jax.random.split(k, len(pat))
            return {f"b{i}": _init_block(ks[i], cfg, pat[i])
                    for i in range(len(pat))}

        gkeys = jax.random.split(k_blocks, n_groups + 1)
        params["groups"] = jax.vmap(init_group)(gkeys[:n_groups])
        tkeys = jax.random.split(gkeys[-1], max(len(tail_kinds), 1))
        params["tail"] = [
            _init_block(tkeys[i], cfg, tail_kinds[i])
            for i in range(len(tail_kinds))]
    else:
        keys = jax.random.split(k_blocks, cfg.num_layers)
        params["layers"] = [
            _init_block(keys[i], cfg, kinds[i])
            for i in range(cfg.num_layers)]
    return params


# --------------------------------------------------------------- forward ---
def _constrain_act(x, cfg: ModelConfig, parts=None):
    parts = parts if parts is not None else cfg.act_sharding
    if not parts:
        return x
    try:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.PartitionSpec(*parts))
    except RuntimeError:  # no mesh context (CPU smoke tests)
        return x


def _remat(fn, cfg: ModelConfig):
    if cfg.remat_policy == "save_proj":
        # saves un-batched dots (the q/k/v/o/mlp projections) and recomputes
        # batched dots (the O(S^2) attention score/value einsums) — the
        # memory/compute sweet spot when flash attention isn't fused
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def _block(p, x, cfg: ModelConfig, kind: str):
    """One block: (x, load-balance aux, counters) where the counters, of a
    sigmoid-routed expert layer only, are ``moe.moe_share``'s."""
    x = _constrain_act(x, cfg)
    stats: Dict[str, Any] = {}
    if kind == "attn":
        if "mla" in p:
            with jax.named_scope("mla"):
                x = x + mla(p["mla"], rmsnorm(p["ln1"], x, cfg.norm_eps), cfg)
        else:
            with jax.named_scope("attn"):
                x = x + attention(p["attn"],
                                  rmsnorm(p["ln1"], x, cfg.norm_eps), cfg)
        with jax.named_scope("mlp"):
            h = rmsnorm(p["ln2"], x, cfg.norm_eps)
            aux = jnp.zeros((), jnp.float32)
            if "moe" in p and cfg.sigmoid_moe:
                y, stats = moe.moe_share(p["moe"], h, cfg)
                if "shared" in p:
                    y = y + mlp(p["shared"], h, cfg)
            elif "moe" in p:
                y, aux = moe.moe_mlp(p["moe"], h, cfg)
            else:
                y = mlp(p["mlp"], h, cfg)
            return x + y, aux, stats
    if kind == "ssm":
        with jax.named_scope("ssm_block"):
            y = mamba.mamba_block(p["ssm"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                                  cfg)
            return x + y, jnp.zeros((), jnp.float32), stats
    if kind == "rec":
        x = x + griffin.rglru_block(
            p["rec"], rmsnorm(p["ln1"], x, cfg.norm_eps), cfg)
        y = mlp(p["mlp"], rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
        return x + y, jnp.zeros((), jnp.float32), stats
    raise ValueError(kind)


def _block_fwd(p, x, cfg: ModelConfig, kind: str):
    """One block: (x, load-balance aux)."""
    x, aux, _ = _block(p, x, cfg, kind)
    return x, aux


@jax.named_scope("embed")
def _embed_tokens(params, tokens, cfg: ModelConfig,
                  extra_embeds: Optional[jax.Array]) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.adtype)
    if extra_embeds is not None:  # VLM / audio stub frontend: prepend
        x = jnp.concatenate([extra_embeds.astype(cfg.adtype), x], axis=1)
    return x


@jax.named_scope("head_loss")
def final_norm(params, x, cfg: ModelConfig) -> jax.Array:
    return rmsnorm(params["final_norm"], x, cfg.norm_eps)


@jax.named_scope("head_loss")
def _unembed(params, x, cfg: ModelConfig) -> jax.Array:
    w = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    return jnp.einsum("bsd,dv->bsv", x, w,
                      preferred_element_type=jnp.float32)


def _trunk(params, tokens, cfg: ModelConfig,
           extra_embeds: Optional[jax.Array] = None):
    """Embedding, blocks and final norm: (features (B,S,D), aux, counters).
    The counters of the expert layers are summed over the layers
    (``moe_rows``) and maxed (``moe_rows_max``)."""
    kinds = cfg.layer_kinds()
    x = _embed_tokens(params, tokens, cfg, extra_embeds)
    stats: Dict[str, Any] = {}

    def blk(kind):
        fwd = functools.partial(_block, cfg=cfg, kind=kind)
        return _remat(fwd, cfg) if cfg.remat else fwd

    if "blocks" in params:
        fwd = blk(kinds[0])
        lead = jnp.zeros((), jnp.float32)
        for p in params.get("dense", ()):
            x, a, _ = fwd(p, x)
            lead = lead + a

        def body(x, p):
            y, aux, st = fwd(p, x)
            return y, (aux, st)

        x, (auxs, st) = jax.lax.scan(body, x, params["blocks"])
        # a stack without a dense prefix keeps the program it had
        aux = jnp.sum(auxs) + lead if "dense" in params else jnp.sum(auxs)
        if st:
            stats = {"moe_rows": jnp.sum(st["rows"]),
                     "moe_rows_max": jnp.max(st["rows_max"])}
    elif "groups" in params:
        pat, n_groups, tail_kinds = _hybrid_layout(cfg)

        def group_fwd(p, x):
            aux = jnp.zeros((), jnp.float32)
            for i, kind in enumerate(pat):
                x, a = _block_fwd(p[f"b{i}"], x, cfg, kind)
                aux = aux + a
            return x, aux

        gf = _remat(group_fwd, cfg) if cfg.remat else group_fwd
        x, auxs = jax.lax.scan(lambda x, p: gf(p, x), x, params["groups"])
        aux = jnp.sum(auxs)
        for i, kind in enumerate(tail_kinds):
            x, a, _ = blk(kind)(params["tail"][i], x)
            aux = aux + a
    else:
        aux = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(kinds):
            x, a, _ = blk(kind)(params["layers"][i], x)
            aux = aux + a
    x = _constrain_act(x, cfg, cfg.head_act_sharding)
    return final_norm(params, x, cfg), aux, stats


def lm_forward(params, tokens, cfg: ModelConfig,
               extra_embeds: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B,S) int32 -> (logits (B,S_total,V) fp32, aux_loss)."""
    x, aux, _ = _trunk(params, tokens, cfg, extra_embeds)
    return _unembed(params, x, cfg), aux


def lm_features(params, tokens, cfg: ModelConfig,
                extra_embeds: Optional[jax.Array] = None):
    """Forward WITHOUT the unembed: (features (B,S,D), unembed_w, aux,
    counters).  Lets the loss fuse the head into sequence chunks so the
    (B,S,V) logits never materialize (the dominant temp for 150k-256k
    vocabs)."""
    x, aux, stats = _trunk(params, tokens, cfg, extra_embeds)
    w = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    return x, w, aux, stats


def _check_servable(cfg: ModelConfig) -> None:
    if cfg.mla or cfg.first_dense:
        raise NotImplementedError(
            f"{cfg.name}: serving needs multi-head latent attention's latent "
            "KV cache and a dense-prefix stack in prefill and decode, which "
            "are not built; this model trains only")


# --------------------------------------------------------------- prefill ---
def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    _check_servable(cfg)
    kinds = cfg.layer_kinds()
    n_attn = sum(k == "attn" for k in kinds)
    cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    if n_attn:
        cache["kv"] = init_kv_cache(cfg, batch, max_len, n_attn)
    if any(k == "ssm" for k in kinds):
        cache["ssm"] = mamba.init_mamba_state(
            cfg, batch, sum(k == "ssm" for k in kinds))
    if any(k == "rec" for k in kinds):
        cache["rec"] = griffin.init_rglru_state(
            cfg, batch, sum(k == "rec" for k in kinds))
    return cache


def _prefill_attn_block(p, x, cfg: ModelConfig, keep: int):
    """One attention block; returns (x, (k_cache, v_cache)) where the caches
    are the last ``keep`` positions (rolling-window layout for SWA)."""
    x = _constrain_act(x, cfg)
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    o, k, v = attention(p["attn"], h, cfg, return_kv=True)
    x = x + o
    hh = rmsnorm(p["ln2"], x, cfg.norm_eps)
    y = (moe.moe_mlp(p["moe"], hh, cfg)[0] if cfg.n_experts
         else mlp(p["mlp"], hh, cfg))
    S = k.shape[1]
    return x + y, (k[:, S - keep:], v[:, S - keep:])


def lm_prefill(params, tokens, cfg: ModelConfig, max_len: int,
               extra_embeds: Optional[jax.Array] = None):
    """Forward + cache construction.  Returns (last-token logits, cache).

    Uniform-kind stacks scan over layers (cache slices emitted as scan ys) so
    the 32k-prefill dry-run HLO stays O(1) in depth.
    """
    _check_servable(cfg)
    B, S = tokens.shape[0], tokens.shape[1]
    if extra_embeds is not None:
        S = S + extra_embeds.shape[1]
    kinds = cfg.layer_kinds()
    cache = init_cache(cfg, B, max_len)
    Swin = cache["kv"]["k"].shape[2] if "kv" in cache else 0
    keep = min(S, Swin)

    x = _embed_tokens(params, tokens, cfg, extra_embeds)
    uniform = "blocks" in params
    if uniform and kinds[0] == "attn":
        def body(x, p):
            return _prefill_attn_block(p, x, cfg, keep)

        x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
        cache["kv"]["k"] = cache["kv"]["k"].at[:, :, :keep].set(ks)
        cache["kv"]["v"] = cache["kv"]["v"].at[:, :, :keep].set(vs)
    elif uniform and kinds[0] == "ssm":
        def body(x, p):
            x = _constrain_act(x, cfg)
            h = rmsnorm(p["ln1"], x, cfg.norm_eps)
            y, hstate, cstate = _mamba_prefill_state(p["ssm"], h, cfg)
            return x + y, (hstate, cstate)

        x, (hs, cs) = jax.lax.scan(body, x, params["blocks"])
        cache["ssm"] = {"h": hs, "conv": cs}
    elif "groups" in params:
        pat, n_groups, tail_kinds = _hybrid_layout(cfg)
        a_per = sum(k == "attn" for k in pat)
        r_per = sum(k == "rec" for k in pat)

        def body(x, p):
            kv_k, kv_v, rhs, rcs = [], [], [], []
            for i, kind in enumerate(pat):
                blk = p[f"b{i}"]
                if kind == "attn":
                    x, (k, v) = _prefill_attn_block(blk, x, cfg, keep)
                    kv_k.append(k)
                    kv_v.append(v)
                else:  # rec
                    x = _constrain_act(x, cfg)
                    h = rmsnorm(blk["ln1"], x, cfg.norm_eps)
                    y, hstate, cstate = _rglru_prefill_state(
                        blk["rec"], h, cfg)
                    x = x + y
                    x = x + mlp(blk["mlp"],
                                rmsnorm(blk["ln2"], x, cfg.norm_eps), cfg)
                    rhs.append(hstate)
                    rcs.append(cstate)
            return x, (jnp.stack(kv_k), jnp.stack(kv_v),
                       jnp.stack(rhs), jnp.stack(rcs))

        x, (ks, vs, rhs, rcs) = jax.lax.scan(body, x, params["groups"])
        na, nr = n_groups * a_per, n_groups * r_per
        cache["kv"]["k"] = cache["kv"]["k"].at[:na, :, :keep].set(
            ks.reshape(na, *ks.shape[2:]))
        cache["kv"]["v"] = cache["kv"]["v"].at[:na, :, :keep].set(
            vs.reshape(na, *vs.shape[2:]))
        cache["rec"]["h"] = cache["rec"]["h"].at[:nr].set(
            rhs.reshape(nr, *rhs.shape[2:]))
        cache["rec"]["conv"] = cache["rec"]["conv"].at[:nr].set(
            rcs.reshape(nr, *rcs.shape[2:]))
        rec_i = nr
        for i, kind in enumerate(tail_kinds):   # tail (rec for r-gemma)
            blk = params["tail"][i]
            x = _constrain_act(x, cfg)
            h = rmsnorm(blk["ln1"], x, cfg.norm_eps)
            y, hstate, cstate = _rglru_prefill_state(blk["rec"], h, cfg)
            cache["rec"]["h"] = cache["rec"]["h"].at[rec_i].set(hstate)
            cache["rec"]["conv"] = cache["rec"]["conv"].at[rec_i].set(cstate)
            rec_i += 1
            x = x + y
            x = x + mlp(blk["mlp"], rmsnorm(blk["ln2"], x, cfg.norm_eps),
                        cfg)
    else:
        attn_i = ssm_i = rec_i = 0
        for i, kind in enumerate(kinds):
            p = params["layers"][i]
            if kind == "attn":
                x, (k, v) = _prefill_attn_block(p, x, cfg, keep)
                cache["kv"]["k"] = cache["kv"]["k"].at[attn_i, :, :keep].set(k)
                cache["kv"]["v"] = cache["kv"]["v"].at[attn_i, :, :keep].set(v)
                attn_i += 1
            elif kind == "ssm":
                h = rmsnorm(p["ln1"], x, cfg.norm_eps)
                y, hstate, cstate = _mamba_prefill_state(p["ssm"], h, cfg)
                cache["ssm"]["h"] = cache["ssm"]["h"].at[ssm_i].set(hstate)
                cache["ssm"]["conv"] = cache["ssm"]["conv"].at[ssm_i].set(cstate)
                ssm_i += 1
                x = x + y
            elif kind == "rec":
                h = rmsnorm(p["ln1"], x, cfg.norm_eps)
                y, hstate, cstate = _rglru_prefill_state(p["rec"], h, cfg)
                cache["rec"]["h"] = cache["rec"]["h"].at[rec_i].set(hstate)
                cache["rec"]["conv"] = cache["rec"]["conv"].at[rec_i].set(cstate)
                rec_i += 1
                x = x + y
                x = x + mlp(p["mlp"], rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _unembed(params, x[:, -1:], cfg)[:, 0]
    cache["pos"] = jnp.full((), S, jnp.int32)
    return logits, cache


def _mamba_prefill_state(p, h, cfg):
    """Mamba fwd that also returns final (h_state, conv_state)."""
    xz = jnp.einsum("bsd,de->bse", h, p["in_proj"],
                    preferred_element_type=jnp.float32).astype(h.dtype)
    u, z = jnp.split(xz, 2, axis=-1)
    u, conv_tail = mamba._causal_conv(u, p["conv_w"], p["conv_b"])
    u = jax.nn.silu(u.astype(jnp.float32)).astype(h.dtype)
    dt, Bc, Cc = mamba._ssm_params(p, u, cfg)
    A = -jnp.exp(p["A_log"])
    # run the chunked scan but keep the final carry
    Bsz, S, di = u.shape
    ds = Bc.shape[-1]
    nc = max(1, S // mamba.CHUNK)
    chunk = S // nc
    uf = u.astype(jnp.float32)

    def chunk_body(hc, xs):
        dt_c, u_c, B_c, C_c = xs
        la = dt_c[..., None] * A[None, None]
        b = (dt_c * u_c)[..., None] * B_c[:, :, None, :]

        def comb(l, r):
            (la1, b1), (la2, b2) = l, r
            return la1 + la2, jnp.exp(la2) * b1 + b2

        la_cum, b_cum = jax.lax.associative_scan(comb, (la, b), axis=1)
        h_all = jnp.exp(la_cum) * hc[:, None] + b_cum
        y = jnp.sum(h_all * C_c[:, :, None, :], axis=-1)
        return h_all[:, -1], y

    xs = tuple(a.reshape(Bsz, nc, chunk, *a.shape[2:]).swapaxes(0, 1)
               for a in (dt.astype(jnp.float32), uf,
                         Bc.astype(jnp.float32), Cc.astype(jnp.float32)))
    h0 = jnp.zeros((Bsz, di, ds), jnp.float32)
    h_fin, ys = jax.lax.scan(chunk_body, h0, xs)
    y = ys.swapaxes(0, 1).reshape(Bsz, S, di)
    y = y + uf * p["D"][None, None]
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(h.dtype)
    out = jnp.einsum("bsd,de->bse", y, p["out_proj"],
                     preferred_element_type=jnp.float32).astype(h.dtype)
    return out, h_fin, conv_tail


def _rglru_prefill_state(p, h, cfg):
    from repro.models.mamba import _causal_conv
    u = jnp.einsum("bsd,dw->bsw", h, p["in_x"],
                   preferred_element_type=jnp.float32).astype(h.dtype)
    gate = jnp.einsum("bsd,dw->bsw", h, p["in_gate"],
                      preferred_element_type=jnp.float32)
    u, conv_tail = _causal_conv(u, p["conv_w"], p["conv_b"])
    i_g, log_a = griffin._gates(p, u)
    hs = griffin.rglru_scan(u, i_g, log_a)
    y = (hs * jax.nn.gelu(gate)).astype(h.dtype)
    out = jnp.einsum("bsw,wd->bsd", y, p["out"],
                     preferred_element_type=jnp.float32).astype(h.dtype)
    return out, hs[:, -1], conv_tail


# ----------------------------------------------------------- decode step ---
def lm_decode_step(params, token, cache, cfg: ModelConfig):
    """token: (B,1) int32; cache from init_cache/lm_prefill.
    Returns (logits (B,V) fp32, updated cache)."""
    _check_servable(cfg)
    kinds = cfg.layer_kinds()
    pos = cache["pos"]
    x = jnp.take(params["embed"], token, axis=0).astype(cfg.adtype)

    uniform = "blocks" in params
    new_cache = {k: v for k, v in cache.items()}

    if uniform and kinds[0] == "attn":
        def body(x, xs):
            p, ck, cv = xs
            h = rmsnorm(p["ln1"], x, cfg.norm_eps)
            o, ck, cv = decode_attention(p["attn"], h, ck, cv, pos, cfg)
            x = x + o
            hh = rmsnorm(p["ln2"], x, cfg.norm_eps)
            y = (moe.moe_mlp(p["moe"], hh, cfg)[0] if cfg.n_experts
                 else mlp(p["mlp"], hh, cfg))
            return x + y, (ck, cv)

        x, (ks, vs) = jax.lax.scan(
            body, x, (params["blocks"], cache["kv"]["k"], cache["kv"]["v"]))
        new_cache["kv"] = {"k": ks, "v": vs}
    elif uniform and kinds[0] == "ssm":
        def body(x, xs):
            p, h, cs = xs
            hid = rmsnorm(p["ln1"], x, cfg.norm_eps)
            o, h, cs = mamba.mamba_decode(p["ssm"], hid, h, cs, cfg)
            return x + o, (h, cs)

        x, (hs, css) = jax.lax.scan(
            body, x, (params["blocks"], cache["ssm"]["h"],
                      cache["ssm"]["conv"]))
        new_cache["ssm"] = {"h": hs, "conv": css}
    elif "groups" in params:
        pat, n_groups, tail_kinds = _hybrid_layout(cfg)
        a_per = sum(k == "attn" for k in pat)
        r_per = sum(k == "rec" for k in pat)
        na, nr = n_groups * a_per, n_groups * r_per
        kv = cache["kv"]
        rec_s = cache["rec"]
        ks_g = kv["k"][:na].reshape(n_groups, a_per, *kv["k"].shape[1:])
        vs_g = kv["v"][:na].reshape(n_groups, a_per, *kv["v"].shape[1:])
        rh_g = rec_s["h"][:nr].reshape(n_groups, r_per,
                                       *rec_s["h"].shape[1:])
        rc_g = rec_s["conv"][:nr].reshape(n_groups, r_per,
                                          *rec_s["conv"].shape[1:])

        def body(x, xs):
            p, ck, cv, rh, rc = xs
            ai = ri = 0
            for i, kind in enumerate(pat):
                blk = p[f"b{i}"]
                if kind == "attn":
                    h = rmsnorm(blk["ln1"], x, cfg.norm_eps)
                    o, k2, v2 = decode_attention(
                        blk["attn"], h, ck[ai], cv[ai], pos, cfg)
                    ck = ck.at[ai].set(k2)
                    cv = cv.at[ai].set(v2)
                    ai += 1
                    x = x + o
                    hh = rmsnorm(blk["ln2"], x, cfg.norm_eps)
                    x = x + mlp(blk["mlp"], hh, cfg)
                else:  # rec
                    hid = rmsnorm(blk["ln1"], x, cfg.norm_eps)
                    o, h2, c2 = griffin.rglru_decode(
                        blk["rec"], hid, rh[ri], rc[ri], cfg)
                    rh = rh.at[ri].set(h2)
                    rc = rc.at[ri].set(c2)
                    ri += 1
                    x = x + o
                    x = x + mlp(blk["mlp"],
                                rmsnorm(blk["ln2"], x, cfg.norm_eps), cfg)
            return x, (ck, cv, rh, rc)

        x, (ks2, vs2, rh2, rc2) = jax.lax.scan(
            body, x, (params["groups"], ks_g, vs_g, rh_g, rc_g))
        new_k = kv["k"].at[:na].set(ks2.reshape(na, *kv["k"].shape[1:]))
        new_v = kv["v"].at[:na].set(vs2.reshape(na, *kv["v"].shape[1:]))
        new_rh = rec_s["h"].at[:nr].set(rh2.reshape(nr,
                                                    *rec_s["h"].shape[1:]))
        new_rc = rec_s["conv"].at[:nr].set(
            rc2.reshape(nr, *rec_s["conv"].shape[1:]))
        rec_i = nr
        for i, kind in enumerate(tail_kinds):
            blk = params["tail"][i]
            hid = rmsnorm(blk["ln1"], x, cfg.norm_eps)
            o, h2, c2 = griffin.rglru_decode(
                blk["rec"], hid, new_rh[rec_i], new_rc[rec_i], cfg)
            new_rh = new_rh.at[rec_i].set(h2)
            new_rc = new_rc.at[rec_i].set(c2)
            rec_i += 1
            x = x + o
            x = x + mlp(blk["mlp"], rmsnorm(blk["ln2"], x, cfg.norm_eps),
                        cfg)
        new_cache["kv"] = {"k": new_k, "v": new_v}
        new_cache["rec"] = {"h": new_rh, "conv": new_rc}
    else:  # hybrid / unrolled
        attn_i = ssm_i = rec_i = 0
        kv = dict(cache.get("kv", {}))
        ssm_s = dict(cache.get("ssm", {}))
        rec_s = dict(cache.get("rec", {}))
        for i, kind in enumerate(kinds):
            p = params["layers"][i]
            if kind == "attn":
                h = rmsnorm(p["ln1"], x, cfg.norm_eps)
                o, ck, cv = decode_attention(
                    p["attn"], h, kv["k"][attn_i], kv["v"][attn_i], pos, cfg)
                kv = {"k": kv["k"].at[attn_i].set(ck),
                      "v": kv["v"].at[attn_i].set(cv)}
                attn_i += 1
                x = x + o
                hh = rmsnorm(p["ln2"], x, cfg.norm_eps)
                y = (moe.moe_mlp(p["moe"], hh, cfg)[0] if cfg.n_experts
                     else mlp(p["mlp"], hh, cfg))
                x = x + y
            elif kind == "ssm":
                hid = rmsnorm(p["ln1"], x, cfg.norm_eps)
                o, h, cs = mamba.mamba_decode(
                    p["ssm"], hid, ssm_s["h"][ssm_i], ssm_s["conv"][ssm_i], cfg)
                ssm_s = {"h": ssm_s["h"].at[ssm_i].set(h),
                         "conv": ssm_s["conv"].at[ssm_i].set(cs)}
                ssm_i += 1
                x = x + o
            elif kind == "rec":
                hid = rmsnorm(p["ln1"], x, cfg.norm_eps)
                o, h, cs = griffin.rglru_decode(
                    p["rec"], hid, rec_s["h"][rec_i], rec_s["conv"][rec_i], cfg)
                rec_s = {"h": rec_s["h"].at[rec_i].set(h),
                         "conv": rec_s["conv"].at[rec_i].set(cs)}
                rec_i += 1
                x = x + o
                x = x + mlp(p["mlp"], rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
        if kv:
            new_cache["kv"] = kv
        if ssm_s:
            new_cache["ssm"] = ssm_s
        if rec_s:
            new_cache["rec"] = rec_s

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _unembed(params, x, cfg)[:, 0]
    new_cache["pos"] = pos + 1
    return logits, new_cache
