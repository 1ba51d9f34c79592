"""Core transformer layers: norms, RoPE, GQA/MQA attention (train + cached
decode), multi-head latent attention (train), dense MLPs.  Pure-functional:
params are plain dict pytrees.

All matmuls accumulate in fp32 (``preferred_element_type``) which mirrors MXU
behaviour on TPU; activations are cast back to ``cfg.dtype``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.models.config import ModelConfig


def _he(key, shape, dtype, fan_in=None):
    fan = fan_in or shape[0]
    return (jax.random.normal(key, shape, jnp.float32)
            * math.sqrt(1.0 / fan)).astype(dtype)


# ---------------------------------------------------------------- norms ----
def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: dict, x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)


# ----------------------------------------------------------------- rope ----
def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); pos: (S,) or scalar broadcastable."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    ang = pos[..., None].astype(jnp.float32) * freqs  # (S, hd/2)
    cos = jnp.cos(ang)[..., None, :]  # (S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope_pairs(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """RoPE in the pairwise (interleaved) layout: rotates (x[2i], x[2i+1])
    by pos * freq_i.  x: (..., S, H, hd); pos: (S,)."""
    hd = x.shape[-1]
    ang = pos[..., None].astype(jnp.float32) * rope_freqs(hd, theta)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], hd // 2, 2)
    x1, x2 = xf[..., 0], xf[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ------------------------------------------------------------ attention ----
def init_attention(key, cfg: ModelConfig, d_model: Optional[int] = None) -> dict:
    D = d_model or cfg.d_model
    H, Hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": _he(ks[0], (D, H * hd), cfg.pdtype),
        "wk": _he(ks[1], (D, Hk * hd), cfg.pdtype),
        "wv": _he(ks[2], (D, Hk * hd), cfg.pdtype),
        "wo": _he(ks[3], (H * hd, D), cfg.pdtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, cfg.pdtype)
        p["k_norm"] = init_rmsnorm(hd, cfg.pdtype)
    return p


def _qkv(p, x, cfg: ModelConfig, pos):
    B, S, _ = x.shape
    H, Hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jnp.einsum("bsd,de->bse", x, p["wq"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    k = jnp.einsum("bsd,de->bse", x, p["wk"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    v = jnp.einsum("bsd,de->bse", x, p["wv"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, Hk, hd)
    v = v.reshape(B, S, Hk, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    # selective-remat tags: with remat_policy="save_proj" the projections
    # are saved and only the O(S^2) score/softmax chain recomputes
    q = checkpoint_name(q, "proj")
    k = checkpoint_name(k, "proj")
    v = checkpoint_name(v, "proj")
    return q, k, v


def _scores_mask(qpos, kpos, window, causal):
    """(Sq, Sk) bool mask; True = attend."""
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        m &= kpos[None, :] > qpos[:, None] - window
    return m


def _sdpa(q, k, v, mask, cfg: ModelConfig):
    """q:(B,Sq,H,hd) k:(B,Sk,Hk,hd) v:(B,Sk,Hk,dv)  mask:(Sq,Sk)
    -> (B,Sq,H,dv)."""
    B, Sq, H, hd = q.shape
    Hk = k.shape[2]
    G = H // Hk
    qg = q.reshape(B, Sq, Hk, G, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32)
    s *= 1.0 / math.sqrt(hd)
    if cfg.attn_logit_softcap:
        c = cfg.attn_logit_softcap
        s = c * jnp.tanh(s / c)
    s = jnp.where(mask[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", w, v,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    return o.reshape(B, Sq, H, v.shape[-1])


def device_memory_bytes() -> int:
    """Memory of one device, from the backend; 16 GiB, one TPU v5e's,
    where the backend does not say (the CPU)."""
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", 16 * 2 ** 30))


def q_chunk(cfg: ModelConfig, B: int, H: int, Sq: int, Sk: int) -> int:
    """Query rows per chunk of the attention core; 0 = unchunked.

    ``cfg.attn_chunk`` sets it.  Otherwise the core chunks when the float32
    scores of the whole sequence, B*H*Sq*Sk*4 bytes shared over the mesh's
    devices, would take more than a fifth of a device's memory, with the
    largest power-of-two chunk whose scores take at most a twenty-fourth
    (danube at 4 x 2048, 2.1 GB, stays whole; at 1 x 8192, 8.6 GB, it
    runs in chunks of 512 queries on a 16 GB chip)."""
    if cfg.attn_chunk:
        return cfg.attn_chunk if Sq > cfg.attn_chunk else 0
    mem = device_memory_bytes()
    row = B * H * Sk * 4 / max(jax.sharding.get_abstract_mesh().size, 1)
    if row * Sq <= mem / 5:
        return 0
    c = Sq
    while c > 1 and (c * row > mem / 24 or Sq % c):
        c //= 2
    return c


def attention_core(q, k, v, pos, cfg: ModelConfig, *, causal: bool = True):
    """Softmax attention of q (B,S,H,hd) over k (B,S,Hk,hd), v (B,S,Hk,dv)
    at positions ``pos`` (S,), -> (B,S,H,dv).  Where ``q_chunk`` says so it
    runs over chunks of queries under the scope ``attn_core``, each chunk
    rematerialised, so that the backward pass holds one chunk's scores at
    a time."""
    B, S, H, hd = q.shape
    chunk = q_chunk(cfg, B, H, S, k.shape[1])
    if not chunk:
        mask = _scores_mask(pos, pos, cfg.window, causal)
        return _sdpa(q, k, v, mask, cfg)

    @jax.checkpoint
    def one(qc, i):
        qpos = jax.lax.dynamic_slice_in_dim(pos, i * chunk, chunk)
        mask = (pos[None, :] <= qpos[:, None]) if causal else \
            jnp.ones((chunk, S), bool)
        if cfg.window is not None:
            mask &= pos[None, :] > qpos[:, None] - cfg.window
        return _sdpa(qc, k, v, mask, cfg)

    with jax.named_scope("attn_core"):
        n = S // chunk
        qs = q.reshape(B, n, chunk, H, hd).transpose(1, 0, 2, 3, 4)
        os = jax.lax.map(lambda a: one(*a), (qs, jnp.arange(n)))
        return os.transpose(1, 0, 2, 3, 4).reshape(B, S, H, v.shape[-1])


def attention(p, x, cfg: ModelConfig, *, causal: bool = True,
              pos_offset: int = 0, return_kv: bool = False):
    """Full-sequence attention (train / prefill); its core bounds the
    (B,H,Sq,Sk) scores it holds (``attention_core``)."""
    B, S, D = x.shape
    pos = jnp.arange(S) + pos_offset
    q, k, v = _qkv(p, x, cfg, pos)
    o = attention_core(q, k, v, pos, cfg, causal=causal)
    o = o.reshape(B, S, cfg.n_heads * cfg.hd)
    out = jnp.einsum("bse,ed->bsd", o, p["wo"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    out = checkpoint_name(out, "proj")
    if return_kv:
        return out, k, v
    return out


# ------------------------------------------ multi-head latent attention ----
# DeepSeek's kv_a_layernorm keeps its modeling code's default eps; the
# configuration's rms_norm_eps is the layer norms'
LATENT_NORM_EPS = 1e-6


def init_mla(key, cfg: ModelConfig) -> dict:
    D, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    ks = jax.random.split(key, 4)
    return {
        "wq": _he(ks[0], (D, H * cfg.qk_hd), cfg.pdtype),
        "wkv_a": _he(ks[1], (D, r + cfg.qk_rope_dim), cfg.pdtype),
        "kv_norm": init_rmsnorm(r, cfg.pdtype),
        "wkv_b": _he(ks[2], (r, H * (cfg.qk_nope_dim + cfg.v_hd)),
                     cfg.pdtype),
        "wo": _he(ks[3], (H * cfg.v_hd, D), cfg.pdtype),
    }


def mla(p, x, cfg: ModelConfig):
    """Multi-head latent attention (DeepSeek-V3, no q LoRA), causal:
    q = x Wq -> (S,H,nope+rope); [c, k_pe] = x Wkv_a, c = RMSNorm(c);
    [k_nope, v] = c Wkv_b; pairwise RoPE on q_pe and on the one k_pe that
    every head shares; softmax scale 1/sqrt(nope+rope); o Wo."""
    B, S, _ = x.shape
    H, nope, r = cfg.n_heads, cfg.qk_nope_dim, cfg.kv_lora_rank
    pos = jnp.arange(S)

    def proj(a, w):
        return jnp.einsum("bsd,de->bse", a, w,
                          preferred_element_type=jnp.float32).astype(x.dtype)

    q = proj(x, p["wq"]).reshape(B, S, H, cfg.qk_hd)
    ckv = proj(x, p["wkv_a"])
    c = rmsnorm(p["kv_norm"], ckv[..., :r], LATENT_NORM_EPS)
    k_pe = apply_rope_pairs(ckv[..., None, r:], pos, cfg.rope_theta)
    kv = proj(c, p["wkv_b"]).reshape(B, S, H, nope + cfg.v_hd)
    q = jnp.concatenate(
        [q[..., :nope], apply_rope_pairs(q[..., nope:], pos, cfg.rope_theta)],
        axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, cfg.qk_rope_dim))],
        axis=-1)
    o = attention_core(q, k, kv[..., nope:], pos, cfg)
    return proj(o.reshape(B, S, H * cfg.v_hd), p["wo"])


# ----------------------------------------------------- cached decoding -----
def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  n_layers: int) -> dict:
    """Cache for the attention layers only (stacked on a leading layer dim).
    SWA archs keep a rolling window buffer: O(window), the sub-quadratic
    property that makes long_500k feasible."""
    Hk, hd = cfg.n_kv_heads, cfg.hd
    S = min(max_len, cfg.window) if cfg.window else max_len
    shape = (n_layers, batch, S, Hk, hd)
    return {"k": jnp.zeros(shape, cfg.adtype),
            "v": jnp.zeros(shape, cfg.adtype)}


def decode_attention(p, x, cache_k, cache_v, pos, cfg: ModelConfig):
    """One-token attention against a cache.

    x: (B,1,D); cache_k/v: (B,S,Hk,hd); pos: the current index — scalar
    int32 (whole batch at one position, training-style decode) or (B,)
    int32 (per-row positions, the continuous-batching serving engine:
    every slot advances independently).  Returns (out (B,1,D), new_k,
    new_v).  For SWA the cache is a rolling buffer indexed mod window.
    """
    B = x.shape[0]
    S = cache_k.shape[1]
    H, Hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    per_row = jnp.ndim(pos) == 1
    q, k, v = _qkv(p, x, cfg,
                   pos[:, None] if per_row else jnp.array([0]) + pos)
    slot = jnp.mod(pos, S) if cfg.window else pos
    if per_row:
        # rows write at different slots — no single dynamic_update_slice
        # start index exists, so scatter arithmetically per row
        oh = (jnp.arange(S)[None, :] == slot[:, None])[..., None, None]
        ck = jnp.where(oh, k.astype(cache_k.dtype), cache_k)
        cv = jnp.where(oh, v.astype(cache_v.dtype), cache_v)
    elif cfg.cache_update == "onehot":
        # arithmetic scatter: elementwise over the (possibly TP-sharded) seq
        # dim — no cross-shard gather under GSPMD (used for seq-sharded
        # decode caches in the dry-run / flash-decoding path)
        oh = (jnp.arange(S) == slot)[None, :, None, None]
        ck = jnp.where(oh, k.astype(cache_k.dtype), cache_k)
        cv = jnp.where(oh, v.astype(cache_v.dtype), cache_v)
    else:
        ck = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                          (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                          (0, slot, 0, 0))
    kpos_abs = jnp.arange(S)
    # (B,1) per-row / scalar shared: the same mask algebra broadcasts to
    # (B,S) or (S,) respectively
    pcol = pos[:, None] if per_row else pos
    if cfg.window:
        # rolling buffer: entry i holds absolute position with i = abs % S
        n_wrap = (pcol // S) * S
        kabs = kpos_abs + jnp.where(kpos_abs <= jnp.mod(pcol, S), n_wrap,
                                    n_wrap - S)
        valid = (kabs >= 0) & (kabs <= pcol) & (kabs > pcol - cfg.window)
    else:
        valid = kpos_abs <= pcol
    G = H // Hk
    qg = q.reshape(B, 1, Hk, G, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ck,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    if cfg.attn_logit_softcap:
        c = cfg.attn_logit_softcap
        s = c * jnp.tanh(s / c)
    s = jnp.where(valid[:, None, None, None, :] if per_row
                  else valid[None, None, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", w, cv,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    o = o.reshape(B, 1, H * hd)
    out = jnp.einsum("bse,ed->bsd", o, p["wo"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    return out, ck, cv


# ------------------------------------------------------------------ mlp ----
def init_mlp(key, cfg: ModelConfig, d_model: Optional[int] = None,
             d_ff: Optional[int] = None) -> dict:
    D = d_model or cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act in ("swiglu", "geglu"):
        return {"w_gate": _he(ks[0], (D, F), cfg.pdtype),
                "w_up": _he(ks[1], (D, F), cfg.pdtype),
                "w_down": _he(ks[2], (F, D), cfg.pdtype)}
    return {"w_up": _he(ks[0], (D, F), cfg.pdtype),
            "w_down": _he(ks[1], (F, D), cfg.pdtype)}


def mlp(p, x, cfg: ModelConfig) -> jax.Array:
    if cfg.act in ("swiglu", "geglu"):
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"],
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("bsd,df->bsf", x, p["w_up"],
                       preferred_element_type=jnp.float32)
        act = jax.nn.silu(g) if cfg.act == "swiglu" else jax.nn.gelu(g)
        h = checkpoint_name((act * u).astype(x.dtype), "proj")
    else:
        u = jnp.einsum("bsd,df->bsf", x, p["w_up"],
                       preferred_element_type=jnp.float32)
        if cfg.act == "sq_relu":          # nemotron: squared ReLU
            h = jnp.square(jax.nn.relu(u)).astype(x.dtype)
        else:
            h = jax.nn.gelu(u).astype(x.dtype)
    h = checkpoint_name(h, "proj")
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"],
                      preferred_element_type=jnp.float32).astype(x.dtype)
