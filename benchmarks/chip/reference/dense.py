"""Plain float32 reference of a dense decoder (Llama/Mistral block): RMS
norms, grouped-query attention with rotary positions (rotate-half) under a
causal sliding window, a SwiGLU MLP; the untied head is applied by
``core.make_step``.

``cfg`` is the configuration file's dict (Hugging Face key names).  Params
follow the layer-stacked tree the program's state uses: ``blocks`` leaves
carry a leading layer dim.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from reference.core import F32, mm, rms

Q_BLOCK = 256

# leaf name -> how the benchmark makes it (see weights.py)
INIT = {"scale": "ones", "_stacked": "zeros"}


def _rope(x, theta):
    """x: (S, H, hd); rotate-half convention."""
    S, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _causal_window_mask(S: int, window):
    q = np.arange(S)[:, None]
    k = np.arange(S)[None, :]
    m = k <= q
    if window:
        m &= k > q - window
    return jnp.asarray(m)


def _block(p, x, cfg, precision, mask):
    """x: (S, D) -> (S, D)."""
    S, _ = x.shape
    H, Hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    h = rms(x, p["ln1"]["scale"], eps)
    q = mm("sd,de->se", h, p["attn"]["wq"], precision).reshape(S, H, hd)
    k = mm("sd,de->se", h, p["attn"]["wk"], precision).reshape(S, Hk, hd)
    v = mm("sd,de->se", h, p["attn"]["wv"], precision).reshape(S, Hk, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, H // Hk, axis=1)
    v = jnp.repeat(v, H // Hk, axis=1)

    @jax.checkpoint
    def attend(qc, mc):
        s = mm("qhd,khd->hqk", qc, k, precision) / jnp.sqrt(F32(hd))
        a = jax.nn.softmax(jnp.where(mc[None], s, -jnp.inf), axis=-1)
        return mm("hqk,khd->qhd", a, v, precision)

    # queries in blocks, so a block's (heads, block, S) scores are all
    # that is held at once
    nb = max(1, S // Q_BLOCK)
    o = jax.lax.map(lambda xs: attend(*xs),
                    (q.reshape(nb, S // nb, H, hd),
                     mask.reshape(nb, S // nb, S)))
    o = o.reshape(S, H * hd)
    x = x + mm("se,ed->sd", o, p["attn"]["wo"], precision)
    h = rms(x, p["ln2"]["scale"], eps)
    g = mm("sd,df->sf", h, p["mlp"]["w_gate"], precision)
    u = mm("sd,df->sf", h, p["mlp"]["w_up"], precision)
    return x + mm("sf,fd->sd", jax.nn.silu(g) * u, p["mlp"]["w_down"],
                  precision)


def features(params, tokens, cfg, precision):
    """tokens: (R, S) -> the head's input (R, S, D), float32."""
    S = tokens.shape[1]
    mask = _causal_window_mask(S, cfg.get("sliding_window"))
    block = jax.checkpoint(lambda p, x: _block(p, x, cfg, precision, mask))

    def row(tok):
        x = params["embed"][tok]
        x, _ = jax.lax.scan(lambda x, p: (block(p, x), None), x,
                            params["blocks"])
        return rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])

    return jax.vmap(row)(tokens)
