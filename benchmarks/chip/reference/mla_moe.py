"""Plain float32 reference of a DeepSeek-V3 decoder (Moonlight): RMS norms,
multi-head latent attention with pairwise rotary positions, a dense SwiGLU
in the first ``first_k_dense_replace`` layers and, after them, sigmoid-
routed experts with shared experts; the untied head is applied by
``core.make_grad``.

The expert layer is this chip's share of it: the router scores all
``router_width`` experts and each token takes the top
``num_experts_per_tok`` of score + selection bias, from its own float32
scores; the ``n_routed_experts`` experts held here (the first ones) run on
every token, each weighted by its gate, which is zero where the token did
not choose it.  So nothing here sorts or dispatches rows.  The selection
bias is the one that balances the step's load over the batch's tokens: the
aux-free update (bias += gamma * sign(mean load - load)) run
``BALANCE_STEPS`` times on the step's own scores, from each expert's mean
score subtracted, gamma a fifth of the scores' spread shrinking by 0.7 a
time.  It takes no gradient.

``cfg`` is the configuration file's dict (Hugging Face key names).  Params
follow the program's tree: ``dense`` holds the leading dense layers,
``blocks`` leaves carry a leading layer dim.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.core import F32, mm, rms

Q_BLOCK = 256
LATENT_EPS = 1e-6       # the latent's RMS norm (DeepSeek's kv_a_layernorm)

BALANCE_STEPS = 12

# leaf name -> how the benchmark makes it (see weights.py)
INIT = {"scale": "ones", "_stacked": "zeros"}


def _rope_pairs(x, theta):
    """x: (S, H, d); rotates each pair (x[2i], x[2i+1])."""
    S, _, d = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    xe, xo = x[..., 0::2], x[..., 1::2]
    return jnp.stack([xe * cos - xo * sin, xo * cos + xe * sin],
                     axis=-1).reshape(x.shape)


def _mla(p, h, cfg, precision):
    """h: (S, D) -> (S, D)."""
    S, _ = h.shape
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, theta = cfg["v_head_dim"], cfg["rope_theta"]
    q = mm("sd,de->se", h, p["wq"], precision).reshape(S, H, nope + rope)
    ckv = mm("sd,de->se", h, p["wkv_a"], precision)
    c = rms(ckv[:, :r], p["kv_norm"]["scale"], LATENT_EPS)
    kv = mm("sr,re->se", c, p["wkv_b"], precision).reshape(S, H, nope + dv)
    q = jnp.concatenate([q[..., :nope], _rope_pairs(q[..., nope:], theta)],
                        axis=-1)
    k_pe = _rope_pairs(ckv[:, None, r:], theta)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (S, H, rope))], axis=-1)
    v = kv[..., nope:]
    kpos = jnp.arange(S)

    @jax.checkpoint
    def attend(qc, qpos):
        s = mm("qhd,khd->hqk", qc, k, precision) / jnp.sqrt(F32(nope + rope))
        mask = kpos[None, :] <= qpos[:, None]
        a = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return mm("hqk,khd->qhd", a, v, precision)

    # queries in blocks, so a block's (heads, block, S) scores are all
    # that is held at once
    nb = max(1, S // Q_BLOCK)
    o = jax.lax.map(lambda xs: attend(*xs),
                    (q.reshape(nb, S // nb, H, nope + rope),
                     kpos.reshape(nb, S // nb)))
    return mm("se,ed->sd", o.reshape(S, H * dv), p["wo"], precision)


def _swiglu(p, h, precision):
    g = mm("sd,df->sf", h, p["w_gate"], precision)
    u = mm("sd,df->sf", h, p["w_up"], precision)
    return mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"], precision)


def _balance(scores, k):
    """The selection bias under which each expert takes about T*k/E of the
    T tokens' top-k picks."""
    T, E = scores.shape
    gamma = 0.2 * jnp.std(scores)
    b = -jnp.mean(scores, axis=0)
    for i in range(BALANCE_STEPS):
        _, top = jax.lax.top_k(scores + b, k)
        load = jnp.sum(jax.nn.one_hot(top, E, dtype=F32), axis=(0, 1))
        b = b + gamma * 0.7 ** i * jnp.sign(T * k / E - load)
    return b


def _experts(p, h, cfg, precision):
    """The held experts' part of the routed experts' output, (T, D) for
    the (T, D) tokens of the whole batch."""
    E, K = cfg["router_width"], cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(mm("sd,de->se", h, p["router"], precision))
    bias = jax.lax.stop_gradient(_balance(scores, K))
    _, top = jax.lax.top_k(scores + bias, K)
    chosen = jnp.sum(jax.nn.one_hot(top, E, dtype=F32), axis=1)   # (T, E)
    gate = chosen * scores
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    gate = gate * cfg["routed_scaling_factor"]
    held = cfg["n_routed_experts"]

    @jax.checkpoint
    def expert(out, xs):
        w_gate, w_up, w_down, g = xs
        y = _swiglu({"w_gate": w_gate, "w_up": w_up, "w_down": w_down}, h,
                    precision)
        return out + g[:, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (p["w_gate"], p["w_up"], p["w_down"],
                           gate[:, :held].T))
    return out


def _block(p, x, cfg, precision):
    """x: (R, S, D) -> (R, S, D); attention row by row, the experts over
    all the batch's tokens."""
    eps = cfg["rms_norm_eps"]
    x = x + jax.vmap(lambda r: _mla(p["mla"], rms(r, p["ln1"]["scale"], eps),
                                    cfg, precision))(x)
    R, S, D = x.shape
    h = rms(x, p["ln2"]["scale"], eps).reshape(R * S, D)
    if "mlp" in p:
        y = _swiglu(p["mlp"], h, precision)
    else:
        y = (_experts(p["moe"], h, cfg, precision)
             + _swiglu(p["shared"], h, precision))
    return x + y.reshape(R, S, D)


def features(params, tokens, cfg, precision):
    """tokens: (R, S) -> the head's input (R, S, D), float32."""
    block = jax.checkpoint(lambda p, x: _block(p, x, cfg, precision))
    x = params["embed"][tokens]
    for p in params["dense"]:
        x = block(p, x)
    x, _ = jax.lax.scan(lambda x, p: (block(p, x), None), x,
                        params["blocks"])
    return rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
