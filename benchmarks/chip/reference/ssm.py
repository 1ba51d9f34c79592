"""Plain float32 reference of a Mamba-1 decoder (arXiv:2312.00752): RMS
norm, in-projection to (u, z), depthwise causal conv, SiLU, the
data-dependent (dt, B, C) projection, the selective scan run one position
at a time, the skip D, the SiLU gate, the out-projection; the untied
head is applied by ``core.make_step``.

``cfg`` is the configuration file's dict (Hugging Face key names).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.core import F32, mm, rms

# leaf name -> how the benchmark makes it (see weights.py)
INIT = {"scale": "ones", "_stacked": "zeros", "conv_b": "zeros",
        "D": "ones", "dt_bias": ["const", -4.6], "A_log": "log_arange"}


def _mixer(p, x, cfg, precision):
    """x: (S, D) normed -> (S, D)."""
    S = x.shape[0]
    K, ds, dr = cfg["conv_kernel"], cfg["state_size"], cfg["time_step_rank"]
    u, z = jnp.split(mm("sd,de->se", x, p["in_proj"], precision), 2, -1)
    up = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u], 0)
    u = sum(up[i:i + S] * p["conv_w"][i] for i in range(K)) + p["conv_b"]
    u = jax.nn.silu(u)
    proj = mm("se,er->sr", u, p["x_proj"], precision)
    dt, Bc, Cc = proj[:, :dr], proj[:, dr:dr + ds], proj[:, dr + ds:]
    dt = jax.nn.softplus(mm("sr,re->se", dt, p["dt_proj"], precision)
                         + p["dt_bias"])
    A = -jnp.exp(p["A_log"])                                  # (di, ds)

    def scan_step(h, xs):
        dt_t, u_t, b_t, c_t = xs
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * u_t)[:, None] * b_t
        return h, h @ c_t

    h0 = jnp.zeros(A.shape, F32)
    _, y = jax.lax.scan(scan_step, h0, (dt, u, Bc, Cc))
    y = (y + u * p["D"]) * jax.nn.silu(z)
    return mm("se,ed->sd", y, p["out_proj"], precision)


def features(params, tokens, cfg, precision):
    """tokens: (R, S) -> the head's input (R, S, D), float32."""
    eps = cfg["layer_norm_epsilon"]
    block = jax.checkpoint(lambda p, x: x + _mixer(
        p["ssm"], rms(x, p["ln1"]["scale"], eps), cfg, precision))

    def row(tok):
        x = params["embed"][tok]
        x, _ = jax.lax.scan(lambda x, p: (block(p, x), None), x,
                            params["blocks"])
        return rms(x, params["final_norm"]["scale"], eps)

    return jax.vmap(row)(tokens)
