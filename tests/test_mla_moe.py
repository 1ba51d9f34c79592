"""Latent attention, the sigmoid-routed expert layer that holds a share of
the experts, the bounded attention core (chunked, or the Pallas flash pair
interpreted), and the whole Moonlight model
against its plain float32 reference (``benchmarks/chip/reference``), all
at small seeded sizes on the CPU."""
import dataclasses
import functools
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa, ops
from repro.models import layers, moe, registry
from repro.models.config import ModelConfig
from repro.obs import counters
from repro.parallel.sharding import ShardingRules
from repro.train import steps

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
HI = jax.lax.Precision.HIGHEST


def _smoke(**kw):
    return dataclasses.replace(registry.get_config("moonlight-16b-a3b",
                                                   smoke=True), **kw)


def _expert_cfg(**kw):
    base = dict(name="share", family="moe", num_layers=2, d_model=32,
                n_heads=2, n_kv_heads=2, d_ff=48, vocab_size=64,
                n_experts=64, top_k=6, router="sigmoid", routed_scale=2.446,
                d_expert=16, n_shared_experts=2, experts_held=8,
                param_dtype="float32", dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


# ------------------------------------------------------------------ MLA ----
def _plain_mla(p, x, cfg):
    """Multi-head latent attention written out for one sequence, fp32."""
    S = x.shape[0]
    H, nope, rope = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    r, dv = cfg.kv_lora_rank, cfg.v_head_dim

    def rot(t):     # (S, h, d): pairs (2i, 2i+1) rotated by pos * freq_i
        d = t.shape[-1]
        out = np.array(t)
        for s in range(S):
            for i in range(d // 2):
                a = s / cfg.rope_theta ** (2 * i / d)
                c, n = math.cos(a), math.sin(a)
                e, o = t[s, :, 2 * i], t[s, :, 2 * i + 1]
                out[s, :, 2 * i] = e * c - o * n
                out[s, :, 2 * i + 1] = o * c + e * n
        return out

    x = np.asarray(x, np.float64)
    q = (x @ p["wq"]).reshape(S, H, nope + rope)
    ckv = x @ p["wkv_a"]
    c = ckv[:, :r]
    c = c / np.sqrt(np.mean(c * c, -1, keepdims=True)
                    + layers.LATENT_NORM_EPS) * p["kv_norm"]["scale"]
    kv = (c @ p["wkv_b"]).reshape(S, H, nope + dv)
    q = np.concatenate([q[..., :nope], rot(q[..., nope:])], -1)
    k_pe = rot(ckv[:, None, r:])
    k = np.concatenate([kv[..., :nope],
                        np.broadcast_to(k_pe, (S, H, rope))], -1)
    s = np.einsum("qhd,khd->hqk", q, k) / math.sqrt(nope + rope)
    s = np.where(np.tril(np.ones((S, S), bool))[None], s, -np.inf)
    a = np.exp(s - s.max(-1, keepdims=True))
    a /= a.sum(-1, keepdims=True)
    o = np.einsum("hqk,khd->qhd", a, kv[..., nope:]).reshape(S, H * dv)
    return o @ p["wo"]


def test_mla_matches_a_plain_fp32_mla():
    cfg = _smoke()
    p = jax.tree.map(np.asarray, layers.init_mla(jax.random.PRNGKey(0), cfg))
    p["kv_norm"]["scale"] = np.linspace(0.5, 1.5, cfg.kv_lora_rank)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = layers.mla(p, x, cfg)[0]
    np.testing.assert_allclose(np.asarray(got), _plain_mla(p, x[0], cfg),
                               rtol=2e-4, atol=2e-5)


def test_rope_pairs_rotate_each_pair_and_keep_norms():
    x = jax.random.normal(jax.random.PRNGKey(2), (5, 3, 8))
    y = layers.apply_rope_pairs(x, jnp.arange(5), 50000.0)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(x[0]))
    pairs = lambda t: jnp.sum(t.reshape(5, 3, 4, 2) ** 2, -1)
    np.testing.assert_allclose(np.asarray(pairs(y)), np.asarray(pairs(x)),
                               rtol=1e-5)


# --------------------------------------------------- the held share ----
def _plain_layer(p_all, x, cfg, bias):
    """The uncut expert layer under the selection bias ``bias``: all
    experts run on every token, each weighed by its gate (zero where not
    chosen), plus the shared experts."""
    xt = np.asarray(x.reshape(-1, cfg.d_model), np.float64)
    scores = 1 / (1 + np.exp(-(xt @ p_all["router"])))
    top = np.argsort(-(scores + bias), axis=1)[:, :cfg.top_k]
    gate = np.zeros_like(scores)
    np.put_along_axis(gate, top, np.take_along_axis(scores, top, 1), 1)
    gate = gate / gate.sum(1, keepdims=True) * cfg.routed_scale
    silu = lambda t: t / (1 + np.exp(-t))
    out = np.zeros_like(xt)
    for e in range(cfg.n_experts):
        h = silu(xt @ p_all["w_gate"][e]) * (xt @ p_all["w_up"][e])
        out += gate[:, e:e + 1] * (h @ p_all["w_down"][e])
    sh = p_all["shared"]
    out += (silu(xt @ sh["w_gate"]) * (xt @ sh["w_up"])) @ sh["w_down"]
    return out


def _uncut_params(cfg, key):
    full = dataclasses.replace(cfg, experts_held=cfg.n_experts)
    k1, k3 = jax.random.split(key)
    p = jax.tree.map(np.asarray, moe.init_moe_share(k1, full))
    p["shared"] = jax.tree.map(np.asarray, layers.init_mlp(
        k3, cfg, d_ff=cfg.d_expert * cfg.n_shared_experts))
    return p


def _share(p_all, s, held):
    """Share ``s`` of the layer, as the chip that holds experts
    [s*held, (s+1)*held) sees it: a layer holds its first experts, so the
    router's columns are turned to put those first."""
    p = {"router": np.roll(p_all["router"], -s * held, axis=1)}
    for k in ("w_gate", "w_up", "w_down"):
        p[k] = p_all[k][s * held:(s + 1) * held]
    return p


def _balance_bias(p_all, x, cfg):
    xt = x.reshape(-1, cfg.d_model)
    return np.asarray(moe.balance_bias(jax.nn.sigmoid(
        jnp.dot(xt, p_all["router"], precision=HI)), cfg.top_k))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    cfg = _expert_cfg()
    p_all = _uncut_params(cfg, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, cfg.d_model))
    held = cfg.experts_held
    with jax.default_matmul_precision("highest"):
        parts = [moe.moe_share(_share(p_all, s, held), x, cfg)
                 for s in range(cfg.n_experts // held)]
        shared = layers.mlp(p_all["shared"], x, cfg)
        bias = _balance_bias(p_all, x, cfg)
    got = sum(y for y, _ in parts) + shared          # shared counted once
    np.testing.assert_allclose(
        np.asarray(got).reshape(-1, cfg.d_model),
        _plain_layer(p_all, x, cfg, bias), rtol=2e-4, atol=2e-5)
    rows = [int(st["rows"]) for _, st in parts]
    assert sum(rows) == x.shape[0] * x.shape[1] * cfg.top_k


def test_dropless_when_the_router_sends_every_token_to_one_held_expert(
        monkeypatch):
    cfg = _expert_cfg(top_k=2, n_shared_experts=0)
    p_all = _uncut_params(cfg, jax.random.PRNGKey(5))
    bias = np.zeros(cfg.n_experts, np.float32)
    bias[[3, 40]] = 10.0                     # every token: experts 3 and 40
    monkeypatch.setattr(moe, "balance_bias", lambda s, k: jnp.asarray(bias))
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 40, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y, st = moe.moe_share(_share(p_all, 0, 8), x, cfg)
    T = x.shape[1]
    assert int(st["rows"]) == T and int(st["rows_max"]) == T
    # the uncut layer less expert 40's part, which is held on another chip
    p_40 = dict(p_all, w_down=p_all["w_down"].copy())
    p_40["w_down"][40] = 0.0
    p_40["shared"] = jax.tree.map(np.zeros_like, p_all["shared"])
    np.testing.assert_allclose(np.asarray(y)[0],
                               _plain_layer(p_40, x, cfg, bias),
                               rtol=2e-4, atol=2e-5)


_RAGGED_DOT = jax.lax.ragged_dot


def _tail(a, sizes):
    return (jnp.arange(a.shape[0]) >= jnp.sum(sizes))[:, None]


@jax.custom_vjp
def _nan_tail(a, w, sizes):
    """ragged_dot whose rows past the groups, in its output and in the
    cotangent of its rows, are NaN, as a grouped matmul may leave them."""
    y = _RAGGED_DOT(a, w, sizes, preferred_element_type=jnp.float32)
    return jnp.where(_tail(a, sizes), jnp.nan, y)


def _nan_tail_fwd(a, w, sizes):
    return _nan_tail(a, w, sizes), (a, w, sizes)


def _nan_tail_bwd(res, g):
    a, w, sizes = res
    da, dw = jax.vjp(lambda a, w: _RAGGED_DOT(
        a, w, sizes, preferred_element_type=jnp.float32), a, w)[1](g)
    return (jnp.where(_tail(a, sizes), jnp.nan, da).astype(da.dtype), dw,
            np.zeros(sizes.shape, jax.dtypes.float0))


_nan_tail.defvjp(_nan_tail_fwd, _nan_tail_bwd)


def test_rows_past_the_held_groups_never_reach_the_result(monkeypatch):
    cfg = _expert_cfg()
    p = _share(_uncut_params(cfg, jax.random.PRNGKey(13)), 0, 8)
    x = jax.random.normal(jax.random.PRNGKey(14), (1, 16, cfg.d_model))

    def value_and_grad():
        return jax.value_and_grad(
            lambda p, x: jnp.sum(jnp.sin(moe.moe_share(p, x, cfg)[0])),
            (0, 1))(p, x)

    want = value_and_grad()
    monkeypatch.setattr(moe.jax.lax, "ragged_dot",
                        lambda a, w, sizes, **_: _nan_tail(a, w, sizes))
    got = value_and_grad()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_the_pallas_grouped_matmul_path_matches_ragged_dot(monkeypatch):
    """The TPU path (megablox, in interpret mode here) gives the held
    experts' part and its gradients as ``ragged_dot`` does."""
    import types
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    from repro.kernels import ops
    cfg = _expert_cfg(d_model=128, d_expert=128, top_k=4)
    p = _share(_uncut_params(cfg, jax.random.PRNGKey(15)), 0, 8)
    x = jax.random.normal(jax.random.PRNGKey(16), (1, 64, cfg.d_model))

    def value_and_grad():
        return jax.value_and_grad(
            lambda p, x: jnp.sum(jnp.sin(moe.moe_share(p, x, cfg)[0])),
            (0, 1))(p, x)

    want = value_and_grad()
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    monkeypatch.setattr(moe, "megablox", types.SimpleNamespace(
        gmm=lambda a, w, s, dt, t: megablox.gmm(a, w, s, dt, t, None, None,
                                                False, True)))
    got = value_and_grad()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)


def test_the_selection_bias_takes_no_gradient_and_no_decay():
    """The bias balances the step's load, takes no gradient, and is no
    parameter, so neither the optimizer nor weight decay moves it."""
    cfg = _expert_cfg()
    scores = jax.nn.sigmoid(2.0 * jax.random.normal(
        jax.random.PRNGKey(7), (512, cfg.n_experts)) + jnp.linspace(-3, 3, 64))
    bias = moe.balance_bias(scores, cfg.top_k)
    _, idx = jax.lax.top_k(scores + bias, cfg.top_k)
    load = np.bincount(np.asarray(idx).ravel(), minlength=cfg.n_experts)
    _, idx0 = jax.lax.top_k(scores, cfg.top_k)
    load0 = np.bincount(np.asarray(idx0).ravel(), minlength=cfg.n_experts)
    target = 512 * cfg.top_k / cfg.n_experts                     # 48
    assert load0.max() > 4 * target and load0.min() == 0
    assert 0.8 * target <= load.min() and load.max() <= 1.2 * target
    g = jax.grad(lambda s: jnp.sum(moe.balance_bias(s, cfg.top_k)))(scores)
    assert float(jnp.max(jnp.abs(g))) == 0.0
    p = _share(_uncut_params(cfg, jax.random.PRNGKey(7)), 0, 8)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 16, cfg.d_model))
    g = jax.grad(lambda p: jnp.sum(moe.moe_share(p, x, cfg)[0] ** 2))(p)
    assert float(jnp.max(jnp.abs(g["router"]))) > 0.0
    assert set(moe.init_moe_share(jax.random.PRNGKey(0), cfg)) == \
        {"router", "w_gate", "w_up", "w_down"}


# ------------------------------------------------ bounded attention ----
@pytest.mark.parametrize("window", [None, 8])
def test_the_chunked_core_equals_the_whole_one(window):
    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                      window=window, param_dtype="float32", dtype="float32")
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (2, 32, 4, 8))
    k = jax.random.normal(ks[1], (2, 32, 2, 8))
    v = jax.random.normal(ks[2], (2, 32, 2, 6))
    pos = jnp.arange(32)

    def loss(c, q, k, v):
        o = layers.attention_core(q, k, v, pos, c)
        return jnp.sum(jnp.sin(o)), o

    whole = jax.value_and_grad(loss, (1, 2, 3), has_aux=True)(cfg, q, k, v)
    chunked = dataclasses.replace(cfg, attn_chunk=8)
    got = jax.value_and_grad(loss, (1, 2, 3), has_aux=True)(chunked, q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(whole)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)
    text = jax.jit(lambda q: loss(chunked, q, k, v)[0]).lower(q).as_text(
        debug_info=True)
    assert "attn_core" in text


@pytest.mark.parametrize("H,Hk,dqk,dv,window,softcap", [
    (2, 2, 192, 128, None, None),
    (4, 2, 64, 64, None, None),
    (2, 2, 64, 32, 200, None),
    (2, 1, 64, 64, None, 30.0),
], ids=["causal-qk192-v128", "gqa-4-over-2", "window-200", "softcap"])
def test_the_flash_pair_equals_the_whole_core(H, Hk, dqk, dv, window,
                                              softcap):
    """The Pallas pair (interpreted) with blocks of 128 over S = 512, so
    that skipped, diagonal and full tiles all occur: its output and its
    gradients in q, k and v against ``_sdpa`` over the masked whole
    sequence."""
    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=32,
                      n_heads=H, n_kv_heads=Hk, d_ff=64, vocab_size=64,
                      window=window, attn_logit_softcap=softcap,
                      param_dtype="float32", dtype="float32")
    S = 512
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    q = jax.random.normal(ks[0], (1, S, H, dqk))
    k = jax.random.normal(ks[1], (1, S, Hk, dqk))
    v = jax.random.normal(ks[2], (1, S, Hk, dv))
    pos = jnp.arange(S)

    def whole(q, k, v):
        return layers._sdpa(q, k, v,
                            layers._scores_mask(pos, pos, window, True), cfg)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  softcap=softcap, block_q=128, block_k=128,
                                  interpret=True)

    def loss(core, q, k, v):
        o = core(q, k, v)
        return jnp.sum(jnp.sin(o)), o

    want = jax.value_and_grad(loss, (1, 2, 3), has_aux=True)(whole, q, k, v)
    got = jax.value_and_grad(loss, (1, 2, 3), has_aux=True)(flash, q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


def test_the_chunk_follows_the_shape_and_the_device_memory(monkeypatch):
    monkeypatch.setattr(layers, "device_memory_bytes", lambda: 16 * 2 ** 30)
    danube = registry.get_config("h2o-danube-3-4b")
    moon = registry.get_config("moonlight-16b-a3b")
    assert layers.q_chunk(danube, 4, 32, 2048, 2048) == 0   # b4s2k, 2.1 GB
    assert layers.q_chunk(danube, 1, 32, 2048, 2048) == 0   # pp2 micro-batch
    assert layers.q_chunk(danube, 1, 32, 8192, 8192) == 512  # 8.6 GB
    assert layers.q_chunk(moon, 1, 16, 8192, 8192) == 1024   # 4.3 GB
    # the same chunks on the memory a v5e's runtime leaves to programs
    monkeypatch.setattr(layers, "device_memory_bytes", lambda: 15.75 * 2 ** 30)
    assert layers.q_chunk(danube, 1, 32, 8192, 8192) == 512
    assert layers.q_chunk(moon, 1, 16, 8192, 8192) == 1024
    assert layers.q_chunk(dataclasses.replace(danube, attn_chunk=256),
                          4, 32, 2048, 2048) == 256
    monkeypatch.setattr(layers, "device_memory_bytes", lambda: 2 ** 16)
    assert layers.q_chunk(danube, 1, 2, 64, 64) == 4     # 4 x 512 B rows


def test_the_core_takes_the_flash_pair_where_it_would_chunk_on_the_tpu(
        monkeypatch):
    """On the TPU (``ops.use_pallas``) the core runs the flash pair where it
    would chunk, on a one-device mesh, with S a multiple of the blocks:
    Moonlight at 1 x 8192.  Danube's 4 x 2048 and pp2 micro-batch keep the
    whole ``_sdpa``; a sequence of 8000, or a mesh of two devices, keeps
    the chunked core; so does the CPU."""
    monkeypatch.setattr(layers, "device_memory_bytes", lambda: 16 * 2 ** 30)
    took = []
    sdpa = layers._sdpa

    def spy_sdpa(q, k, v, mask, cfg):
        took.append(("sdpa", mask.shape))
        return sdpa(q, k, v, mask, cfg)

    def spy_flash(q, k, v, **kw):
        took.append(("flash", kw["window"]))
        return jnp.zeros(q.shape[:3] + v.shape[3:], q.dtype)

    monkeypatch.setattr(layers, "_sdpa", spy_sdpa)
    monkeypatch.setattr(fa, "flash_attention", spy_flash)
    danube = registry.get_config("h2o-danube-3-4b")
    moon = registry.get_config("moonlight-16b-a3b")

    def path(cfg, B, S, dqk, dv):
        H, Hk = cfg.n_heads, cfg.n_kv_heads
        sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        took.clear()
        jax.eval_shape(lambda q, k, v: layers.attention_core(
            q, k, v, jnp.arange(S), cfg), sd(B, S, H, dqk),
            sd(B, S, Hk, dqk), sd(B, S, Hk, dv))
        return took[:]

    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    assert path(moon, 1, 8192, 192, 128) == [("flash", None)]
    assert path(danube, 4, 2048, 120, 120) == [("sdpa", (2048, 2048))]
    assert path(danube, 1, 2048, 120, 120) == [("sdpa", (2048, 2048))]
    assert 8000 % fa.BLOCK_Q or 8000 % fa.BLOCK_K
    assert path(moon, 1, 8000, 192, 128) == [("sdpa", (1000, 8000))]
    two = jax.sharding.AbstractMesh((2,), ("data",))
    with jax.sharding.use_abstract_mesh(two):
        assert path(moon, 2, 8192, 192, 128) == [("sdpa", (1024, 8192))]
    monkeypatch.setattr(ops, "use_pallas", lambda: False)
    assert path(moon, 1, 8192, 192, 128) == [("sdpa", (1024, 8192))]


def test_mla_through_the_flash_pair_equals_the_chunked_core(monkeypatch):
    """Latent attention whose core chunks, on the flash pair's path
    (interpreted, blocks of 128 over one kernel block of S): the value and
    the gradients in every weight and the input equal the chunked core's."""
    cfg = _smoke(attn_chunk=128)
    S = fa.BLOCK_Q * fa.BLOCK_K // math.gcd(fa.BLOCK_Q, fa.BLOCK_K)
    p = layers.init_mla(jax.random.PRNGKey(11), cfg)
    x = jax.random.normal(jax.random.PRNGKey(12), (1, S, cfg.d_model))

    def loss(p, x):
        return jnp.sum(jnp.sin(layers.mla(p, x, cfg)))

    want = jax.value_and_grad(loss, (0, 1))(p, x)
    calls = []
    kernel = functools.partial(fa.flash_attention, block_q=128, block_k=128,
                               interpret=True)

    def flash(*a, **kw):
        calls.append(kw)
        return kernel(*a, **kw)

    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    monkeypatch.setattr(fa, "flash_attention", flash)
    got = jax.value_and_grad(loss, (0, 1))(p, x)
    assert calls
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        # float32 sums in another order, over gradients of up to ~100
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=1e-4)


def test_danubes_2048_steps_hold_no_chunked_core():
    """The one-chip danube cell's shapes (4 x 2048, published widths)
    lower to the unchunked core; 1 x 8192 to the chunked one."""
    b = registry.get_bundle("h2o-danube-3-4b", num_layers=1)
    state = jax.eval_shape(lambda k: steps.init_train_state(b, k),
                           jax.random.PRNGKey(0))
    step = steps.make_train_step(b, ShardingRules(b.cfg, tp=1))

    def text(B, S):
        tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
        return jax.jit(step).lower(state, {"tokens": tok, "labels": tok}
                                   ).as_text(debug_info=True)

    assert "attn_core" not in text(4, 2048)
    assert "attn_core" in text(1, 8192)


# ---------------------------------------------- the whole tiny model ----
def _reference_cfg(cfg):
    return {"num_attention_heads": cfg.n_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_dim,
            "qk_rope_head_dim": cfg.qk_rope_dim, "v_head_dim": cfg.v_head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "router_width": cfg.n_experts, "num_experts_per_tok": cfg.top_k,
            "routed_scaling_factor": cfg.routed_scale,
            "n_routed_experts": cfg.experts_held_}


def test_tiny_model_loss_and_gradients_match_the_plain_reference():
    sys.path.insert(0, str(CHIP))
    try:
        from reference import core, mla_moe
    finally:
        sys.path.remove(str(CHIP))
    b = registry.get_bundle("moonlight-16b-a3b", smoke=True)
    cfg = b.cfg
    params = b.init(jax.random.PRNGKey(10), cfg)
    batch = registry.make_batch(cfg, batch=2, seq=32,
                                key=jax.random.PRNGKey(12))
    loss_fn = steps.make_loss_fn(b, ShardingRules(cfg, tp=1))
    dtypes = jax.tree.map(lambda x: x.dtype, params)
    ref = core.make_grad(mla_moe, _reference_cfg(cfg), dtypes,
                         z_loss=steps.Z_COEF, precision="fp32")
    with jax.default_matmul_precision("highest"):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        ref_loss, ref_grads = jax.jit(ref)(params, batch["tokens"],
                                           batch["labels"])
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(ref_grads)):
        scale = max(float(jnp.max(jnp.abs(r))), 1e-6)
        err = float(jnp.max(jnp.abs(g - r))) / scale
        assert err < 2e-4, (jax.tree_util.keystr(path), err)
    T = 2 * 32 * cfg.top_k * (cfg.num_layers - cfg.first_dense)
    assert 0 < int(metrics["moe_rows"]) < T
    assert int(metrics["moe_rows_max"]) <= 2 * 32


def test_trainer_records_the_routed_rows(tmp_path):
    from repro.launch.mesh import make_mesh
    from repro.train.trainer import Trainer, TrainerConfig
    b = registry.get_bundle("moonlight-16b-a3b", smoke=True)
    t = Trainer(b, make_mesh((1, 1), ("data", "model")),
                TrainerConfig(global_batch=2, seq_len=32,
                              ckpt_dir=str(tmp_path), ckpt_every=10 ** 6))
    t.run(2)
    last = counters.recent(2)
    assert len(last) == 2
    for rec in last:
        assert 0 < rec["moe_rows"] <= 2 * 32 * b.cfg.top_k * 2
        assert rec["moe_rows_max"] <= rec["moe_rows"]
        assert (rec["d_model"], rec["d_expert"], rec["experts_held"],
                rec["moe_layers"], rec["passes"]) == (64, 32, 8, 2, 4)


def test_serving_refuses_clearly_and_the_pipeline_refuses_the_prefix():
    from repro.parallel import pipeline
    b = registry.get_bundle("moonlight-16b-a3b", smoke=True)
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        b.init_cache(1, 16)
    with pytest.raises(ValueError, match="first_dense"):
        pipeline.make_pp_loss_fn(b.cfg, None, 2, 2)


def test_counts_price_the_model_that_runs():
    full = registry.get_config("moonlight-16b-a3b")
    assert full.param_count() == pytest.approx(15.96e9, rel=1e-3)
    chip = dataclasses.replace(full, num_layers=5, vocab_size=20480,
                               experts_held=8)
    # the cut the one-chip cell runs: 83.0 M dense layer, 4 x 100.4 M
    # expert layers, 83.9 M embedding and head
    assert chip.param_count() == pytest.approx(568.5e6, rel=2e-3)
    b = registry.bundle_for(chip)
    shapes = jax.eval_shape(lambda k: b.init(k, chip), jax.random.PRNGKey(0))
    held = sum(x.size for x in jax.tree.leaves(shapes)) - 1   # - _stacked
    assert chip.param_count() + chip.d_model == held    # + the final norm
    # a token uses 6 of 64 experts: 0.75 of one held expert on average
    F3 = 3 * 2048 * 1408
    assert chip.mlp_params(1) - chip.mlp_params(1, active_only=True) == \
        pytest.approx(8 * F3 - 0.75 * F3)
