"""Per-architecture smoke tests (deliverable f): reduced config of the same
family, one forward + one train step on CPU, asserting shapes + no NaNs."""
import jax
import jax.numpy as jnp
import pytest

from repro.models import registry
from repro.parallel.sharding import ShardingRules
from repro.train import steps


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_forward_shapes_no_nan(arch):
    b = registry.get_bundle(arch, smoke=True)
    cfg = b.cfg
    params = b.init(jax.random.PRNGKey(0), cfg)
    batch = registry.make_batch(cfg, batch=2, seq=32)
    logits, aux = jax.jit(lambda p, bt: b.forward(p, bt, cfg))(params, batch)
    S_total = 32
    assert logits.shape == (2, S_total, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert not bool(jnp.any(jnp.isnan(logits)))
    assert not bool(jnp.isnan(aux))


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_train_step_decreases_nothing_nan(arch):
    b = registry.get_bundle(arch, smoke=True)
    rules = ShardingRules(b.cfg, tp=1, dp_axes=("data",))
    step = steps.make_train_step(b, rules)
    state = steps.init_train_state(b, jax.random.PRNGKey(0))
    batch = registry.make_batch(b.cfg, batch=2, seq=32)
    state, metrics = jax.jit(step)(state, batch)
    assert not bool(jnp.isnan(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0
    assert int(state["step"]) == 1
    # one more step: loss finite and params changed
    state2, metrics2 = jax.jit(step)(state, batch)
    assert not bool(jnp.isnan(metrics2["loss"]))
    emb0 = state["params"]["embed"]
    emb1 = state2["params"]["embed"]
    assert bool(jnp.any(emb0 != emb1))


@pytest.mark.parametrize("arch", ["llama3-8b", "mixtral-8x7b",
                                  "falcon-mamba-7b", "recurrentgemma-9b",
                                  "whisper-tiny", "phi-3-vision-4.2b"])
def test_prefill_decode_matches_forward(arch):
    """Serving path (prefill -> decode) reproduces the training forward."""
    ov = {"capacity_factor": 8.0} if "mo" in arch or "mixtral" in arch else {}
    b = registry.get_bundle(arch, smoke=True, **ov)
    cfg = b.cfg
    params = b.init(jax.random.PRNGKey(0), cfg)
    S = 32
    batch = registry.make_batch(cfg, batch=2, seq=S, with_labels=False)
    logits_full, _ = b.forward(params, batch, cfg)
    if cfg.family == "vlm":
        pre = {"tokens": batch["tokens"][:, :-4],
               "image_embeds": batch["image_embeds"]}
        tail = batch["tokens"][:, -4:]
        n_pre = batch["tokens"].shape[1] - 4 + cfg.n_vision_tokens
    elif cfg.family == "encdec":
        pre = {"tokens": batch["tokens"][:, :S - 4],
               "frames": batch["frames"]}
        tail = batch["tokens"][:, S - 4:]
        n_pre = S - 4
    else:
        pre = {"tokens": batch["tokens"][:, :S - 4]}
        tail = batch["tokens"][:, S - 4:]
        n_pre = S - 4
    lg, cache = b.prefill(params, pre, cfg, max_len=S)
    errs = [float(jnp.max(jnp.abs(lg - logits_full[:, n_pre - 1])))]
    for t in range(4):
        lg, cache = b.decode_step(params, tail[:, t:t + 1], cache, cfg)
        if t < 3:
            errs.append(float(jnp.max(jnp.abs(
                lg - logits_full[:, n_pre + t]))))
    assert max(errs) < 5e-5, f"decode diverges from forward: {errs}"


def test_rolling_window_cache_beyond_window():
    """SWA decode must stay exact after the cache wraps (> window tokens)."""
    b = registry.get_bundle("h2o-danube-3-4b", smoke=True, window=8)
    cfg = b.cfg
    params = b.init(jax.random.PRNGKey(0), cfg)
    S = 24
    batch = registry.make_batch(cfg, batch=1, seq=S, with_labels=False)
    logits_full, _ = b.forward(params, batch, cfg)
    lg, cache = b.prefill(params, {"tokens": batch["tokens"][:, :16]},
                          cfg, max_len=S)
    errs = [float(jnp.max(jnp.abs(lg - logits_full[:, 15])))]
    for t in range(16, S - 1):
        lg, cache = b.decode_step(params, batch["tokens"][:, t:t + 1],
                                  cache, cfg)
        errs.append(float(jnp.max(jnp.abs(lg - logits_full[:, t]))))
    assert max(errs) < 5e-5, errs


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_full_config_dims(arch):
    """The FULL configs carry the exact assigned dimensions."""
    cfg = registry.get_config(arch)
    expect = {
        "llama3-8b": (32, 4096, 32, 8, 14336, 128256),
        "qwen3-14b": (40, 5120, 40, 8, 17408, 151936),
        "nemotron-4-15b": (32, 6144, 48, 8, 24576, 256000),
        "h2o-danube-3-4b": (24, 3840, 32, 8, 10240, 32000),
        "falcon-mamba-7b": (64, 4096, 1, 1, 0, 65024),
        "phi-3-vision-4.2b": (32, 3072, 32, 32, 8192, 32064),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
        "phi3.5-moe-42b-a6.6b": (32, 4096, 32, 8, 6400, 32064),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "whisper-tiny": (4, 384, 6, 6, 1536, 51872),  # vocab padded for TP
        "moonlight-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expect
    if arch == "mixtral-8x7b":
        assert (cfg.n_experts, cfg.top_k, cfg.window) == (8, 2, 4096)
    if arch == "phi3.5-moe-42b-a6.6b":
        assert (cfg.n_experts, cfg.top_k) == (16, 2)
    if arch == "falcon-mamba-7b":
        assert cfg.ssm_state == 16
    if arch == "recurrentgemma-9b":
        assert cfg.block_pattern == ("rec", "rec", "attn")
    if arch == "moonlight-16b-a3b":
        assert (cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
                cfg.v_head_dim) == (512, 128, 64, 128)
        assert (cfg.n_experts, cfg.top_k, cfg.d_expert, cfg.n_shared_experts,
                cfg.first_dense, cfg.routed_scale) == (64, 6, 1408, 2, 1,
                                                       2.446)
