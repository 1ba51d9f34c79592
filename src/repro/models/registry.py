"""Architecture registry: maps arch-id -> (config, unified model functions).

Unified batch dict keys:
  tokens        (B, S) int32           all archs
  frames        (B, S_enc, D) float    enc-dec audio stub frontend
  image_embeds  (B, N, D) float        VLM stub frontend (prepended)
  labels        (B, S) int32           training

The registry is what launch/, the planner and the benchmarks consume; adding
an architecture = one config file + a registry entry.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import encdec, transformer
from repro.models.config import ModelConfig

ARCH_IDS = (
    "llama3-8b", "qwen3-14b", "nemotron-4-15b", "h2o-danube-3-4b",
    "falcon-mamba-7b", "phi-3-vision-4.2b", "mixtral-8x7b",
    "phi3.5-moe-42b-a6.6b", "recurrentgemma-9b", "whisper-tiny",
    "moonlight-16b-a3b",
)

_MODULES = {a: "repro.configs." + a.replace("-", "_").replace(".", "_")
            for a in ARCH_IDS}


def check_last_logits(logits, batch: int, vocab: int,
                      where: str = "prefill"):
    """Serving contract: ``prefill`` and ``decode_step`` return
    LAST-position logits of shape (B, V) — never the full-sequence
    (B, S, V) that ``forward`` returns.  Every family in the registry
    satisfies it (transformer.lm_prefill slices ``x[:, -1:]``, encdec
    likewise), and the serving engine asserts it once per compiled
    function so a new arch entry can't silently hand full-sequence logits
    to the sampler (which would argmax over vocab at EVERY position and
    emit position-0's token)."""
    shape = tuple(getattr(logits, "shape", ()))
    if shape != (batch, vocab):
        raise ValueError(
            f"{where} logits must be last-position (batch, vocab) = "
            f"{(batch, vocab)}, got {shape} — full-sequence (B, S, V) "
            f"logits violate the registry serving contract")
    return logits


@dataclasses.dataclass(frozen=True)
class ArchBundle:
    cfg: ModelConfig
    init: Callable[..., Any]
    forward: Callable[..., Any]       # (params, batch, cfg) -> (logits, aux)
    # serving contract (check_last_logits): both return (B, V) logits of
    # the LAST position only
    prefill: Callable[..., Any]       # (params, batch, cfg, max_len) -> (logits, cache)
    decode_step: Callable[..., Any]   # (params, token, cache, cfg) -> (logits, cache)

    def init_cache(self, batch: int, max_len: int):
        if self.cfg.family == "encdec":
            return encdec.encdec_init_cache(self.cfg, batch, max_len, max_len)
        return transformer.init_cache(self.cfg, batch, max_len)

    @property
    def has_decoder(self) -> bool:
        return True  # all assigned archs have a decode path (enc-dec included)

    @property
    def subquadratic(self) -> bool:
        """True if long_500k is runnable (SWA window / SSM / hybrid)."""
        c = self.cfg
        if c.family in ("ssm", "hybrid"):
            return True
        return c.window is not None


def _lm_forward(params, batch, cfg):
    extra = batch.get("image_embeds")
    return transformer.lm_forward(params, batch["tokens"], cfg,
                                  extra_embeds=extra)


def lm_features(params, batch, cfg):
    extra = batch.get("image_embeds")
    return transformer.lm_features(params, batch["tokens"], cfg,
                                   extra_embeds=extra)


def _lm_prefill(params, batch, cfg, max_len):
    extra = batch.get("image_embeds")
    return transformer.lm_prefill(params, batch["tokens"], cfg, max_len,
                                  extra_embeds=extra)


def _ed_forward(params, batch, cfg):
    return encdec.encdec_forward(params, batch["frames"], batch["tokens"], cfg)


def _ed_prefill(params, batch, cfg, max_len):
    return encdec.encdec_prefill(params, batch["frames"], batch["tokens"],
                                 cfg, max_len)


def bundle_for(cfg: ModelConfig) -> ArchBundle:
    if cfg.family == "encdec":
        return ArchBundle(cfg, encdec.init_encdec, _ed_forward, _ed_prefill,
                          encdec.encdec_decode_step)
    return ArchBundle(cfg, transformer.init_lm, _lm_forward, _lm_prefill,
                      transformer.lm_decode_step)


def get_config(arch: str, smoke: bool = False, **overrides) -> ModelConfig:
    mod = importlib.import_module(_MODULES[arch])
    cfg = mod.SMOKE if smoke else mod.CONFIG
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def get_bundle(arch: str, smoke: bool = False, **overrides) -> ArchBundle:
    return bundle_for(get_config(arch, smoke=smoke, **overrides))


def make_batch(cfg: ModelConfig, batch: int, seq: int, key=None,
               with_labels: bool = True) -> Dict[str, jax.Array]:
    """Concrete (small) batch for smoke tests; mirrors launch.input_specs."""
    key = key if key is not None else jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    out: Dict[str, jax.Array] = {}
    if cfg.family == "encdec":
        out["frames"] = jax.random.normal(
            k2, (batch, seq, cfg.d_model), jnp.float32).astype(cfg.adtype)
        out["tokens"] = jax.random.randint(k1, (batch, seq), 0,
                                           cfg.vocab_size, jnp.int32)
    elif cfg.family == "vlm":
        n = cfg.n_vision_tokens
        s_text = max(seq - n, 8)
        out["tokens"] = jax.random.randint(k1, (batch, s_text), 0,
                                           cfg.vocab_size, jnp.int32)
        out["image_embeds"] = jax.random.normal(
            k2, (batch, n, cfg.d_model), jnp.float32).astype(cfg.adtype)
    else:
        out["tokens"] = jax.random.randint(k1, (batch, seq), 0,
                                           cfg.vocab_size, jnp.int32)
    if with_labels:
        total = out["tokens"].shape[1] + (
            cfg.n_vision_tokens if cfg.family == "vlm" else 0)
        out["labels"] = jax.random.randint(jax.random.PRNGKey(7),
                                           (batch, total), 0,
                                           cfg.vocab_size, jnp.int32)
    return out
