"""jit-able train / prefill / decode steps with sharding attached.

``make_train_step`` builds the pjit'd fwd+bwd+AdamW step for any registry
arch; ``make_prefill_step`` / ``make_decode_step`` build the serving steps.
These are what launch/dryrun.py lowers for every (arch x shape x mesh) cell
and what launch/train.py executes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.registry import ArchBundle
from repro.optim import adamw
from repro.parallel.sharding import ShardingRules

AUX_COEF = 0.01
Z_COEF = 1e-4


@jax.named_scope("head_loss")
def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Stable CE over a (possibly vocab-sharded) logits tensor, fp32.

    The gold logit is extracted with a one-hot contraction, not
    take_along_axis: a gather indexed across a sharded vocab dim would make
    GSPMD all-gather the full logits (tens of GB); the one-hot product
    partitions cleanly (local mask-multiply + small psum)."""
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1)
    zloss = Z_COEF * jnp.mean(jnp.square(lse))
    return jnp.mean(lse - gold) + zloss


def constrain(x, spec):
    """with_sharding_constraint that no-ops outside a mesh context."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except RuntimeError:
        return x


@jax.named_scope("head_loss")
def _ce_sums(logits, labels):
    """(sum of (lse - gold), sum of lse^2, count) — chunk-combinable."""
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1)
    return jnp.sum(lse - gold), jnp.sum(jnp.square(lse)), lse.size


def make_loss_fn(bundle: ArchBundle, rules: ShardingRules):
    cfg = bundle.cfg

    def loss_fn(params, batch):
        from repro.models import registry as _reg
        if cfg.loss_chunk and cfg.family != "encdec":
            # fuse unembed+CE over sequence chunks: the (B,S,V) logits never
            # materialize (dominant temp for 150k-256k vocabs)
            feats, w, aux, stats = _reg.lm_features(params, batch, cfg)
            labels = constrain(batch["labels"], P(rules.dp_axes, None))
            B, S, D = feats.shape
            c = min(cfg.loss_chunk, S)
            n = S // c
            fc = feats[:, :n * c].reshape(B, n, c, D).swapaxes(0, 1)
            lc = labels[:, :n * c].reshape(B, n, c).swapaxes(0, 1)

            @jax.named_scope("head_loss")
            def body(acc, xs):
                f, l = xs
                logits = jnp.einsum("bsd,dv->bsv", f, w,
                                    preferred_element_type=jnp.float32)
                logits = constrain(logits, rules.logits_spec())
                s_ce, s_z, cnt = _ce_sums(logits, l)
                return (acc[0] + s_ce, acc[1] + s_z, acc[2] + cnt), None

            fn = jax.checkpoint(body) if cfg.remat else body
            (s_ce, s_z, cnt), _ = jax.lax.scan(
                fn, (jnp.zeros(()), jnp.zeros(()), 0.0), (fc, lc))
            ce = s_ce / cnt + Z_COEF * (s_z / cnt)
            return ce + AUX_COEF * aux, {"ce": ce, "aux": aux, **stats}
        logits, aux = bundle.forward(params, batch, cfg)
        logits = constrain(logits, rules.logits_spec())
        labels = constrain(batch["labels"], P(rules.dp_axes, None))
        ce = cross_entropy(logits, labels)
        return ce + AUX_COEF * aux, {"ce": ce, "aux": aux}

    return loss_fn


def make_train_step(bundle: ArchBundle, rules: ShardingRules,
                    opt_cfg: Optional[adamw.AdamWConfig] = None,
                    grad_accum: int = 1, loss_fn=None):
    """Returns train_step(state, batch) -> (state, metrics).

    grad_accum > 1 splits the per-step batch into microbatches scanned with
    gradient accumulation (activation-memory lever; the pipeline runtime has
    its own microbatching).  A custom loss_fn (e.g. the pod-axis pipeline)
    may replace the default full-forward loss."""
    cfg = bundle.cfg
    opt_cfg = opt_cfg or adamw.AdamWConfig()
    loss_fn = loss_fn or make_loss_fn(bundle, rules)

    def train_step(state, batch):
        params = state["params"]
        if grad_accum == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            def split(x):
                b = x.shape[0]
                return x.reshape(grad_accum, b // grad_accum, *x.shape[1:])

            micro = jax.tree.map(split, batch)

            def body(carry, mb):
                acc, lsum = carry
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb)
                return (jax.tree.map(jnp.add, acc, g), lsum + l), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, lsum), _ = jax.lax.scan(body, (zeros, 0.0), micro)
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            loss = lsum / grad_accum
            metrics = {"ce": loss, "aux": jnp.zeros((), jnp.float32)}
        new_params, new_opt, om = adamw.adamw_update(
            params, grads, state["opt"], opt_cfg)
        metrics = dict(metrics, loss=loss, **om)
        return {"params": new_params, "opt": new_opt,
                "step": state["step"] + 1}, metrics

    return train_step


def init_train_state(bundle: ArchBundle, key) -> dict:
    params = bundle.init(key, bundle.cfg)
    keep_master = bundle.cfg.param_dtype != "float32"
    return {"params": params,
            "opt": adamw.init_opt_state(params, keep_master=keep_master),
            "step": jnp.zeros((), jnp.int32)}


# ------------------------------------------------------------- sharding ----
def state_specs(bundle: ArchBundle, rules: ShardingRules, state_shape,
                data_size: int):
    """PartitionSpec pytree for the train state (ZeRO-1 on moments)."""
    pspecs = rules.param_specs(state_shape["params"])

    def zero1(spec_tree, shapes_tree):
        return jax.tree.map(
            lambda sp, sh: rules.opt_state_spec(sp, sh.shape, data_size),
            spec_tree, shapes_tree)

    opt = state_shape["opt"]
    opt_specs = {"count": P()}
    for k in ("m", "v", "master"):
        if k in opt:
            opt_specs[k] = zero1(pspecs, opt[k])
    return {"params": pspecs, "opt": opt_specs, "step": P()}


def batch_specs(cfg: ModelConfig, rules: ShardingRules, batch_shape) -> Any:
    out = {}
    for k in batch_shape:
        if k in ("tokens", "labels"):
            out[k] = rules.batch_spec()
        else:  # frames / image_embeds: (B, S, D)
            out[k] = P(rules.batch_axes, None, None)
    return out


def cache_specs(cfg: ModelConfig, rules: ShardingRules, cache_shape,
                data_size: int) -> Any:
    """Decode cache sharding: batch->data, seq->model (flash-decoding
    layout); SSM/rec states shard inner dims over model."""
    T = rules.tp_axis
    D_ = rules.dp_axes

    def spec_of(path, leaf):
        names = [getattr(k, "key", str(k)) for k in path]
        shape = leaf.shape
        batch_ok = len(shape) > 1 and shape[1] % data_size == 0
        bspec = D_ if batch_ok else None
        if "kv" in names or "xkv" in names:       # (L, B, S, Hk, hd)
            seq_ok = shape[2] % rules.tp == 0
            return P(None, bspec, T if seq_ok else None, None, None)
        if names[-1] == "h" and "ssm" in names:   # (L, B, di, ds)
            return P(None, bspec, T if rules.shard_inner else None, None)
        if names[-1] == "conv" and "ssm" in names:  # (L, B, K-1, di)
            return P(None, bspec, None, T if rules.shard_inner else None)
        if names[-1] == "h" and "rec" in names:   # (L, B, W)
            return P(None, bspec, T if rules.shard_lru else None)
        if names[-1] == "conv" and "rec" in names:
            return P(None, bspec, None, T if rules.shard_lru else None)
        return P()

    flat = jax.tree_util.tree_flatten_with_path(cache_shape)[0]
    specs = [spec_of(kp, leaf) for kp, leaf in flat]
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(cache_shape), specs)


def make_prefill_step(bundle: ArchBundle, max_len: int):
    cfg = bundle.cfg

    def prefill_step(params, batch):
        return bundle.prefill(params, batch, cfg, max_len)

    return prefill_step


def make_decode_step(bundle: ArchBundle):
    cfg = bundle.cfg

    def decode_step(params, token, cache):
        return bundle.decode_step(params, token, cache, cfg)

    return decode_step
