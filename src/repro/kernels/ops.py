"""jit'd dispatch wrappers: compiled Pallas kernel on TPU, the pure-jnp
oracle (which XLA fuses) on every other backend."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import flash_attention as fa
from repro.kernels import ref, rmsnorm as rn, swiglu as sg


def use_pallas() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap"))
def flash_attention(q, k, v, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None):
    if not use_pallas():
        return ref.flash_attention_ref(q, k, v, causal=causal,
                                       window=window, softcap=softcap)
    return fa.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap)


@functools.partial(jax.jit, static_argnames=("eps",))
def rmsnorm(x, scale, eps: float = 1e-5):
    if not use_pallas():
        return ref.rmsnorm_ref(x, scale, eps)
    return rn.rmsnorm(x, scale, eps)


@jax.jit
def swiglu(g, u):
    if not use_pallas():
        return ref.swiglu_ref(g, u)
    return sg.swiglu(g, u)
