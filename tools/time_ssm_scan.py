#!/usr/bin/env python
"""Times the selective-scan kernels alone on a TPU, and checks them on the
chip against the sequential oracle.

    PYTHONPATH=src python tools/time_ssm_scan.py

Check: the custom-vjp scan (``kernels/ssm_scan.py``) against
``kernels/ref.ssm_scan_ref`` and its vjp at B 2, S 512, d_inner 1024,
d_state 16, all fp32; prints the largest error of y and of each gradient
relative to the oracle's largest magnitude.

Times: the forward and the backward kernel each, at falcon-mamba-7b's
widths and the benchmark cell's shape (u (1, 2048, 8192) bf16, dt fp32,
B and C (1, 2048, 16) fp32, chunk 128), for d_inner blocks of 256, 512
and 1024 lanes: the host clock over 20 calls after two warm-up calls, in
ms per call (one layer).

Each line of stdout is the JSON object so far; the last is complete.
Exits non-zero without a TPU.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref, ssm_scan as ss


def _inputs(B, S, di, ds, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(ks[0], (B, S, di)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)) - 4.0)
    Bc = jax.random.normal(ks[2], (B, S, ds))
    Cc = jax.random.normal(ks[3], (B, S, ds))
    A = -jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32)[None], (di, 1))
    dy = jax.random.normal(ks[5], (B, S, di))
    return u, dt, Bc, Cc, A, dy


def _ms_per_call(f, *args, n=20):
    for _ in range(2):
        jax.block_until_ready(f(*args))
    t = time.perf_counter()
    for _ in range(n):
        r = f(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t) / n * 1e3


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def check_against_oracle() -> dict:
    u, _, Bc, Cc, _, dy = _inputs(2, 512, 1024, 16, seed=1)
    u = u.astype(jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(9), u.shape)
                         - 1.0)
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(8), (1024, 16)) * 0.3)
    with jax.default_matmul_precision("highest"):
        yk, vk = jax.jit(lambda *x: jax.vjp(ss.ssm_scan, *x))(
            u, dt, Bc, Cc, A)
        yr, vr = jax.jit(lambda *x: jax.vjp(ref.ssm_scan_ref, *x))(
            u, dt, Bc, Cc, A)
        gk, gr = jax.jit(vk)(dy), jax.jit(vr)(dy)
    return {"y": _rel(yk, yr), **{k: _rel(a, b) for k, a, b in
                                   zip(("du", "ddt", "dB", "dC", "dA"),
                                       gk, gr)}}


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"time_ssm_scan: needs a TPU, JAX found {dev.platform!r}")
    out = {"device": dev.device_kind, "check": check_against_oracle(),
           "ms": {}}
    print(json.dumps(out), flush=True)
    u, dt, Bc, Cc, A, dy = _inputs(1, 2048, 8192, 16)
    bt, ct, at = Bc.swapaxes(1, 2), Cc.swapaxes(1, 2), A.T
    for dib in (256, 512, 1024):
        fwd = jax.jit(lambda *a, dib=dib: ss._forward(*a, 128, dib, False))
        bwd = jax.jit(lambda *a, dib=dib: ss._backward(*a, 128, dib, False))
        _, h0 = fwd(u, dt, bt, ct, at)
        out["ms"][str(dib)] = {
            "fwd": _ms_per_call(fwd, u, dt, bt, ct, at),
            "bwd": _ms_per_call(bwd, u, dt, bt, ct, at, h0, dy)}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
