"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import ref
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssm_scan import ssm_scan
from repro.kernels.swiglu import swiglu


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,Sq,Sk,H,Hk,hd", [
    (1, 128, 128, 4, 4, 64),      # MHA square
    (2, 128, 128, 4, 2, 64),      # GQA
    (1, 256, 256, 8, 1, 128),     # MQA, 128 head dim
    (2, 128, 256, 4, 2, 64),      # decode-suffix (Sq < Sk, end-aligned)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(B, Sq, Sk, H, Hk, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hk, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hk, hd), jnp.float32).astype(dtype)
    out = fa.flash_attention(q, k, v, causal=True, interpret=True,
                             block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [32, 64])
def test_flash_attention_swa(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))
    out = fa.flash_attention(q, k, v, causal=True, window=window,
                             interpret=True, block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_softcap():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))
    out = fa.flash_attention(q, k, v, causal=True, softcap=30.0,
                             interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 64), dtype=jnp.float32)
    k = jax.random.normal(ks[1], (2, 128, 4, 64))
    v = jax.random.normal(ks[2], (2, 128, 4, 64))
    out = fa.flash_attention(q, k, v, causal=False, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", [(64, 256), (3, 17, 384), (2, 8, 8, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, shape, jnp.float32).astype(dtype)
    s = jax.random.normal(k2, (shape[-1],), jnp.float32).astype(dtype)
    out = rmsnorm(x, s, interpret=True, block_rows=16)
    want = ref.rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,di,ds,chunk,dib", [
    (1, 64, 64, 8, 16, 32),
    (2, 128, 128, 16, 64, 64),
    (1, 256, 64, 4, 128, 64),
])
def test_ssm_scan(B, S, di, ds, chunk, dib):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    u = jax.random.normal(ks[0], (B, S, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)) - 1.0)
    Bc = jax.random.normal(ks[2], (B, S, ds))
    Cc = jax.random.normal(ks[3], (B, S, ds))
    A = -jnp.exp(jax.random.normal(ks[4], (di, ds)) * 0.3)
    out = ssm_scan(u, dt, Bc, Cc, A, chunk=chunk, di_block=dib,
                   interpret=True)
    want = ref.ssm_scan_ref(u, dt, Bc, Cc, A)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def _scan_inputs(B, S, di, ds, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(ks[0], (B, S, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)) - 1.0)
    Bc = jax.random.normal(ks[2], (B, S, ds))
    Cc = jax.random.normal(ks[3], (B, S, ds))
    A = -jnp.exp(jax.random.normal(ks[4], (di, ds)) * 0.3)
    dy = jax.random.normal(ks[5], (B, S, di))
    return (u, dt, Bc, Cc, A), dy


def _close(got, want, rel=1e-4):
    """Within ``rel`` of the largest magnitude of ``want``."""
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=rel,
                               atol=rel * float(np.max(np.abs(want))))


@pytest.mark.parametrize("B,S,di,ds,chunk,dib", [
    (1, 32, 128, 8, 32, 128),     # one chunk, one d_inner block
    (2, 64, 256, 16, 16, 128),    # several chunks and d_inner blocks
    (1, 48, 256, 4, 32, 128),     # padded to two chunks, several blocks
    (2, 32, 128, 16, 16, 128),    # batch 2, one d_inner block
], ids=["one_chunk", "chunks_blocks", "padded", "batch2"])
def test_ssm_scan_grad(B, S, di, ds, chunk, dib):
    """The custom-vjp scan and its gradient of u, dt, B, C and A against
    the sequential oracle and jax.grad of it."""
    args, dy = _scan_inputs(B, S, di, ds)
    y, vjp = jax.vjp(lambda *a: ssm_scan(*a, chunk=chunk, di_block=dib,
                                         interpret=True), *args)
    y_ref, vjp_ref = jax.vjp(ref.ssm_scan_ref, *args)
    _close(y, y_ref)
    for got, want in zip(vjp(dy), vjp_ref(dy)):
        _close(got, want)


@pytest.mark.parametrize("S,di,kernel", [
    (32, 128, True),
    (40, 128, False),             # sequence not a multiple of 16 rows
    (32, 96, False),              # d_inner not a multiple of 128 lanes
], ids=["kernel", "fallback_seq", "fallback_lanes"])
def test_selective_scan_dispatch(monkeypatch, S, di, kernel):
    """``selective_scan`` takes the kernel where the shapes allow it and
    the jnp chunked scan elsewhere; both give the jnp path's values and
    gradients.  The test steers it onto the kernel path on the CPU, with
    the kernels interpreted, and counts the kernel's calls."""
    from repro.kernels import ops, ssm_scan as ss
    from repro.models import mamba
    (u, dt, Bc, Cc, A), dy = _scan_inputs(2, S, di, 4, seed=1)
    ks = jax.random.split(jax.random.PRNGKey(2))
    D, z = jax.random.normal(ks[0], (di,)), jax.random.normal(ks[1], u.shape)
    args = (u, dt, Bc, Cc, A, D, z)

    def run(*a):
        return jax.vjp(lambda *x: mamba.selective_scan(*x, chunk=16), *a)

    y_ref, vjp_ref = run(*args)
    calls, scan = [], ss.ssm_scan

    def interpreted(*a, **kw):
        calls.append(a[0].shape)
        return scan(*a, interpret=True, **kw)

    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    monkeypatch.setattr(ss, "ssm_scan", interpreted)
    y, vjp = run(*args)
    assert calls == ([u.shape] if kernel else [])
    _close(y, y_ref)
    for got, want in zip(vjp(dy), vjp_ref(dy)):
        _close(got, want)


def test_selective_scan_shard_map_on_four_devices():
    """The kernel path over meshes of four host devices runs under
    ``jax.shard_map`` and gives the single-device values and gradients:
    batch over data and d_inner over model; batch over (pod, data) inside
    a vmap over pipeline stages; and a batch that data does not divide,
    held as a replica (subprocess: the device count is set before JAX
    starts)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import functools
import jax, jax.numpy as jnp, numpy as np
from repro.kernels import ops, ssm_scan as ss
from repro.launch.mesh import make_mesh
from repro.models import mamba
ops.use_pallas = lambda: True
ss.ssm_scan = functools.partial(ss.ssm_scan, interpret=True)

def inputs(lead, S=32, di=256, ds=4):
    ks = jax.random.split(jax.random.PRNGKey(len(lead)), 8)
    seq, rows = lead + (S, di), lead + (S, ds)
    return ((jax.random.normal(ks[0], seq),
             jax.nn.softplus(jax.random.normal(ks[1], seq) - 1.0),
             jax.random.normal(ks[2], rows), jax.random.normal(ks[3], rows),
             -jnp.exp(jax.random.normal(ks[4], (di, ds)) * 0.3),
             jax.random.normal(ks[5], (di,)), jax.random.normal(ks[6], seq)),
            jax.random.normal(ks[7], seq))

def grads(stages):
    def f(dy, *a):
        scan = lambda *x: mamba.selective_scan(*x, chunk=16)
        if stages:
            scan = jax.vmap(scan, in_axes=(0, 0, 0, 0, None, None, 0))
        y, vjp = jax.vjp(scan, *a)
        return (y,) + vjp(dy)
    return jax.jit(f)

for shape, axes, lead in [((2, 2), ("data", "model"), (2,)),
                          ((2, 2, 1), ("pod", "data", "model"), (2, 4)),
                          ((4, 1), ("data", "model"), (2,))]:
    args, dy = inputs(lead)
    f = grads(len(lead) == 2)
    want = f(dy, *args)
    with jax.set_mesh(make_mesh(shape, axes)):
        assert "shard_map" in str(jax.make_jaxpr(f)(dy, *args))
        got = f(dy, *args)
    for g, w in zip(got, want):
        w = np.asarray(w)
        np.testing.assert_allclose(np.asarray(g), w, rtol=1e-5,
                                   atol=1e-5 * float(np.max(np.abs(w))))
print("SHARDED_SCAN_OK")
"""
    import subprocess
    import sys
    from pathlib import Path
    r = subprocess.run([sys.executable, "-c", code],
                       cwd=str(Path(__file__).resolve().parents[1]),
                       capture_output=True, text=True, timeout=600)
    assert "SHARDED_SCAN_OK" in r.stdout, r.stderr[-2000:]


@pytest.mark.parametrize("shape", [(32, 128), (2, 64, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swiglu(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    g = jax.random.normal(k1, shape, jnp.float32).astype(dtype)
    u = jax.random.normal(k2, shape, jnp.float32).astype(dtype)
    out = swiglu(g, u, interpret=True, block_rows=16)
    want = ref.swiglu_ref(g, u)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_model_attention_uses_same_math():
    """layers.attention (model path) agrees with the kernel oracle."""
    from repro.models.config import ModelConfig
    from repro.models.layers import _sdpa
    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64,
                      param_dtype="float32", dtype="float32")
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 32, 4, 16))
    k = jax.random.normal(ks[1], (2, 32, 2, 16))
    v = jax.random.normal(ks[2], (2, 32, 2, 16))
    mask = jnp.tril(jnp.ones((32, 32), bool))
    out = _sdpa(q, k, v, mask, cfg)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------- ring attention step ----
def _ring_state(B, Cq, H, hd):
    from repro.kernels.ring_attention import NEG_INF
    return (jnp.full((B, Cq, H, 1), NEG_INF, jnp.float32),
            jnp.zeros((B, Cq, H, 1), jnp.float32),
            jnp.zeros((B, Cq, H, hd), jnp.float32))


@pytest.mark.parametrize("q_start,k_start,k_valid", [
    (0, 0, 48),       # self hop (ring step 0): causal diagonal inside
    (48, 0, 48),      # past hop: fully visible prefix block
    (0, 48, 48),      # wrap hop: KV from a LATER chunk — fully masked
    (64, 32, 17),     # masked partial chunk: only 17 of 48 rows real
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ring_step_matches_ref(q_start, k_start, k_valid, dtype):
    """One Pallas ring hop (interpret mode) vs the jnp fold, across the
    hop geometries the ring visits: self, past, wrap and ragged-partial
    KV blocks.  The carried (m, l, acc) state must agree element-wise —
    the ring result is only as good as every intermediate fold."""
    import math
    from repro.kernels import ring_attention as ra
    B, Cq, Ck, H, Hk, hd = 2, 48, 48, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, Cq, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Ck, Hk, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Ck, Hk, hd), jnp.float32).astype(dtype)
    # a warm carry (from a previous self hop) so the fold is a real merge
    m0, l0, acc0 = ra._ring_step_ref(
        q, q[:, :, :Hk], v, *_ring_state(B, Cq, H, hd),
        q_start=q_start, k_start=q_start, k_valid=Cq, causal=True,
        sm_scale=1.0 / math.sqrt(hd))
    want = ra._ring_step_ref(q, k, v, m0, l0, acc0, q_start=q_start,
                             k_start=k_start, k_valid=k_valid, causal=True,
                             sm_scale=1.0 / math.sqrt(hd))
    got = ra.ring_step(q, k, v, m0, l0, acc0, q_start=q_start,
                       k_start=k_start, k_valid=k_valid, causal=True,
                       block_q=32, block_k=32, interpret=True)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), **_tol(dtype))


def test_ring_step_fully_masked_hop_is_noop():
    """A wrap hop under causal masking (every key in the future) must pass
    the carried state through bit-exactly once a self hop seeded a finite
    max — the SPMD no-causal-skip invariant the cp loss builder relies
    on."""
    import math
    from repro.kernels import ring_attention as ra
    B, C, H, Hk, hd = 1, 32, 2, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, C, H, hd))
    k = jax.random.normal(ks[1], (B, C, Hk, hd))
    v = jax.random.normal(ks[2], (B, C, Hk, hd))
    state = ra._ring_step_ref(q, k, v, *_ring_state(B, C, H, hd),
                              q_start=0, k_start=0, k_valid=C, causal=True,
                              sm_scale=1.0 / math.sqrt(hd))
    for step in (ra._ring_step_ref,):
        m1, l1, acc1 = step(q, k, v, *state, q_start=0, k_start=C,
                            k_valid=C, causal=True,
                            sm_scale=1.0 / math.sqrt(hd))
        for a, b in zip((m1, l1, acc1), state):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    m1, l1, acc1 = ra.ring_step(q, k, v, *state, q_start=0, k_start=C,
                                k_valid=C, causal=True, block_q=32,
                                block_k=32, interpret=True)
    for a, b in zip((m1, l1, acc1), state):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
