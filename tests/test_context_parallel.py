"""Context-parallelism equivalence lockdown (ISSUE: cp as a plan dim).

  * ring attention (jnp ring + Pallas step) fwd+bwd vs the kernel oracle
    over random (batch, heads, seq, cp, causal) shapes — equal AND ragged
    per-island chunk splits, including a final partial chunk;
  * ``segmentation.cp_split`` exact min-bottleneck optimality against
    brute force on small cases (the dp_split lockdown applied to the
    context axis), plus the causal-triangle property (equal-rate rings
    want DECREASING chunks) and heterogeneous-rate behaviour;
  * the SPMD cp loss builder (parallel/context.py) vs the reference loss
    fwd+grad, and the Trainer routing a pp=1 cp>1 plan through it;
  * the cp=1 contract: plans without cp are bit-identical through the
    predictor and never enter the cp builder.

Numerics: online-softmax regrouping is not bit-associative, so cp>1 vs
reference is tolerance-based (2e-5 fp32 / 2e-2 bf16 — the repo-wide
kernel tolerance); cp=1 paths must be bit-exact.
"""
import random
import tempfile
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import cluster as C
from repro.core import costmodel, segmentation
from repro.core.plan import ParallelPlan, StagePlacement
from repro.core.predictor import PerformancePredictor
from repro.kernels import ref
from repro.kernels import ring_attention as ra
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.parallel import context
from repro.parallel.sharding import ShardingRules
from repro.profile.store import ProfileStore
from repro.train import steps
from repro.train.trainer import Trainer, TrainerConfig


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


def _rand_chunks(rng, S, cp):
    """A random ragged composition of S into cp parts (each >= 1)."""
    cuts = sorted(rng.sample(range(1, S), cp - 1)) if cp > 1 else []
    bounds = [0] + cuts + [S]
    return tuple(b - a for a, b in zip(bounds, bounds[1:]))


# ------------------------------------------------------ ring vs oracle ----
@pytest.mark.parametrize("chunks", [
    (48, 48),              # equal split
    (40, 31, 25),          # ragged, decreasing (the cp_split shape)
    (16, 50, 30),          # ragged, non-monotone
    (95, 1),               # final partial chunk (1 token on the last rank)
    (1, 94, 1),            # degenerate first/last ranks
])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_reference(chunks, causal):
    S = sum(chunks)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, S, 4, 32))
    k = jax.random.normal(ks[1], (2, S, 2, 32))
    v = jax.random.normal(ks[2], (2, S, 2, 32))
    out = ra.ring_flash_attention(q, k, v, chunks, causal=causal)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               **_tol(jnp.float32))


@pytest.mark.parametrize("chunks", [(48, 48), (40, 31, 25), (50, 30, 16)])
def test_ring_backward_matches_reference(chunks):
    """jax.grad through the jnp ring == grad through the oracle."""
    S = sum(chunks)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, S, 4, 32))
    k = jax.random.normal(ks[1], (1, S, 2, 32))
    v = jax.random.normal(ks[2], (1, S, 2, 32))

    def f_ring(q, k, v):
        return jnp.sum(jnp.square(
            ra.ring_flash_attention(q, k, v, chunks, causal=True)))

    def f_ref(q, k, v):
        return jnp.sum(jnp.square(
            ref.flash_attention_ref(q, k, v, causal=True)))

    g0 = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
    g1 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g0, g1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_ring_seeded_shape_sweep():
    """Deterministic randomized sweep over (B, heads, seq, cp, causal) —
    runs even without hypothesis."""
    rng = random.Random(42)
    for _ in range(25):
        B = rng.randint(1, 2)
        Hk = rng.choice([1, 2])
        H = Hk * rng.choice([1, 2, 4])
        hd = rng.choice([16, 32])
        cp = rng.randint(2, 4)
        S = rng.randint(cp, 96)
        causal = rng.random() < 0.7
        chunks = _rand_chunks(rng, S, cp)
        ks = jax.random.split(jax.random.PRNGKey(rng.randint(0, 999)), 3)
        q = jax.random.normal(ks[0], (B, S, H, hd))
        k = jax.random.normal(ks[1], (B, S, Hk, hd))
        v = jax.random.normal(ks[2], (B, S, Hk, hd))
        out = ra.ring_flash_attention(q, k, v, chunks, causal=causal)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5,
            err_msg=f"chunks={chunks} causal={causal} B={B} H={H}/{Hk}")


@given(st.integers(2, 4), st.integers(0, 2 ** 30), st.booleans())
@settings(max_examples=30, deadline=None)
def test_ring_matches_reference_property(cp, seed, causal):
    """Property form: any ragged composition of any S agrees with the
    oracle (seeded via --hypothesis-seed=0 in CI)."""
    rng = random.Random(seed)
    S = rng.randint(cp, 80)
    chunks = _rand_chunks(rng, S, cp)
    ks = jax.random.split(jax.random.PRNGKey(seed % 997), 3)
    q = jax.random.normal(ks[0], (1, S, 2, 16))
    k = jax.random.normal(ks[1], (1, S, 2, 16))
    v = jax.random.normal(ks[2], (1, S, 2, 16))
    out = ra.ring_flash_attention(q, k, v, chunks, causal=causal)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_pallas_path_matches_reference():
    """The Pallas ring_step hop chain (interpret mode) agrees with the
    oracle on a ragged split including the wrap hop."""
    chunks = (40, 31, 25)
    S = sum(chunks)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, S, 4, 32))
    k = jax.random.normal(ks[1], (1, S, 2, 32))
    v = jax.random.normal(ks[2], (1, S, 2, 32))
    out = ra.ring_flash_attention(q, k, v, chunks, causal=True,
                                  use_pallas=True, block_q=32, block_k=32,
                                  interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_pad_unpad_roundtrip():
    x = jnp.arange(2 * 17 * 3, dtype=jnp.float32).reshape(2, 17, 3)
    for chunks in [(17,), (9, 8), (5, 11, 1)]:
        y = ra.unpad_chunks(ra.pad_chunks(x, chunks), chunks)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


# ------------------------------------------------------------- cp_split ----
def _brute_cp_bottleneck(S, cp, attn, lin, rates=None, causal=True):
    """Exhaustive min over all compositions of S into cp chunks."""
    r = rates or [1.0] * cp
    best = None

    def rec(rank, left, prefix, worst):
        nonlocal best
        if rank == cp - 1:
            c = left
            b = prefix + c
            kv = b if causal else S
            cost = max(worst, r[rank] * c * (lin + attn * kv))
            best = cost if best is None else min(best, cost)
            return
        for c in range(1, left - (cp - rank - 1) + 1):
            b = prefix + c
            kv = b if causal else S
            cost = r[rank] * c * (lin + attn * kv)
            rec(rank + 1, left - c, b, max(worst, cost))

    rec(0, S, 0, 0.0)
    return best


def _cp_cost(split, attn, lin, rates=None, causal=True):
    S = sum(split)
    r = rates or [1.0] * len(split)
    b, worst = 0, 0.0
    for rank, c in enumerate(split):
        b += c
        kv = b if causal else S
        worst = max(worst, r[rank] * c * (lin + attn * kv))
    return worst


def test_cp_split_optimal_brute_force():
    """cp_split's bottleneck equals the exhaustive optimum (the dp_split
    lockdown applied to the context axis)."""
    rng = random.Random(42)
    for _ in range(60):
        cp = rng.randint(2, 4)
        S = rng.randint(cp, 24)
        attn = rng.uniform(0.01, 1.0)
        lin = rng.choice([0.0, rng.uniform(0.0, 2.0)])
        if attn == 0.0 and lin == 0.0:
            continue
        causal = rng.random() < 0.7
        rates = ([rng.uniform(0.5, 2.0) for _ in range(cp)]
                 if rng.random() < 0.5 else None)
        split = segmentation.cp_split(S, cp, attn, lin, rates=rates,
                                      causal=causal)
        assert sum(split) == S and all(c >= 1 for c in split)
        got = _cp_cost(split, attn, lin, rates, causal)
        want = _brute_cp_bottleneck(S, cp, attn, lin, rates, causal)
        assert got == pytest.approx(want, rel=1e-9), \
            (S, cp, attn, lin, rates, causal, split)


def test_cp_split_causal_triangle_decreasing():
    """Equal rates + causal: later ranks see longer prefixes, so the
    optimal chunks never increase along the ring."""
    for S, cp in [(4096, 4), (1000, 3), (64, 2)]:
        split = segmentation.cp_split(S, cp, attn=1.0 / S, lin=0.5)
        assert all(a >= b for a, b in zip(split, split[1:])), split
        assert sum(split) == S


def test_cp_split_heterogeneous_rates():
    """A slower rank (HexiSeq: slower device kind) gets a shorter chunk
    than an equal-rate ring would give it."""
    S, cp = 1024, 4
    even = segmentation.cp_split(S, cp, attn=1.0 / S, lin=1.0)
    slow = segmentation.cp_split(S, cp, attn=1.0 / S, lin=1.0,
                                 rates=[1.0, 1.0, 1.0, 3.0])
    assert slow[-1] < even[-1]
    assert sum(slow) == S


def test_cp_split_noncausal_is_rate_proportional():
    split = segmentation.cp_split(120, 3, attn=1.0, lin=0.0, causal=False,
                                  rates=[1.0, 2.0, 1.0])
    # rank 1 runs 2x slower: its chunk is about half the others'
    assert split[1] < split[0] and split[1] < split[2]
    assert sum(split) == 120


@given(st.integers(2, 4), st.integers(0, 2 ** 30))
@settings(max_examples=40, deadline=None)
def test_cp_split_optimal_property(cp, seed):
    rng = random.Random(seed)
    S = rng.randint(cp, 20)
    attn = rng.uniform(0.05, 1.0)
    lin = rng.uniform(0.0, 1.0)
    split = segmentation.cp_split(S, cp, attn, lin)
    got = _cp_cost(split, attn, lin)
    want = _brute_cp_bottleneck(S, cp, attn, lin)
    assert got == pytest.approx(want, rel=1e-9)


# ------------------------------------------------------- plan contract ----
def test_plan_cp_fields_validate():
    st1 = (StagePlacement(0, 2, 4, 1, True),)
    p = ParallelPlan(stages=st1, micro_bs=1, global_batch=8, seq_len=64,
                     cp=2, cp_chunks=(40, 24))
    assert p.cp_chunk_sizes == (40, 24)
    assert "cp=2" in p.describe() and "40/24" in p.describe()
    q = ParallelPlan.from_dict(p.to_dict())
    assert q == p
    # even-split fallback when chunks are unset
    p2 = ParallelPlan(stages=st1, micro_bs=1, global_batch=8, seq_len=64,
                      cp=2)
    assert p2.cp_chunk_sizes == (32, 32)
    with pytest.raises(ValueError):       # cp must divide every stage dp
        ParallelPlan(stages=(StagePlacement(0, 2, 3, 1, True),),
                     micro_bs=1, global_batch=6, seq_len=64, cp=2)
    with pytest.raises(ValueError):       # chunks must sum to seq_len
        ParallelPlan(stages=st1, micro_bs=1, global_batch=8, seq_len=64,
                     cp=2, cp_chunks=(40, 23))


def test_plan_cp_tick_algebra():
    """A cp ring collectively consumes ONE microbatch: the data-group
    width is dp/cp, so micro_batches grows x cp."""
    st1 = (StagePlacement(0, 2, 8, 1, True),)
    base = ParallelPlan(stages=st1, micro_bs=1, global_batch=64, seq_len=64)
    cp4 = ParallelPlan(stages=st1, micro_bs=1, global_batch=64, seq_len=64,
                       cp=4)
    assert cp4.micro_batches == 4 * base.micro_batches
    assert cp4.stage_micro_bs(0) == base.stage_micro_bs(0)


def test_predictor_cp1_bit_identical():
    """A cp=1 plan prices bit-for-bit like a plan with no cp fields."""
    cfg = registry.get_config("llama3-8b")
    cl = C.paper_cluster_of_size(96)
    pred = PerformancePredictor(cl, cfg)
    stages = tuple(StagePlacement(g, 16, 8, 1, i == 1)
                   for i, g in enumerate((0, 1)))
    a = ParallelPlan(stages=stages, micro_bs=1, global_batch=64,
                     seq_len=4096)
    b = ParallelPlan(stages=stages, micro_bs=1, global_batch=64,
                     seq_len=4096, cp=1)
    pa, pb = pred.predict(a), pred.predict(b)
    assert pa.iter_time == pb.iter_time
    assert pa.peak_mem_gb == pb.peak_mem_gb
    assert pa.bubble_frac == pb.bubble_frac


def test_predictor_cp_lowers_peak_memory():
    """cp is a memory/feasibility lever: per-rank activation residency
    scales with the longest chunk, at a modeled compute+ring overhead."""
    cfg = registry.get_config("llama3-8b")
    cl = C.paper_cluster_of_size(96)
    pred = PerformancePredictor(cl, cfg)
    stages = tuple(StagePlacement(g, 16, 8, 1, i == 1)
                   for i, g in enumerate((0, 1)))
    base = ParallelPlan(stages=stages, micro_bs=1, global_batch=64,
                        seq_len=4096)
    attn_f = costmodel.attention_flops_fraction(cfg, 4096)
    chunks = tuple(segmentation.cp_split(4096, 4, attn=attn_f / 4096,
                                         lin=1.0 - attn_f))
    cp4 = ParallelPlan(stages=stages, micro_bs=1, global_batch=64,
                       seq_len=4096, cp=4, cp_chunks=chunks)
    p0, p4 = pred.predict(base), pred.predict(cp4)
    assert max(p4.peak_mem_gb) < max(p0.peak_mem_gb)
    assert p4.iter_time > p0.iter_time      # cp costs hops + imbalance
    # triangle-balanced chunks lower the ring's compute bottleneck vs an
    # even split (the linear/hop terms scale with the max chunk instead,
    # so iter_time can still favour even splits — cp_scales is the
    # invariant cp_split optimizes)
    even = ParallelPlan(stages=stages, micro_bs=1, global_batch=64,
                        seq_len=4096, cp=4)
    assert pred.cp_scales(cp4)[0] <= pred.cp_scales(even)[0]


# -------------------------------------------------- cp loss vs reference ---
@pytest.fixture(scope="module")
def _bundle():
    return registry.get_bundle("llama3-8b", smoke=True, num_layers=4)


@pytest.mark.parametrize("chunks", [(48, 48), (40, 31, 25), (1, 94, 1)])
def test_cp_loss_matches_reference(_bundle, chunks):
    """make_cp_loss_fn == make_loss_fn within float tolerance, fwd+grad,
    equal and ragged splits."""
    b = _bundle
    rules = ShardingRules(b.cfg, tp=1, dp_axes=("data",))
    params = b.init(jax.random.PRNGKey(0), b.cfg)
    batch = registry.make_batch(b.cfg, batch=2, seq=sum(chunks))
    ref_loss = steps.make_loss_fn(b, rules)
    cp_loss = context.make_cp_loss_fn(b.cfg, None, chunks)
    l0, m0 = jax.jit(ref_loss)(params, batch)
    l1, m1 = jax.jit(cp_loss)(params, batch)
    assert float(jnp.abs(l0 - l1)) < 2e-5
    assert float(jnp.abs(m0["ce"] - m1["ce"])) < 2e-5
    g0 = jax.grad(lambda p: ref_loss(p, batch)[0])(params)
    g1 = jax.grad(lambda p: cp_loss(p, batch)[0])(params)
    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b_: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b_.astype(jnp.float32)))), g0, g1)))
    assert err < 2e-4, err


def test_cp_loss_rejects_unsupported(_bundle):
    import dataclasses
    with pytest.raises(ValueError, match="sliding-window"):
        context.make_cp_loss_fn(
            dataclasses.replace(_bundle.cfg, window=8), None, (16, 16))
    with pytest.raises(ValueError, match="softcap"):
        context.make_cp_loss_fn(
            dataclasses.replace(_bundle.cfg, attn_logit_softcap=30.0),
            None, (16, 16))


def test_trainer_runs_cp_plan(_bundle):
    """A pp=1 cp>1 plan routes through the cp loss builder and the losses
    track a reference (no-plan) trainer step for step."""
    mesh = make_mesh((1, 1), ("data", "model"))
    cl = C.homogeneous_cluster(C.GPU_A, 2)

    def mk(plan):
        return Trainer(
            _bundle, mesh,
            TrainerConfig(global_batch=8, seq_len=32,
                          ckpt_dir=str(Path(tempfile.mkdtemp()) / "ck"),
                          ckpt_every=100),
            cluster=cl, plan=plan, profile_store=ProfileStore())

    plan = ParallelPlan(stages=(StagePlacement(0, 4, 2, 1, True),),
                        micro_bs=8, global_batch=8, seq_len=32,
                        cp=2, cp_chunks=(20, 12))
    t_cp, t_ref = mk(plan), mk(None)
    assert t_cp._cp_active() and not t_cp._pipeline_active()
    assert not t_ref._cp_active()
    h_cp = t_cp.run(3)["losses"]
    h_ref = t_ref.run(3)["losses"]
    assert np.all(np.isfinite(h_cp))
    np.testing.assert_allclose(h_cp, h_ref, rtol=1e-4, atol=1e-4)


def test_trainer_cp1_plan_keeps_reference_step(_bundle):
    """cp=1 never enters the cp builder — the default train step runs."""
    mesh = make_mesh((1, 1), ("data", "model"))
    cl = C.homogeneous_cluster(C.GPU_A, 2)
    plan = ParallelPlan(stages=(StagePlacement(0, 4, 2, 1, True),),
                        micro_bs=8, global_batch=8, seq_len=32)
    t = Trainer(_bundle, mesh,
                TrainerConfig(global_batch=8, seq_len=32,
                              ckpt_dir=str(Path(tempfile.mkdtemp()) / "ck"),
                              ckpt_every=100),
                cluster=cl, plan=plan, profile_store=ProfileStore())
    assert not t._cp_active()


# ----------------------------------------------- planner chooses cp > 1 ----
def test_planner_picks_cp_with_unequal_chunks():
    """Long-context preset on a tp-constrained homogeneous island: the
    cp=1 winner runs m=1 (huge bubble); splitting each microbatch over a
    cp=4 ring multiplies the microbatch count and triangle-balances the
    attention, so the planner picks cp=4 with DECREASING unequal chunks
    — the acceptance preset for the cp plan dimension."""
    from repro.core import planner
    cfg = registry.get_config("llama3-8b")
    cl = C.homogeneous_cluster(C.GPU_A, 8)
    kw = dict(global_batch=8, seq_len=32768, pp_options=[2, 4],
              tp_options=(1, 2), micro_bs_options=(1,), vpp_options=(2,))
    base = planner.search(cl, cfg, **kw)
    r = planner.search(cl, cfg, cp_options=(1, 2, 4), **kw)
    assert r.plan.cp > 1
    chunks = r.plan.cp_chunk_sizes
    assert len(set(chunks)) > 1                      # genuinely unequal
    assert all(a >= b for a, b in zip(chunks, chunks[1:]))
    assert sum(chunks) == 32768
    assert r.prediction.iter_time < base.prediction.iter_time
    # identity: cp_options=(1,) reproduces the cp-less search exactly
    r1 = planner.search(cl, cfg, cp_options=(1,), **kw)
    assert r1.plan == base.plan
    assert r1.prediction.iter_time == base.prediction.iter_time
