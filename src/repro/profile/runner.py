"""Microbenchmark harness: measure what the predictor otherwise derives.

Times three families of work on whatever devices JAX exposes (a forced
host-platform device farm when run as a CLI on CPU, real TPU/GPU devices
when available) and writes the results into a ProfileStore:

  * kernels   — rmsnorm / swiglu / flash_attention via repro.kernels.ops,
                fwd and fwd+bwd, jit + block_until_ready, warmup + trimmed
                mean;
  * layers    — full model loss fwd and fwd+bwd at two depths (pattern
                length a and 2a); per-layer time is the difference, the
                paper's 'profile small, predict big' probe applied to wall
                time.  Swept over (seq_len, micro_bs, tp);
  * collectives — psum / all-gather / ppermute through the ICCL
                ``Communicator`` inside shard_map, several payload sizes;
                effective Gb/s summarised into 'link' entries.

Usage:
    python -m repro.profile.runner --quick           # CI smoke sweep
    python -m repro.profile.runner --arch llama3-8b  # full sweep
"""
from __future__ import annotations

import os

if __name__ == "__main__":  # pragma: no cover — CLI path
    # A small device farm for collective benchmarks on hosts without
    # accelerators.  MUST precede any jax import (device count locks on
    # first init); importing this module from tests has no side effects.
    _n = os.environ.get("REPRO_PROFILE_DEVICES", "8")
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={_n}")

import argparse
import statistics
import time
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.iccl.communicator import Communicator
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.parallel.sharding import ShardingRules
from repro.profile.store import ProfileStore
from repro.train import steps


# ----------------------------------------------------------------- timing --
def timeit(fn: Callable[[], object], warmup: int = 2, reps: int = 5,
           trim: float = 0.2) -> Tuple[float, float]:
    """(trimmed-mean, stdev) of fn's wall time; blocks on the result."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts: List[float] = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    k = int(len(ts) * trim)
    core = ts[k:len(ts) - k] or ts
    mean = sum(core) / len(core)
    std = statistics.pstdev(core) if len(core) > 1 else 0.0
    return mean, std


def device_kind() -> str:
    d = jax.devices()[0]
    kind = getattr(d, "device_kind", "") or d.platform
    return kind.strip().lower().replace(" ", "-")


# ---------------------------------------------------------------- kernels --
def bench_kernels(store: ProfileStore, dev: str, seqs: Sequence[int],
                  micro_bss: Sequence[int], d_model: int = 256,
                  warmup: int = 2, reps: int = 5, verbose: bool = True):
    from repro.kernels import ops
    key = jax.random.PRNGKey(0)
    n_heads, hd = 4, d_model // 4
    for seq in seqs:
        for mbs in micro_bss:
            shape = {"seq_len": seq, "micro_bs": mbs, "d_model": d_model}
            x = jax.random.normal(key, (mbs, seq, d_model), jnp.float32)
            scale = jnp.ones((d_model,), jnp.float32)
            qkv = jax.random.normal(key, (mbs, seq, n_heads, hd),
                                    jnp.float32)
            cases: Dict[str, Tuple[Callable, tuple]] = {
                "rmsnorm": (ops.rmsnorm, (x, scale)),
                "swiglu": (ops.swiglu, (x, x)),
                "flash_attention": (ops.flash_attention, (qkv, qkv, qkv)),
            }
            for name, (fn, args) in cases.items():
                t_fwd, s_fwd = timeit(lambda: fn(*args), warmup, reps)
                grad = jax.jit(jax.grad(
                    lambda *a: jnp.sum(fn(*a).astype(jnp.float32))))
                t_fb, s_fb = timeit(lambda: grad(*args), warmup, reps)
                store.put(dev, f"kernel_{name}", shape,
                          {"fwd_s": t_fwd, "fwd_std": s_fwd,
                           "fwdbwd_s": t_fb, "fwdbwd_std": s_fb})
                if verbose:
                    print(f"  kernel {name:16s} seq={seq:5d} mbs={mbs} "
                          f"fwd={t_fwd*1e3:8.3f}ms fwd+bwd={t_fb*1e3:8.3f}ms")


# ----------------------------------------------------------------- layers --
def _loss_fns(arch: str, n_layers: int, tp: int):
    b = registry.get_bundle(arch, smoke=True, num_layers=n_layers,
                            scan_layers=False)
    rules = ShardingRules(b.cfg, tp=tp, dp_axes=("data",))
    params = b.init(jax.random.PRNGKey(0), b.cfg)
    loss = steps.make_loss_fn(b, rules)
    fwd = jax.jit(lambda p, bt: loss(p, bt)[0])
    step = jax.jit(jax.grad(lambda p, bt: loss(p, bt)[0]))
    return b.cfg, params, fwd, step


def bench_layers(store: ProfileStore, dev: str, arch: str,
                 seqs: Sequence[int], micro_bss: Sequence[int], tp: int = 1,
                 warmup: int = 2, reps: int = 5, verbose: bool = True):
    """Per-layer fwd/bwd wall time from two depth probes (a vs 2a)."""
    cfg0 = registry.get_config(arch, smoke=True)
    a = len(cfg0.block_pattern) if cfg0.block_pattern else 1
    probes = {}
    for L in (a, 2 * a):
        probes[L] = _loss_fns(arch, L, tp)
    for seq in seqs:
        for mbs in micro_bss:
            per = {}
            for L, (cfg, params, fwd, step) in probes.items():
                batch = registry.make_batch(cfg, batch=mbs, seq=seq)
                t_f, _ = timeit(lambda: fwd(params, batch), warmup, reps)
                t_s, _ = timeit(lambda: step(params, batch), warmup, reps)
                per[L] = (t_f, t_s)
                store.put(dev, "loss_probe",
                          {"arch": arch, "seq_len": seq,
                           "micro_bs": mbs, "tp": tp, "n_layers": L},
                          {"fwd_s": t_f, "step_s": t_s})
            fwd_layer = max((per[2 * a][0] - per[a][0]) / a, 1e-9)
            step_layer = max((per[2 * a][1] - per[a][1]) / a, fwd_layer)
            store.put(dev, "layer_step",
                      {"arch": arch, "seq_len": seq, "micro_bs": mbs,
                       "tp": tp},
                      {"fwd_s": fwd_layer, "bwd_s": step_layer - fwd_layer})
            if verbose:
                print(f"  layer  {arch:16s} seq={seq:5d} mbs={mbs} "
                      f"fwd/layer={fwd_layer*1e3:8.3f}ms "
                      f"bwd/layer={(step_layer-fwd_layer)*1e3:8.3f}ms")


# ------------------------------------------------------------ collectives --
def bench_collectives(store: ProfileStore, dev: str,
                      payload_bytes: Sequence[int],
                      warmup: int = 2, reps: int = 5, verbose: bool = True):
    n = len(jax.devices())
    if n < 2:
        if verbose:
            print("  collectives: single device — skipped")
        return
    mesh = make_mesh((n,), ("x",))
    comm = Communicator(axis="x")

    def shard_fn(body):
        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("x"),),
                                     out_specs=P("x"), check_vma=False))

    perm = [(i, (i + 1) % n) for i in range(n)]
    cases = {
        "psum": (shard_fn(comm.iallreduce),
                 lambda nb: 2.0 * (n - 1) / n * nb),        # ring wire bytes
        "all_gather": (shard_fn(lambda y: comm.iallgather(y, axis=0)),
                       lambda nb: (n - 1) * nb),   # receives n-1 shards
        "ppermute": (shard_fn(lambda y: comm.isend_irecv(y, perm)),
                     lambda nb: float(nb)),
    }
    link_gbps = None
    for nbytes in payload_bytes:
        n_f32 = max(nbytes // 4 // n * n, n)
        x = jnp.ones((n_f32,), jnp.float32)
        shard_bytes = x.nbytes / n
        for name, (fn, wire) in cases.items():
            t, s = timeit(lambda: fn(x), warmup, reps)
            gbps = wire(shard_bytes) * 8.0 / t / 1e9
            store.put(dev, f"collective_{name}",
                      {"nbytes": shard_bytes, "n_dev": n},
                      {"time_s": t, "std": s, "gbps": gbps})
            if name == "ppermute":
                link_gbps = gbps   # largest payload wins (last iteration)
            if verbose:
                print(f"  coll   {name:12s} shard={shard_bytes/1e6:7.3f}MB "
                      f"n={n} t={t*1e3:8.3f}ms eff={gbps:8.2f}Gb/s")
    if link_gbps is not None:
        # measured intra-island p2p bandwidth -> the predictor's link model
        store.put(dev, "link", {"scope": "intra"}, {"gbps": link_gbps})
        # the context-parallel ring hop IS a collective-permute: the same
        # measurement serves ProfiledCostModel.ring_hop_gbps
        store.put(dev, "ring_hop", {"scope": "intra"}, {"gbps": link_gbps})


# -------------------------------------------------------------------- cli --
def run(arch: str = "llama3-8b", quick: bool = False, out: str = None,
        tp_options: Sequence[int] = (1,), verbose: bool = True
        ) -> ProfileStore:
    dev = device_kind()
    store = (ProfileStore.open(out) if out
             else ProfileStore.for_device(dev))
    if quick:
        seqs, mbss, payloads = (64, 128), (1, 2), (1 << 20,)
        warmup, reps = 1, 3
    else:
        seqs, mbss = (128, 256, 512), (1, 2, 4)
        payloads = (1 << 20, 8 << 20, 64 << 20)
        warmup, reps = 2, 7
    if verbose:
        print(f"[profile] device_kind={dev} n_dev={len(jax.devices())} "
              f"backend={jax.default_backend()} -> {store.path}")
    bench_kernels(store, dev, seqs, mbss, warmup=warmup, reps=reps,
                  verbose=verbose)
    for tp in tp_options:
        bench_layers(store, dev, arch, seqs, mbss, tp=tp, warmup=warmup,
                     reps=reps, verbose=verbose)
    bench_collectives(store, dev, payloads, warmup=warmup, reps=reps,
                      verbose=verbose)
    path = store.save()
    if verbose:
        print(f"[profile] {len(store)} entries -> {path}")
    return store


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--quick", action="store_true",
                    help="tiny sweep for CI (seconds, not minutes)")
    ap.add_argument("--out", default=None,
                    help="profile path (default: per-device-kind file under "
                         "benchmarks/artifacts/profiles/)")
    ap.add_argument("--tp", type=int, nargs="*", default=[1])
    args = ap.parse_args(argv)
    if args.arch not in registry.ARCH_IDS:
        ap.error(f"unknown --arch {args.arch!r}; "
                 f"choose from {', '.join(registry.ARCH_IDS)}")
    run(arch=args.arch, quick=args.quick, out=args.out,
        tp_options=args.tp)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
