"""One run of one cell: set-up, the measured window, the traced steps, and
the check against the plain reference.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``configs/<name>.json``: the registry arch, its overrides, the published
keys as run) under a traffic mix (``traffic/<name>.json``).  The system
runs its normal path: ``registry.get_bundle`` -> ``planner.search`` (for a
traffic mix with a ``plan``) -> ``make_train_mesh`` -> ``Trainer`` ->
``Trainer.run``.  The benchmark makes the weights and the batches from the
seed and hands them to the Trainer as its state and its feed.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
CHECKOUT = ROOT.parents[1]


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Spec:
    """What BENCHMARK.json and the cell's files say about one cell."""
    name: str
    chips: int
    cfg: dict
    traffic: dict
    limits_path: Path
    end_to_end: list
    per_layer: list
    root: Path

    @property
    def family(self) -> str:
        return self.cfg["family"]

    def module(self, kind: str, name: str):
        path = self.root / kind / f"{name}.py"
        if not path.is_file():
            raise SystemExit(f"no {kind} module {path}")
        return load_module(path)


def load_spec(name: str, checkout: Path = CHECKOUT, root: Path = ROOT):
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = json.loads((checkout / conf["file"]).read_text())
    traffic = json.loads(
        (root / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(m):
        return name in m.get("workloads", [name])

    return Spec(name, int(cell["chips"]), cfg, traffic,
                root / "limits" / f"{name}.json",
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)], root)


def require_chips(n: int):
    """The first ``n`` accelerator devices; exits when there are none."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < n:
        raise SystemExit(f"needs {n} chips; JAX found {len(devs)}")
    return devs[:n]


def peak_for(kind: str, root: Path = ROOT) -> dict:
    table = json.loads((root / "peaks.json").read_text())["kinds"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def _islands(plan_spec: dict):
    from repro.core import cluster as cluster_mod
    dev = next(d for d in vars(cluster_mod).values()
               if isinstance(d, cluster_mod.DeviceType)
               and d.name == plan_spec["device"])
    return cluster_mod.ClusterSpec(groups=tuple(
        cluster_mod.NodeGroup(dataclasses.replace(dev, name=f"{dev.name}-{i}"),
                              1, accel_per_node=plan_spec["chips_per_island"])
        for i in range(plan_spec["islands"])))


def _check_program_config(program_cfg, cfg: dict, keys: dict):
    for k, attr in keys.items():
        want, got = cfg[k], getattr(program_cfg, attr)
        if want != got:
            raise SystemExit(f"the program runs {attr}={got!r}; the "
                             f"configuration file says {k}={want!r}")


class Cell:
    """The Trainer for one cell, with the benchmark's weights and feed."""

    def __init__(self, spec: Spec, devices, seed: int, log=print):
        import jax
        import jax.numpy as jnp
        from repro.ckpt import checkpoint as ckpt
        from repro.core import planner
        from repro.launch.mesh import make_train_mesh
        from repro.models import registry
        from repro.optim.adamw import AdamWConfig
        from repro.train import steps
        from repro.train.trainer import Trainer, TrainerConfig

        import weights
        from reference.core import leaf_norms
        from traffic import Feed

        self.spec, self.seed, self.devices = spec, seed, devices
        tr = spec.traffic
        reg = spec.cfg["registry"]
        self.bundle = registry.get_bundle(reg["arch"],
                                          smoke=reg.get("smoke", False),
                                          **reg.get("overrides", {}))
        _check_program_config(self.bundle.cfg, spec.cfg,
                              spec.module("flops", spec.family).PROGRAM_KEYS)
        self.plan = cluster = self.prediction = None
        if "plan" in tr:
            p = tr["plan"]
            cluster = _islands(p)
            t0 = time.perf_counter()
            res = planner.search(cluster, self.bundle.cfg,
                                 global_batch=tr["global_batch"],
                                 seq_len=tr["seq_len"],
                                 pp_options=p["pp_options"],
                                 tp_options=p["tp_options"])
            self.plan, self.prediction = res.plan, res.prediction
            log(f"plan {self.plan.describe()} predicted "
                f"{self.prediction.iter_time!r} s/step, search "
                f"{time.perf_counter() - t0:.3f} s")
        self.mesh = make_train_mesh(self.plan, devices)
        self._ckpt_dir = tempfile.TemporaryDirectory(prefix="bench_ckpt_")
        self.trainer = Trainer(
            self.bundle, self.mesh,
            TrainerConfig(global_batch=tr["global_batch"],
                          seq_len=tr["seq_len"],
                          ckpt_dir=self._ckpt_dir.name, ckpt_every=2 ** 62),
            cluster=cluster, plan=self.plan,
            opt_cfg=AdamWConfig(**tr["optimizer"]))
        t = self.trainer
        t.data = Feed(tr, self.bundle.cfg.vocab_size, seed)

        # the benchmark's weights, in the state layout the plan runs
        self.canon = jax.eval_shape(
            lambda k: steps.init_train_state(self.bundle, k),
            jax.random.PRNGKey(0))["params"]
        layout = (ckpt.plan_layout(self.plan)
                  if self.plan is not None and self.plan.pp > 1 else None)
        init = spec.module("reference", spec.family).INIT
        opt_keys = set(t.state["opt"])
        f32 = jnp.float32

        def make_state(key):
            params = weights.make_params(key, self.canon, init)

            def zeros():
                return jax.tree.map(lambda p: jnp.zeros(p.shape, f32), params)

            opt = {"m": zeros(), "v": zeros(),
                   "count": jnp.zeros((), jnp.int32)}
            if "master" in opt_keys:
                opt["master"] = jax.tree.map(
                    lambda p: p.astype(f32),
                    jax.lax.optimization_barrier(params))
            state = {"params": params, "opt": opt,
                     "step": jnp.zeros((), jnp.int32)}
            return ckpt.migrate(state, None, layout)

        want = jax.tree.map(lambda x: (x.shape, x.dtype), t.state)
        got = jax.tree.map(lambda x: (x.shape, x.dtype),
                           jax.eval_shape(make_state, weights.seed_key(0)))
        if want != got:
            raise SystemExit("the benchmark's state does not match the "
                             "Trainer's layout")
        shardings = jax.tree.map(lambda x: x.sharding, t.state)
        self.key = weights.seed_key(seed)
        t.state = None
        gc.collect()
        with jax.set_mesh(self.mesh):
            t.state = jax.jit(make_state, out_shardings=shardings)(self.key)
        b1 = tr["optimizer"]["b1"]
        self._grad_norms = jax.jit(lambda m: {
            k: v / (1 - b1) for k, v in leaf_norms(m).items()})
        self._change_norms = jax.jit(lambda master, key: leaf_norms(
            jax.tree.map(lambda a, b: a - b.astype(f32), master,
                         make_state(key)["params"])))

    @property
    def pods(self):
        if "pod" not in self.mesh.axis_names:
            return None
        return {s: [d.id for d in self.mesh.devices[s].flat]
                for s in range(self.mesh.shape["pod"])}

    def check_steps(self) -> dict:
        """The first steps, through the window's own call and feed: the
        program's readings.  They also warm up every shape the window
        uses."""
        t = self.trainer
        losses, out = [], {}
        n = self.spec.traffic["check_steps"]
        for i in range(n):
            losses += t.run(1)["losses"]
            if i == 0:
                m = t.state["opt"]["m"]
                out["grad_norms"] = {k: float(v) for k, v in
                                     self._grad_norms(m).items()}
        master = t.state["opt"].get("master", t.state["params"])
        out["change_norms"] = {k: float(v) for k, v in
                               self._change_norms(master, self.key).items()}
        out["losses"] = losses
        return out

    def window(self, seconds: float):
        """Trainer.run(1) until ``seconds`` have passed: (steps, seconds to
        the end of the last step, losses)."""
        t, losses, n = self.trainer, [], 0
        t0 = time.perf_counter()
        while True:
            losses += t.run(1)["losses"]
            n += 1
            el = time.perf_counter() - t0
            if el >= seconds:
                return n, el, losses

    def traced(self, steps: int, trace_dir: str):
        import jax
        losses = []
        jax.profiler.start_trace(trace_dir)
        try:
            for i in range(steps):
                with jax.profiler.StepTraceAnnotation("train", step_num=i):
                    losses += self.trainer.run(1)["losses"]
        finally:
            jax.profiler.stop_trace()
        return losses

    def memory_peak(self) -> int:
        """Bytes at the peak of the fullest chip: the allocator's peak in use
        (arguments, state, outputs) plus the peak it reserved for programs'
        temporaries, which the TPU runtime keeps apart from ``in use``."""
        peaks = []
        for d in self.devices:
            st = d.memory_stats() or {}
            peaks.append(st.get("peak_bytes_in_use", 0)
                         + st.get("peak_bytes_reserved", 0))
        return max(peaks)

    def free(self):
        self.trainer = None
        self._ckpt_dir.cleanup()
        gc.collect()


def reference_readings(spec: Spec, canon, devices, seed: int,
                       precision: str = "fp32", fault=None) -> dict:
    """The plain reference (or the control, or a planted fault) from the
    same seed, on the cell's own chips."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import weights
    from reference.core import train_readings
    from traffic import Feed

    model = spec.module("reference", spec.family)
    tr = spec.traffic
    n = len(devices)
    shardings = batch_sharding = None
    if n > 1:
        mesh = Mesh(np.asarray(devices), ("r",))

        def shard(x):
            dims = [i for i, d in enumerate(x.shape) if d % n == 0]
            if not dims:
                return NamedSharding(mesh, P())
            i = max(dims, key=lambda j: x.shape[j])
            return NamedSharding(mesh, P(*[("r" if j == i else None)
                                           for j in range(len(x.shape))]))

        shardings = jax.tree.map(shard, canon)
        batch_sharding = NamedSharding(mesh, P("r", None))
    make = jax.jit(lambda k: jax.tree.map(
        lambda p: p.astype(jnp.float32),
        weights.make_params(k, canon, model.INIT)), out_shardings=shardings)
    key = weights.seed_key(seed)
    feed = Feed(tr, spec.cfg["vocab_size"], seed)
    batches = [(b["tokens"], b["labels"]) for b in
               (feed.batch_at(i) for i in range(tr["check_steps"]))]
    dtypes = jax.tree.map(lambda x: x.dtype, canon)
    return train_readings(model, spec.cfg, lambda: make(key), dtypes,
                          batches, tr["optimizer"], tr["objective"]["z_loss"],
                          precision=precision, fault=fault,
                          shardings=shardings, batch_sharding=batch_sharding)


def finite(xs) -> int:
    return sum(1 for x in xs if math.isfinite(x))


def configure_jax() -> dict:
    """Turns on the checkout's persistent compile cache for every program;
    returns counts, kept up to date, of the programs compiled (and
    written to the cache) and of those loaded from it."""
    import jax
    from repro.launch import compile_cache
    counts = {"compiled": 0, "cached": 0, "cache_dir": compile_cache.enable()}
    names = {"/jax/compilation_cache/cache_misses": "compiled",
             "/jax/compilation_cache/cache_hits": "cached"}

    def count(event, **_):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(count)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return counts


def add_paths(checkout: Path = CHECKOUT, root: Path = ROOT):
    for p in (str(root), str(checkout / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
