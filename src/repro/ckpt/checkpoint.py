"""Sharded checkpointing: atomic, async-capable, resharding-on-restore.

Layout (one directory per step):
    <dir>/step_000042/
        manifest.json          # pytree structure, shapes, dtypes, data state
        arrays/<idx>.npy       # one file per leaf (per-process slice on a
                               # real multi-host job; full leaf here)

Fault-tolerance contract:
  * atomic: written to ``step_X.tmp`` then os.rename'd — a crash mid-save
    never corrupts the latest checkpoint;
  * restartable: ``latest_step`` scans for complete manifests only;
  * reshardable: restore() takes target shardings — a post-failure replan
    with a different mesh/plan loads the same arrays and pjit re-lays them
    out (HETHUB elastic recovery, train/trainer.py);
  * migratable: ``migrate`` reshards a train state between stacked-block
    pipeline layouts (old plan -> new plan) purely in memory, so a replan
    applies without restarting the process; the Trainer also records the
    layout in the checkpoint manifest so a from-disk restore can migrate;
  * async: save_async() snapshots to host (device_get) synchronously, then
    writes on a background thread so the train loop keeps stepping.  All
    thread bookkeeping AND the keep-window GC run under one lock — GC
    scanning the directory concurrently with a newer save's rename was a
    race (it could act on a torn listing).

Invariant — ``migrate`` is bit-exact on real layers: unstacking a state to
canonical layer order and restacking it under any pipeline layout (and
back) is the identity on every real layer of params and every optimizer
moment tree; only padding slots are re-zeroed.  Chained migrations
(canonical -> A -> B -> canonical) compose to the identity too.  This is
what lets a live replan move optimizer+param state onto a new plan with
zero numeric drift — the adaptation controller's migrations are free of
training-trajectory side effects.  Locked by tests/test_replan.py
(seeded + hypothesis round-trips, e2e migrated-vs-restarted equality) and
tests/test_adapt.py (autonomous vs manual path, bit for bit).
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from pathlib import Path
from typing import Any, Dict, Optional

import jax
import numpy as np


def _leaves_with_paths(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [("/".join(str(getattr(k, "key", k)) for k in kp), leaf)
            for kp, leaf in flat]


def save(ckpt_dir: str, step: int, state: Any,
         extra: Optional[Dict] = None) -> Path:
    """Synchronous atomic save."""
    root = Path(ckpt_dir)
    final = root / f"step_{step:08d}"
    tmp = root / f"step_{step:08d}.tmp"
    if tmp.exists():
        shutil.rmtree(tmp)
    (tmp / "arrays").mkdir(parents=True)
    host_state = jax.device_get(state)
    leaves = _leaves_with_paths(host_state)
    manifest = {"step": step, "extra": extra or {}, "leaves": []}
    for i, (path, leaf) in enumerate(leaves):
        arr = np.asarray(leaf)
        np.save(tmp / "arrays" / f"{i}.npy", arr)
        manifest["leaves"].append(
            {"path": path, "file": f"{i}.npy",
             "shape": list(arr.shape), "dtype": str(arr.dtype)})
    (tmp / "manifest.json").write_text(json.dumps(manifest))
    if final.exists():
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


class AsyncCheckpointer:
    """Snapshot-on-call, write-on-thread.  One in-flight save at a time.

    Thread-safe: ``wait``/``save_async`` may race from different threads
    (the train loop, a replan, an adaptation).  The ``_thread`` swap
    and the keep-window ``_gc`` both run under ``_lock`` — the historical
    bug was a ``wait()`` returning concurrently with a fresh
    ``save_async()``: the finished thread's ``_thread = None`` clobbered
    the new registration, the next save started unsupervised, and its
    rename raced the previous ``_gc``'s directory scan
    (tests/test_replan.py locks this down)."""

    def __init__(self, ckpt_dir: str, keep: int = 3):
        self.dir = ckpt_dir
        self.keep = keep
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self.last_error: Optional[BaseException] = None

    def wait(self):
        """Block until no save is in flight; re-raise (once) a background
        save's error."""
        while True:
            with self._lock:
                t = self._thread
            if t is None:
                break
            t.join()
            with self._lock:
                if self._thread is t:   # only clear what we joined
                    self._thread = None
        with self._lock:
            err, self.last_error = self.last_error, None
        if err is not None:
            raise err

    def save_async(self, step: int, state: Any,
                   extra: Optional[Dict] = None):
        """Start a background save.  Like ``wait``, surfaces a PREVIOUS
        background save's error here (once) before starting the new one —
        a failed checkpoint must not go unnoticed until shutdown."""
        self.wait()
        host_state = jax.device_get(state)   # snapshot before mutation

        def work():
            try:
                save(self.dir, step, host_state, extra)
                with self._lock:     # gc under the same lock as completion
                    self._gc()
            except BaseException as e:  # noqa: BLE001
                with self._lock:
                    self.last_error = e

        t = threading.Thread(target=work, daemon=True)
        while True:
            with self._lock:
                if self._thread is None:
                    # register AND start under the lock: a concurrent
                    # wait() must never see (and join) an unstarted thread
                    self._thread = t
                    t.start()
                    break
            self.wait()   # lost a registration race: drain and retry

    def _gc(self):
        # caller holds self._lock
        steps = sorted(all_steps(self.dir))
        for s in steps[:-self.keep]:
            shutil.rmtree(Path(self.dir) / f"step_{s:08d}",
                          ignore_errors=True)


def all_steps(ckpt_dir: str):
    root = Path(ckpt_dir)
    if not root.exists():
        return []
    out = []
    for p in root.iterdir():
        if p.name.startswith("step_") and not p.name.endswith(".tmp") \
                and (p / "manifest.json").exists():
            out.append(int(p.name.split("_")[1]))
    return sorted(out)


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = all_steps(ckpt_dir)
    return steps[-1] if steps else None


def manifest_extra(ckpt_dir: str, step: int) -> Dict:
    """The ``extra`` dict a checkpoint was saved with (manifest-only read —
    no arrays touched).  The Trainer stores the state's pipeline layout
    here so a restore onto a different plan knows what to migrate from."""
    d = Path(ckpt_dir) / f"step_{step:08d}"
    return json.loads((d / "manifest.json").read_text()).get("extra", {})


# ------------------------------------------------------- plan migration ----
def plan_layout(plan) -> Optional[Dict[str, Any]]:
    """A ParallelPlan's stacked-block layout as a JSON-able dict (what the
    Trainer stamps into checkpoint manifests); None = the canonical
    unstacked (L, ...) layout of a non-pipeline state.  ``stage_tp``
    records each stage's tensor-parallel width: state arrays are stored
    as full (unsharded) leaves, so a tp-width change never moves layer
    CONTENT — but the layout must still record it so a migration across
    an asymmetric-tp replan re-places the state under the new plan's
    shardings rather than silently treating the layouts as equal."""
    if plan is None:
        return None
    return {"pp": plan.pp, "vpp": plan.vpp,
            "virtual_layers": list(plan.virtual_layers),
            "stage_tp": [s.tp for s in plan.stages]}


def _norm_layout(layout) -> Optional[Dict[str, Any]]:
    if layout is None:
        return None
    if isinstance(layout, dict):
        pp = int(layout["pp"])
        if "stage_tp" not in layout:
            # manifests predating per-stage tp carry no stage_tp KEY:
            # default to width 1 everywhere (the restack migrate runs on
            # real layers is the identity, so the compat default is safe,
            # never lossy)
            tps = [1] * pp
        else:
            # a PRESENT stage_tp is a post-PR-7 manifest and must be
            # well-formed: an empty or wrong-length list is corruption,
            # not legacy — silently defaulting it would migrate state
            # under the wrong tp widths
            tps = layout["stage_tp"]
            try:
                ok = (isinstance(tps, (list, tuple)) and len(tps) == pp
                      and all(int(x) >= 1 for x in tps))
            except (TypeError, ValueError):
                ok = False
            if not ok:
                raise ValueError(
                    f"malformed stage_tp {tps!r} in layout (pp={pp}): "
                    f"expected {pp} widths >= 1, or no stage_tp key at "
                    f"all for a pre-stage_tp legacy manifest")
        return {"pp": pp, "vpp": int(layout["vpp"]),
                "virtual_layers": [int(x) for x in layout["virtual_layers"]],
                "stage_tp": [int(x) for x in tps]}
    return plan_layout(layout)   # a ParallelPlan (duck-typed)


def _unstack_blocks(tree: Dict[str, Any], layout: Dict[str, Any]
                    ) -> Dict[str, Any]:
    """(pp, [vpp,] Lmax, ...) stacked blocks -> canonical (L, ...) order.
    Virtual-stage order IS model-layer order (contiguous chunks, chunk c
    of stage s at slot [s, c]); padded rows are dropped."""
    import jax.numpy as jnp
    pp, vpp = layout["pp"], layout["vpp"]
    vl = layout["virtual_layers"]

    def un(a):
        pieces = []
        for vs, ls in enumerate(vl):
            s, c = vs % pp, vs // pp
            pieces.append(a[s, c, :ls] if vpp > 1 else a[s, :ls])
        return jnp.concatenate(pieces, axis=0)

    out = dict(tree)
    out["blocks"] = jax.tree.map(un, tree["blocks"])
    return out


def migrate(state: Any, old_plan, new_plan) -> Any:
    """Reshard a train state across a plan change — the live half of the
    HETHUB replan loop (train/trainer.py drives it; restart-free).

    ``old_plan``/``new_plan`` are ParallelPlans, layout dicts (as stored
    by ``plan_layout`` in checkpoint manifests), or None (canonical
    unstacked layout).  Params and every optimizer moment tree (m, v,
    master) move from the old stage/chunk assignment to the new one:
    unstack to canonical layer order, restack per the new plan's
    ``virtual_layers``.  Real layers are carried over bit-exactly (pure
    gathers/concats); padding rows are re-created as zeros, matching a
    fresh stacked init.  tp-width-changing layouts (asymmetric per-stage
    tp replans) migrate the same way: leaves are full arrays, so width
    only changes the target shardings the Trainer re-places under — the
    content round-trip stays bit-exact (tests/test_replan.py).  Works on host numpy and device arrays alike and
    is traceable (jax.eval_shape uses it to derive layout shapes)."""
    old = _norm_layout(old_plan)
    new = _norm_layout(new_plan)
    if old == new:
        return state
    from repro.parallel import pipeline

    def tr(tree):
        if old is not None:
            tree = _unstack_blocks(tree, old)
        if new is not None:
            tree = pipeline.stack_blocks_for_stages(
                tree, new["pp"], new["virtual_layers"], vpp=new["vpp"])
        return tree

    out = dict(state)
    out["params"] = tr(state["params"])
    opt = dict(state["opt"])
    for k in ("m", "v", "master"):
        if k in opt:
            opt[k] = tr(opt[k])
    out["opt"] = opt
    return out


def restore(ckpt_dir: str, step: int, target: Any,
            shardings: Optional[Any] = None):
    """Restore into the structure of ``target`` (a pytree of arrays or
    ShapeDtypeStructs).  With ``shardings`` (pytree of NamedSharding) the
    leaves are placed directly into the (possibly NEW, post-replan) layout.
    Returns (state, extra)."""
    d = Path(ckpt_dir) / f"step_{step:08d}"
    manifest = json.loads((d / "manifest.json").read_text())
    by_path = {l["path"]: l for l in manifest["leaves"]}
    flat = jax.tree_util.tree_flatten_with_path(target)
    leaves = []
    shard_flat = (jax.tree_util.tree_flatten(shardings)[0]
                  if shardings is not None else None)
    for i, (kp, leaf) in enumerate(flat[0]):
        path = "/".join(str(getattr(k, "key", k)) for k in kp)
        ent = by_path[path]
        arr = np.load(d / "arrays" / ent["file"])
        want_shape = tuple(leaf.shape)
        if tuple(arr.shape) != want_shape:
            raise ValueError(f"shape mismatch at {path}: "
                             f"{arr.shape} vs {want_shape}")
        if shard_flat is not None:
            arr = jax.device_put(arr, shard_flat[i])
        leaves.append(arr)
    state = jax.tree_util.tree_unflatten(flat[1], leaves)
    return state, manifest.get("extra", {})
