"""Training loop with the HETHUB control plane wrapped around it:

  * periodic async checkpointing (atomic, resharding-on-restore);
  * crash/restart recovery: resume from the latest complete checkpoint,
    data pipeline state included — migrating the state's pipeline layout
    when the checkpoint was written under a different plan;
  * pipeline execution: given a ParallelPlan with pp > 1 the trainer runs
    the plan's own SPMD pipeline step (repro.parallel.pipeline) with the
    plan's stage/chunk layer assignment and schedule-matched telemetry;
  * online stage telemetry (repro.telemetry): per-stage/per-tick compute
    and per-schedule bubble observations folded into the profile store as
    ``observed_stage_tick`` / ``observed_bubble`` entries — the closed
    loop the paper's predictor+planner need to track reality;
  * elastic scaling / node failure: ``replan(new_cluster)`` re-runs the
    automatic parallel planner on the surviving cluster — against the
    online profile once dense enough, with degradation-scaled observed
    times and the incumbent plan as the search baseline — then LIVE
    MIGRATES the optimizer+param state onto the new plan's stage/chunk
    assignment (in-memory reshard; checkpoint round-trip fallback);
  * autonomous adaptation: given a ``repro.adapt.ReplanPolicy`` the
    trainer consults it every telemetry step and invokes
    ``degrade``+``replan``+migrate ITSELF — no operator in the loop —
    recording every decision in ``adapt_log`` (structured AdaptEvents;
    docs/adaptation.md).  A ``repro.adapt`` aggregator gathers every
    process's telemetry folds into one per-island profile before the
    policy evaluates, and makes the DECISION cluster-symmetric: the
    leader process (aggregator.is_leader) evaluates policy + search on
    the gathered view and broadcasts the resulting directive, so every
    process enters the collective adoption together or not at all —
    per-process policy state never gates a collective.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import planner as planner_mod
from repro.core.cluster import ClusterSpec
from repro.core.plan import ParallelPlan
from repro.ckpt import checkpoint as ckpt
from repro.data.pipeline import DataState, SyntheticTokens
from repro.models.registry import ArchBundle
from repro.obs import counters, scopes
from repro.optim.adamw import AdamWConfig
from repro.parallel import context, pipeline
from repro.parallel.sharding import ShardingRules
from repro.telemetry import StageTelemetry
from repro.train import steps as steps_mod


@dataclasses.dataclass
class TrainerConfig:
    global_batch: int = 8
    seq_len: int = 64
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 10
    tp: int = 1
    # replan uses the accumulating online profile as the planner's cost
    # source once it holds at least this many folded layer-time
    # observations (density threshold: a couple of steps is noise, not a
    # profile)
    replan_profile_min_obs: float = 8.0
    # with a policy + aggregator attached, gather the cluster-wide
    # telemetry view — and run the adaptation decision + its broadcast —
    # every this many steps.  Both happen at a step-synchronized point of
    # run() — EVERY process executes them at the same step — because a
    # collective (process_allgather, the directive broadcast) invoked
    # from a data-dependent branch would deadlock processes whose local
    # policy state diverged.  Raise it when per-step collectives are too
    # chatty for the fabric.
    aggregate_every: int = 1
    # stage telemetry mode for the pipeline step: "auto" picks per-tick
    # host callbacks on a single-device CPU mesh (ordered callbacks run on
    # one device only) and cheap step-bucketed timers elsewhere; "off"
    # disables recording entirely
    telemetry: str = "auto"
    # bounded staleness for profile entries of DEPARTED device kinds: a
    # lost island's measurements are kept this many steps (a flapping
    # node that rejoins inside the window gets its warm profile back —
    # no re-baseline, no planner thrash), then dropped from planning
    profile_stale_steps: int = 200


@dataclasses.dataclass(frozen=True)
class _AdoptedPlan:
    """Minimal ``_adopt`` argument for a plan that arrived through a
    broadcast adaptation directive rather than a local PlannerResult."""
    plan: ParallelPlan


class Trainer:
    def __init__(self, bundle: ArchBundle, mesh, cfg: TrainerConfig,
                 cluster: Optional[ClusterSpec] = None,
                 plan: Optional[ParallelPlan] = None,
                 opt_cfg: Optional[AdamWConfig] = None,
                 profile_store=None, policy=None, aggregator=None,
                 adapt_search_kw: Optional[Dict[str, Any]] = None,
                 obs=None):
        self.bundle = bundle
        self.mesh = mesh
        self.cfg = cfg
        self.cluster = cluster
        self.plan = plan
        # observability (repro.obs.Observability): None (the default)
        # leaves every hot path exactly as before — the telemetry sink
        # stays unbound, no collective sink installs, and the run loop
        # skips its per-step emission branch
        self.obs = obs
        if obs is not None:
            obs.install_iccl()
        self.profile_store = profile_store   # repro.profile.ProfileStore
        # autonomous adaptation: policy (repro.adapt.ReplanPolicy) decides
        # when to replan; aggregator (repro.adapt aggregators) folds every
        # process's telemetry into one cluster view first; adapt_search_kw
        # constrains the controller's searches (pp/tp options etc.)
        self.policy = policy
        self.aggregator = aggregator
        self.adapt_search_kw = dict(adapt_search_kw or {})
        self.adapt_log: list = []        # structured AdaptEvents
        self._adapt_seen = 0             # telemetry steps already shown
        # elastic membership: queued node-lost/node-joined events (the
        # leader turns them into broadcast directives at the next cadence
        # point), the healthy spec of each departed island (a rejoin by
        # kind restores it), and the last leadership answer (a False->True
        # flip is a re-election worth logging)
        self._membership_pending: list = []
        self._departed_groups: Dict[str, Any] = {}
        self._was_leader: Optional[bool] = None
        self._inject_scale: Dict[str, float] = {}
        self._inject_bubble = 1.0        # observed-bubble injection factor
        self._cluster_view = None        # cached aggregator.gather result
        self._store_tick_state = None    # (n, n·mean) sums per stage at
        #                                  the last policy look (delta
        #                                  basis for _store_stage_ticks)
        self._pred_bubble = None         # (plan, cluster, bubble) cache
        # the HEALTHY reference per device kind: telemetry folds are
        # tagged with their slowdown relative to it (obs_scale) and replan
        # cost sources project target degradations against it — never
        # against the already-degraded incumbent (which would double-count
        # slowdowns the observations contain)
        self._ref_tflops: Dict[str, float] = (
            {g.device.name: g.device.effective_tflops
             for g in cluster.groups} if cluster is not None else {})
        self.opt_cfg = opt_cfg or AdamWConfig()
        self.rules = ShardingRules(bundle.cfg, tp=cfg.tp,
                                   dp_axes=("data",))
        self.data = SyntheticTokens(
            vocab_size=bundle.cfg.vocab_size, seq_len=cfg.seq_len,
            global_batch=cfg.global_batch, family=bundle.cfg.family,
            d_model=bundle.cfg.d_model,
            n_vision_tokens=bundle.cfg.n_vision_tokens)
        self.ckpt = ckpt.AsyncCheckpointer(cfg.ckpt_dir)
        self.telemetry: Optional[StageTelemetry] = None
        self.replans = 0
        self.migrations = {"memory": 0, "checkpoint": 0}
        self._build()
        self._init_or_restore()

    # ------------------------------------------------------------ build ---
    def _pipeline_active(self) -> bool:
        """The trainer EXECUTES its plan (SPMD pipeline step, stacked
        state) only when the plan describes this trainer's own workload —
        same global batch and sequence length, microbatches dividing the
        batch.  A plan searched for some other workload shape (e.g. a
        capacity study) stays advisory, as before."""
        plan = self.plan
        return (plan is not None and plan.pp > 1
                and plan.global_batch == self.cfg.global_batch
                and plan.seq_len == self.cfg.seq_len
                and self.cfg.global_batch % plan.tokens_per_tick == 0)

    def _cp_active(self) -> bool:
        """A pp == 1, cp > 1 plan matching this trainer's workload runs
        the SPMD ring-attention loss (repro.parallel.context) in place of
        the reference loss.  pp > 1 plans keep the pipeline step whatever
        their cp: on single-host test meshes the sequence axis runs
        monolithic inside each stage and the plan's cp stays advisory —
        the predictor still prices it, ``schedule_health`` still compares
        against it.  Models outside the cp builder's scope (hybrid
        stacks, SWA, MoE) also stay on the reference loss."""
        plan = self.plan
        if (plan is None or plan.pp != 1 or plan.cp <= 1
                or plan.global_batch != self.cfg.global_batch
                or plan.seq_len != self.cfg.seq_len):
            return False
        try:
            context.check_cp_supported(self.bundle.cfg)
        except ValueError:
            return False
        return True

    def _build(self):
        # the next step is the first of this program: it compiles, and it
        # notes the program for the profile's scope table
        self._first_step = True
        if self._pipeline_active():
            plan = self.plan
            m = plan.micro_batches
            mode = self.cfg.telemetry
            if mode == "auto":
                mode = ("callback" if jax.default_backend() == "cpu"
                        and self.mesh.size == 1 else "timer")
            self.telemetry = (StageTelemetry(plan.pp, plan.vpp, m, mode=mode)
                              if mode != "off" else None)
            if self.obs is not None and self.telemetry is not None:
                # the observed-lane tap rides the recorder's existing
                # host endpoint — no additional callbacks in the step
                self.telemetry.sink = self.obs.make_telemetry_sink(
                    plan, self._stage_kinds(), self.telemetry.mode,
                    scales_fn=self._stage_scales)
            # only callback mode wires tick marks into the step — timer
            # mode must keep host callbacks off the hot path entirely
            loss_fn = pipeline.make_pp_loss_fn(
                self.bundle.cfg, self.mesh, plan.pp, m,
                layers_per_stage=list(plan.virtual_layers), vpp=plan.vpp,
                telemetry=(self.telemetry if mode == "callback" else None),
                stage_tp=list(plan.tps))
            self.train_step = steps_mod.make_train_step(
                self.bundle, self.rules, self.opt_cfg, loss_fn=loss_fn)
        elif self._cp_active():
            # cp ring execution: same state layout and train step as the
            # reference path, only the loss is the pod-axis ring program
            self.telemetry = None
            loss_fn = context.make_cp_loss_fn(
                self.bundle.cfg, self.mesh, self.plan.cp_chunk_sizes)
            self.train_step = steps_mod.make_train_step(
                self.bundle, self.rules, self.opt_cfg, loss_fn=loss_fn)
        else:
            self.telemetry = None
            self.train_step = steps_mod.make_train_step(
                self.bundle, self.rules, self.opt_cfg)
        # the state keeps its layout across steps: left to propagation,
        # the ZeRO-1 moments' data sharding spreads onto the params, and
        # the second step recompiles for the new input layout
        self._jit = jax.jit(
            self.train_step, donate_argnums=0,
            out_shardings=(self._state_shardings(self._state_sds()),
                           NamedSharding(self.mesh, P())))
        if self.obs is not None and self._pipeline_active() \
                and self.cluster is not None:
            # a (re)build IS a plan adoption: render a fresh predicted
            # lane anchored here and stamp a plan record in the metrics
            self.obs.on_plan_adopted(getattr(self, "step", 0), self.plan,
                                     self.cluster, self.bundle.cfg,
                                     self._stage_kinds())

    # -------------------------------------------------- state & layouts ---
    def _state_layout(self) -> Optional[Dict[str, Any]]:
        """The pipeline layout the CURRENT plan stacks the state into
        (None = canonical unstacked)."""
        return (ckpt.plan_layout(self.plan) if self._pipeline_active()
                else None)

    def _init_state(self, key, layout=None):
        state = steps_mod.init_train_state(self.bundle, key)
        layout = layout if layout is not None else self._state_layout()
        if layout is not None:
            state = ckpt.migrate(state, None, layout)
        return state

    def _state_sds(self, layout=None):
        return jax.eval_shape(
            lambda k: self._init_state(k, layout), jax.random.PRNGKey(0))

    def _state_shardings(self, state_sds):
        if self._pipeline_active() and \
                "pod" in getattr(self.mesh, "axis_names", ()):
            data_size = self.mesh.shape.get("data", 1)
            p_specs = pipeline.pp_param_specs(
                self.rules.param_specs(state_sds["params"]))
            opt_specs: Dict[str, Any] = {"count": P()}
            for k in ("m", "v", "master"):
                if k in state_sds["opt"]:
                    opt_specs[k] = jax.tree.map(
                        lambda sp, sh: self.rules.opt_state_spec(
                            sp, sh.shape, data_size),
                        p_specs, state_sds["opt"][k])
            specs = {"params": p_specs, "opt": opt_specs, "step": P()}
        else:
            specs = steps_mod.state_specs(
                self.bundle, self.rules, state_sds,
                data_size=self.mesh.shape.get("data", 1))
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs)

    def _place(self, host_state, shardings):
        return jax.tree.map(jax.device_put, host_state, shardings)

    def _init_or_restore(self):
        step = ckpt.latest_step(self.cfg.ckpt_dir)
        key = jax.random.PRNGKey(0)
        layout = self._state_layout()
        state_sds = self._state_sds(layout)
        shardings = self._state_shardings(state_sds)
        if step is None:
            with jax.set_mesh(self.mesh):
                self.state = jax.jit(
                    lambda k: self._init_state(k, layout),
                    out_shardings=shardings)(key)
            self.step = 0
            return
        extra = ckpt.manifest_extra(self.cfg.ckpt_dir, step)
        stored = extra.get("layout")
        if ckpt._norm_layout(stored) == ckpt._norm_layout(layout):
            self.state, extra = ckpt.restore(
                self.cfg.ckpt_dir, step, state_sds, shardings)
        else:
            # checkpoint written under a different plan: restore into the
            # STORED layout's shapes, migrate, then lay out per the
            # current plan (HETHUB elastic recovery)
            state, extra = ckpt.restore(
                self.cfg.ckpt_dir, step, self._state_sds(stored))
            state = ckpt.migrate(state, stored, layout)
            self.state = self._place(state, shardings)
            self.migrations["checkpoint"] += 1
        self.data.state = DataState.from_dict(extra["data"])
        self.step = step

    # ------------------------------------------------------------- run ----
    def _device_batch(self, np_batch):
        pp_m = self.plan.micro_batches if self._pipeline_active() else None

        def put(k, v):
            if v.dtype == np.float32 and k in ("frames", "image_embeds"):
                v = v.astype(self.bundle.cfg.adtype)
            spec = (self.rules.batch_spec() if v.ndim == 2
                    else P(self.rules.dp_axes, None, None))
            if pp_m is not None:
                # the pipeline consumes pre-microbatched (m, B_tick, ...)
                v = v.reshape(pp_m, v.shape[0] // pp_m, *v.shape[1:])
                spec = P(None, *tuple(spec))
            return jax.device_put(v, NamedSharding(self.mesh, spec))

        return {k: put(k, v) for k, v in np_batch.items()}

    def run(self, n_steps: int) -> Dict[str, Any]:
        try:
            return self._run(n_steps)
        except Exception as e:
            # a wedged schedule (planner/simulator ScheduleError) is the
            # flight recorder's primary customer: dump the last few
            # hundred controller decisions next to the stack trace
            from repro.core.simulator import ScheduleError
            if self.obs is not None and isinstance(e, ScheduleError):
                self.obs.flight_dump("schedule-error")
            raise

    def _run(self, n_steps: int) -> Dict[str, Any]:
        """Each phase of a step runs in a ``trainer.*`` profiler span, on
        the clock of the device ops in a ``jax.profiler`` trace."""
        losses = []
        for _ in range(n_steps):
            t0 = time.perf_counter()
            with TraceAnnotation("trainer.batch"):
                np_batch = self.data.batch_at(self.step)
            with TraceAnnotation("trainer.put"):
                batch = self._device_batch(np_batch)
            input_s = time.perf_counter() - t0
            if self._first_step:
                scopes.note_program("train_step", self._jit,
                                    scopes.abstract((self.state, batch)),
                                    self.mesh)
            with TraceAnnotation("trainer.dispatch"), \
                    jax.set_mesh(self.mesh):
                self.state, metrics = self._jit(self.state, batch)
            with TraceAnnotation("trainer.sync"):
                jax.block_until_ready(metrics["loss"])
                dt = time.perf_counter() - t0
                host = jax.device_get(
                    {k: metrics[k] for k in ("loss",) + counters.NAMES
                     if k in metrics})
                losses.append(float(host["loss"]))
            counters.record(self.bundle.cfg, host)
            with TraceAnnotation("trainer.after"):
                self._after_step(dt, input_s)
        self.ckpt.wait()
        if self.profile_store is not None and self.profile_store.path:
            self.profile_store.save()
        return {"losses": losses, "step": self.step}

    def _after_step(self, dt: float, input_s: float) -> None:
        """Profile fold, adaptation, observability and checkpoint of the
        step just synced."""
        self.step += 1
        self.data.state.step = self.step
        if self.profile_store is not None:
            self._refine_profile(dt)
            # bounded staleness ticks with or without a controller
            # attached: a departed kind expires on schedule even when
            # no policy/aggregator drives _maybe_adapt
            self._expire_stale_profiles()
        self._first_step = False
        # --- autonomous adaptation (repro.adapt closed loop) ---
        # membership events ride the same machinery with or without a
        # policy: a node loss is a topology FACT, not a policy call,
        # so the controller runs whenever there is a policy, an
        # aggregator (followers must enter every broadcast), or a
        # queued membership event
        if self.policy is not None or self.aggregator is not None \
                or self._membership_pending:
            # BOTH collectives of the loop — the telemetry gather and
            # the decision broadcast inside _maybe_adapt — run HERE,
            # unconditionally on a step cadence: self.step is
            # identical across SPMD processes, so every process
            # enters them together (policy/telemetry state may
            # diverge per process and must never gate a collective)
            on_cadence = (self.step
                          % max(1, self.cfg.aggregate_every) == 0)
            if self.policy is not None and self.aggregator is not None \
                    and self.profile_store is not None and on_cadence:
                self._cluster_view = self.aggregator.gather(
                    self.profile_store)
            if on_cadence or \
                    not getattr(self.aggregator, "collective", False):
                self._maybe_adapt()
        # --- observability (repro.obs; default None = untouched) ---
        if self.obs is not None:
            self.obs.on_step(self.step, dt, self.schedule_health(),
                             input_s=input_s)
        if self.step % self.cfg.ckpt_every == 0:
            self.ckpt.save_async(self.step, self.state,
                                 extra=self._ckpt_extra())

    def _ckpt_extra(self) -> Dict[str, Any]:
        return {"data": self.data.state.to_dict(),
                "layout": self._state_layout()}

    # ------------------------------------- online profile refinement ------
    def _refine_profile(self, dt: float):
        """Fold one observed step wall-time into the profile (running mean
        keyed by the exact workload shape), plus a per-layer estimate the
        ProfiledCostModel can interpolate.  The first step after a (re)build
        is excluded: it pays jit compilation, not steady-state time."""
        if self._first_step:
            return
        from repro.profile.runner import device_kind
        dev = device_kind()
        cfgm = self.bundle.cfg
        shape = {"arch": cfgm.name, "seq_len": self.cfg.seq_len,
                 "global_batch": self.cfg.global_batch, "tp": self.cfg.tp}
        self.profile_store.fold(dev, "observed_step", shape, "time_s", dt)
        # per-layer per-SEQUENCE time: a whole-step observation cannot
        # separate microbatch sizes, so normalize by the batch and let the
        # cost model scale linearly to the queried micro_bs.  obs_scale
        # tags the REAL slowdown of this host's kind only — injection
        # distorts telemetry, never the measured wall time
        self.profile_store.fold(
            dev, "observed_layer_step",
            {"arch": cfgm.name, "seq_len": self.cfg.seq_len,
             "tp": self.cfg.tp},
            "per_seq_s", dt / (max(cfgm.num_layers, 1)
                               * self.cfg.global_batch),
            also={"obs_scale": self._model_scale(dev)})
        if self.telemetry is not None:
            self.telemetry.observe_step(dt)    # no-op in callback mode
            self._fold_telemetry(dev)

    def _fold_telemetry(self, dev: str):
        """Fold fresh per-stage/per-tick observations as
        ``observed_stage_tick`` / ``observed_bubble`` entries.  Single-host
        runs fold every stage under this host's device kind (each host of
        a real deployment folds its own stage under its own kind)."""
        plan = self.plan
        vl = list(plan.virtual_layers)
        lmax = max(vl)
        obs = self._obs_scales()
        folded = self.telemetry.fold_into(
            self.profile_store, [dev] * plan.pp,
            arch=self.bundle.cfg.name, seq_len=self.cfg.seq_len,
            tp=self.cfg.tp, schedule=plan.schedule,
            layers_per_vstage=vl,
            padded_per_stage=[plan.vpp * lmax] * plan.pp,
            micro_bs_per_stage=[plan.stage_micro_bs(i)
                                for i in range(plan.pp)],
            stage_scale=(self._stage_scales()
                         if self._inject_scale else None),
            stage_obs_scale=(
                [obs.get(self.cluster.groups[st.group].device.name, 1.0)
                 for st in plan.stages]
                if self.cluster is not None else None))
        if self.obs is not None:
            self.obs.on_fold(self.step, folded, dev)

    # ------------------------------------ autonomous adaptation (adapt) ---
    def inject_degrade(self, device_kind: str, factor: float) -> None:
        """Straggler INJECTION: make the telemetry report ``device_kind``'s
        stages as ``factor``x slower from now on.  On a serial CPU mesh a
        degraded device cannot actually slow down, so this is the testing/
        demo hook that drives the autonomous controller end-to-end (the
        launch layer wires ``--degrade KIND:FACTOR@STEP`` to it); the
        observations it distorts are exactly what real degraded hardware
        would have produced.  Injections compose multiplicatively per
        kind; requires a cluster (to map stages to kinds)."""
        if self.cluster is None:
            raise ValueError("inject_degrade needs a cluster "
                             "(stage -> device kind mapping)")
        if factor <= 0:
            raise ValueError(f"factor must be > 0, got {factor}")
        if all(g.device.name != device_kind for g in self.cluster.groups):
            known = sorted({g.device.name for g in self.cluster.groups})
            raise ValueError(f"unknown device kind {device_kind!r}; "
                             f"cluster has {known}")
        self._inject_scale[device_kind] = \
            self._inject_scale.get(device_kind, 1.0) * factor

    def inject_link_degrade(self, factor: float) -> None:
        """Boundary-link INJECTION, ``inject_degrade``'s sibling for the
        wrong-schedule signal: make the OBSERVED pipeline bubble report
        ``factor``x the recorder's value from now on.  A slowed
        inter-island boundary link stretches exactly the send-dominated
        idle ticks — stage compute is untouched, so the straggler signal
        stays quiet and the bubble ratio in ``schedule_health`` is what
        departs from prediction (the scenario the ``replan-schedule``
        policy decision exists for).  Factors compose multiplicatively."""
        if factor <= 0:
            raise ValueError(f"factor must be > 0, got {factor}")
        self._inject_bubble *= factor

    # -------------------------------- elastic membership (node loss/join) --
    def lose_node(self, device_kind: str, *, rank: Optional[int] = None
                  ) -> None:
        """Membership FACT: ``device_kind``'s island left the cluster
        (scheduler preemption, hardware death).  Queues a ``node-lost``
        event; at the next adaptation cadence the surviving leader forces
        a replan onto the surviving topology (dp-width and pp-depth
        changes allowed) and every process live-migrates — no restart.
        The island's healthy spec is remembered so ``join_node`` can
        restore it, and its profile entries enter the bounded-staleness
        window (``profile_stale_steps``).

        ``rank``: the jax process rank hosted on the lost island, when
        the caller knows it — removed from the aggregator's surviving set
        immediately, so leadership re-elects (lowest surviving rank)
        BEFORE the directive for this very event must be originated.
        Every process must be told the same facts (the launch harness /
        scheduler hook calls this on all survivors)."""
        if self.cluster is None:
            raise ValueError("lose_node needs a cluster")
        if all(g.device.name != device_kind for g in self.cluster.groups):
            known = sorted({g.device.name for g in self.cluster.groups})
            raise ValueError(f"unknown device kind {device_kind!r}; "
                             f"cluster has {known}")
        if len(self.cluster.groups) == 1:
            raise ValueError(f"cannot lose {device_kind!r}: it is the "
                             "last island in the cluster")
        if rank is not None and hasattr(self.aggregator, "lose_rank"):
            self.aggregator.lose_rank(rank)
        self._membership_pending.append(
            {"op": "lost", "kind": device_kind})

    def join_node(self, device_kind: Optional[str] = None, *,
                  group=None, rank: Optional[int] = None) -> None:
        """Membership FACT: an island (re)joined the cluster.  By
        ``device_kind`` it restores the remembered healthy spec of an
        island ``lose_node`` removed earlier; a brand-new island joins by
        explicit ``group`` (a ``NodeGroup``).  Queues a ``node-joined``
        event: the leader forces a replan on the grown topology — a
        rejoin restores the plan shape the capacity allows.  ``rank``
        restores a previously-lost process rank in the aggregator."""
        if self.cluster is None:
            raise ValueError("join_node needs a cluster")
        if group is None:
            if device_kind is None:
                raise ValueError("join_node needs a device_kind (rejoin) "
                                 "or an explicit group=NodeGroup")
            group = self._departed_groups.get(device_kind)
            if group is None:
                raise ValueError(
                    f"no departed island of kind {device_kind!r} to "
                    f"rejoin (departed: "
                    f"{sorted(self._departed_groups)}); pass "
                    f"group=NodeGroup(...) for a brand-new island")
        if rank is not None and hasattr(self.aggregator, "rejoin_rank"):
            self.aggregator.rejoin_rank(rank)
        self._membership_pending.append(
            {"op": "joined", "group": group.to_dict()})

    def _stage_kinds(self):
        """Per-PHYSICAL-stage device kind names ("?" without a cluster)."""
        if self.cluster is None or self.plan is None:
            return ["?"] * (self.plan.pp if self.plan else 0)
        return [self.cluster.groups[st.group].device.name
                for st in self.plan.stages]

    def _stage_scales(self):
        """Per-PHYSICAL-stage injected tick multipliers (1.0 = healthy)."""
        if self.cluster is None or self.plan is None:
            return [1.0] * (self.plan.pp if self.plan else 0)
        return [self._inject_scale.get(
            self.cluster.groups[st.group].device.name, 1.0)
            for st in self.plan.stages]

    def _model_scale(self, kind: str) -> float:
        """Slowdown of ``kind`` the CURRENT cluster spec models, relative
        to the healthy reference (1.0 when healthy or not a cluster
        kind)."""
        if self.cluster is None:
            return 1.0
        for g in self.cluster.groups:
            if g.device.name == kind and g.device.effective_tflops > 0:
                ref = self._ref_tflops.get(kind, g.device.effective_tflops)
                return ref / g.device.effective_tflops
        return 1.0

    def _obs_scales(self) -> Dict[str, float]:
        """Per-device-kind slowdown the current telemetry folds are
        OBSERVED under, relative to the healthy reference — the
        ``obs_scale`` tag the replan cost source later divides out.
        Injection and an adopted cluster degradation describe the SAME
        slowdown (the injection exists because test hardware cannot
        actually slow down; real hardware already slows the measured
        ticks the model then adopts), so the two are not composed: the
        scale is whichever has caught up further."""
        out: Dict[str, float] = {}
        kinds = set(self._inject_scale)
        if self.cluster is not None:
            kinds |= {g.device.name for g in self.cluster.groups}
        for k in kinds:
            s = max(self._inject_scale.get(k, 1.0), self._model_scale(k))
            if abs(s - 1.0) > 1e-12:
                out[k] = s
        return out

    def _merged_store(self):
        """The cluster-wide profile view: every process's telemetry folds
        gathered into one store (repro.adapt aggregators; identity on a
        single process / without an aggregator).  The adaptive run loop
        refreshes the view at a step-synchronized cadence
        (``aggregate_every``) and this serves the cached copy — calling a
        COLLECTIVE aggregator from a data-dependent code path (a policy
        decision, a health probe) would deadlock diverged processes.  The
        lazy fallback below only fires outside an adaptive loop (manual
        replan), where the caller owns cross-process symmetry."""
        if self.profile_store is None or self.aggregator is None:
            return self.profile_store
        if self._cluster_view is not None:
            return self._cluster_view
        return self.aggregator.gather(self.profile_store)

    def _stage_tick_obs(self):
        """Per-PHYSICAL-stage forward tick seconds (each stage's vpp
        chunks summed, injected degradation applied) — the policy's
        straggler signal.  Single-process: the local telemetry's most
        recent observation.  With a multi-process (collective) aggregator
        the ticks come from the gathered CLUSTER view instead — every
        process's folds, covering stages this process never hosts.  None
        before the first kept/gathered observation."""
        if getattr(self.aggregator, "collective", False):
            return self._store_stage_ticks()
        ticks = self.telemetry.stage_ticks() if self.telemetry else None
        if ticks is None:
            return None
        pp, vpp = self.plan.pp, self.plan.vpp
        scales = self._stage_scales()
        return [scales[i] * sum(ticks[ch * pp + i] for ch in range(vpp))
                for i in range(pp)]

    def _store_stage_ticks(self):
        """Per-physical-stage tick times reconstructed from the gathered
        cluster view (``observed_stage_tick`` folds of EVERY process,
        degradation as observed — raw, not the reference-healthy
        normalization the cost source uses).  The store only holds
        all-time running means, under which a fresh degradation would
        surface ever more slowly as the run ages — so the policy is fed
        the DELTA between consecutive evaluations: (Σn·mean)_now minus
        (Σn·mean)_prev per stage, i.e. exactly the mean of the folds that
        arrived since the last look (frozen entries from superseded plans
        cancel out of the difference).  None until every stage of the
        executing plan has fresh observations."""
        store = self._merged_store()
        if store is None:
            return None
        plan, cfgm = self.plan, self.bundle.cfg
        sums = [0.0] * plan.pp
        ns = [0.0] * plan.pp
        for e in store.entries(op="observed_stage_tick"):
            s = e.shape
            if (s.get("arch") != cfgm.name
                    or s.get("seq_len") != self.cfg.seq_len
                    or s.get("tp") != self.cfg.tp
                    or s.get("schedule") != plan.schedule
                    or s.get("pp") != plan.pp or s.get("vpp") != plan.vpp
                    or "tick_s" not in e.value):
                continue
            i = s.get("stage", -1)
            if not 0 <= i < plan.pp:
                continue
            n = e.value.get("n", 1.0)
            sums[i] += n * e.value["tick_s"]
            ns[i] += n
        prev = self._store_tick_state
        self._store_tick_state = (ns, sums)
        if prev is not None and len(prev[0]) == len(ns):
            d_n = [a - b for a, b in zip(ns, prev[0])]
            d_s = [a - b for a, b in zip(sums, prev[1])]
            if all(d > 0.0 for d in d_n):
                return [s / n for s, n in zip(d_s, d_n)]
            return None       # no fresh folds everywhere since last look
        if any(n <= 0.0 for n in ns):
            return None
        return [t / n for t, n in zip(sums, ns)]

    def _emit(self, event) -> None:
        self.adapt_log.append(event)
        if self.obs is not None:
            self.obs.on_adapt_event(event)

    def _adapt_leader(self) -> bool:
        """Whether THIS process runs the policy/search.  Exactly one
        process of a multi-process run leads (the aggregator names it);
        without an aggregator every trainer is its own leader."""
        if self.aggregator is None:
            return True
        return getattr(self.aggregator, "is_leader", lambda: True)()

    def _maybe_adapt(self) -> None:
        """One pass of the closed loop, CLUSTER-SYMMETRIC by construction:
        the leader process turns queued membership events into directives
        (forced — topology facts carry no ε gate), else consults the
        policy on its new telemetry (the gathered cluster view on
        multi-process runs), searches, and ε-gates; the resulting
        directive — or None — is then BROADCAST through the aggregator,
        and every process applies it (or skips) together.  Per-process
        policy/hysteresis/cooldown state therefore never gates the
        collective adoption (checkpoint, jit-step rebuild, live
        migration): the broadcast itself is the only data-independent
        collective, entered unconditionally at the run-loop's
        step-synchronized cadence point.

        Leadership is re-evaluated every pass: when the previous leader's
        rank was lost, the aggregator's lowest-surviving-rank rule makes
        a new process answer ``is_leader() == True`` — it logs a
        ``re-elect`` event and takes over originating directives, so the
        loop survives the leader process itself dying."""
        if self.cluster is None:
            return       # nothing to replan against without a cluster
        self._expire_stale_profiles()
        lead = self._adapt_leader()
        if lead and self._was_leader is False:
            from repro.adapt import AdaptEvent
            self._emit(AdaptEvent(
                self.step, "re-elect",
                "this process is now the adaptation leader "
                "(lowest surviving rank)",
                {"leader_rank": getattr(self.aggregator, "leader_rank",
                                        lambda: 0)()}))
        self._was_leader = lead
        directive = None
        if lead:
            directive = self._membership_directive()
            if directive is None and self.policy is not None \
                    and self.telemetry is not None \
                    and self._pipeline_active():
                directive = self._adapt_decide()
        if self.aggregator is not None:
            directive = self.aggregator.broadcast(directive)
        if directive is None:
            return
        if directive.get("membership"):
            self._apply_membership(directive)
        else:
            self._adapt_apply(directive)

    def _membership_directive(self) -> Optional[Dict[str, Any]]:
        """LEADER ONLY: turn the oldest queued membership event into an
        adoption directive — edit the cluster (``remove_group`` /
        ``add_group``), force a replan on the edited topology (dp-width
        and pp-depth changes are whatever ``adapt_search_kw`` allows; the
        ε gate does NOT apply: membership is a fact, staying put is not
        an option), and ship the searched plan.  The incumbent plan is
        dropped as the search baseline across a LOSS — group indices
        shift when an island is removed, so scoring the old plan against
        the new topology would map stages onto the wrong islands."""
        from repro.adapt import AdaptEvent
        from repro.core.cluster import NodeGroup
        while self._membership_pending:
            ev = self._membership_pending.pop(0)
            if ev["op"] == "lost":
                new_cluster = self.cluster.remove_group(ev["kind"])
                search_kw = dict(self.adapt_search_kw,
                                 baseline_plan=None)
            else:
                group = NodeGroup.from_dict(ev["group"]).healthy
                new_cluster = self.cluster.add_group(group)
                search_kw = dict(self.adapt_search_kw)
            try:
                result = self.plan_for(
                    new_cluster, global_batch=self.cfg.global_batch,
                    seq_len=self.cfg.seq_len, **search_kw)
            except RuntimeError as e:
                # no feasible plan on the edited topology under the
                # configured search space: keep training on the incumbent
                # (the operator sees why) and try the next queued event
                self._emit(AdaptEvent(
                    self.step, "skip",
                    f"membership {ev['op']} search failed: {e}",
                    {"membership": dict(ev)}))
                continue
            gain = result.expected_gain
            self._emit(AdaptEvent(
                self.step, "replan",
                f"membership {ev['op']}: searched {result.evaluated} "
                f"candidates (forced, no ε gate)",
                {"winner": result.plan.describe(),
                 "iter_time": result.prediction.iter_time,
                 "baseline_time": result.baseline_time,
                 "expected_gain": (round(gain, 4) if gain is not None
                                   else None)}))
            return {"membership": dict(ev),
                    "plan": result.plan.to_dict()}
        return None

    def _apply_membership(self, directive: Dict[str, Any]) -> None:
        """EVERY process (leader and followers alike): commit a broadcast
        membership directive — apply the same cluster edit, adopt the
        leader's searched plan, live-migrate in memory.  The profile
        entries of a departed kind enter the bounded-staleness window
        (kept ``profile_stale_steps`` steps for a rejoin, then dropped
        from planning); a rejoined kind's mark clears so its kept
        entries serve again (warm profile, no re-baseline)."""
        from repro.adapt import AdaptEvent
        from repro.core.cluster import NodeGroup
        mem = directive["membership"]
        plan = ParallelPlan.from_dict(directive["plan"])
        if mem["op"] == "lost":
            kind = mem["kind"]
            for g in self.cluster.groups:
                if g.device.name == kind:
                    self._departed_groups[kind] = g.healthy
            new_cluster = self.cluster.remove_group(kind)
            if self.profile_store is not None:
                self.profile_store.mark_departed(kind, self.step)
            self._inject_scale.pop(kind, None)   # the island is gone
            self._emit(AdaptEvent(
                self.step, "node-lost",
                f"island {kind} left the cluster",
                {"kind": kind,
                 "surviving": [g.device.name
                               for g in new_cluster.groups]}))
        else:
            group = NodeGroup.from_dict(mem["group"]).healthy
            kind = group.device.name
            new_cluster = self.cluster.add_group(group)
            if self.profile_store is not None:
                self.profile_store.mark_rejoined(kind)
            self._departed_groups.pop(kind, None)
            self._emit(AdaptEvent(
                self.step, "node-joined",
                f"island {kind} joined the cluster",
                {"kind": kind,
                 "groups": [g.device.name for g in new_cluster.groups]}))
        # a follower that was told the same fact locally must not re-raise
        # it after the collective adoption already handled it
        self._membership_pending = [
            ev for ev in self._membership_pending
            if not (ev["op"] == mem["op"]
                    and (ev.get("kind") == mem.get("kind")
                         or ev.get("group", {}).get("device", {})
                         .get("name") == kind))]
        self._adopt(_AdoptedPlan(plan), new_cluster, migrate="memory")
        if self.policy is not None:
            self.policy.reset(self.step)
        self._adapt_seen = 0
        self._store_tick_state = None    # new plan: fresh delta basis
        self._emit(AdaptEvent(
            self.step, "migrate",
            f"adopted the post-{mem['op']} plan live",
            {"plan": plan.describe(),
             "migrations": dict(self.migrations)}))

    def _adapt_decide(self) -> Optional[Dict[str, Any]]:
        """LEADER ONLY: consult the policy on each NEW telemetry
        observation; when it fires, search — and return an adoption
        directive only if the predicted gain clears the policy's ε gate.
        The whole decision trail lands in ``adapt_log`` as structured
        AdaptEvents."""
        from repro.adapt import AdaptEvent
        if self.telemetry.steps <= self._adapt_seen:
            return None                   # no new observation this step
        self._adapt_seen = self.telemetry.steps
        health = self.schedule_health()
        decision = self.policy.observe(
            self.step, self._stage_tick_obs(),
            bubble_ratio=(health["ratio"] if health else None),
            provenance=("bucketed" if self.telemetry.mode == "timer"
                        else "exact"))
        if decision is None:
            return None
        self._emit(AdaptEvent(
            self.step, "trigger", decision.reason,
            {"action": decision.action,
             "signal": round(decision.signal, 4),
             **({"stage": decision.stage,
                 "factor": decision.factor}
                if decision.stage is not None else {})}))
        if decision.action == "replan-straggler":
            g = self.cluster.groups[self.plan.stages[decision.stage].group]
            kind = g.device.name
            # the policy measures slowdown relative to the plan it is
            # watching — a plan that already absorbed any earlier degrade
            # — while ``degrade()`` is absolute vs the healthy rating
            # (replace-not-compose).  Ship the product so a second REAL
            # slowdown on an already-degraded kind lands in full.
            factor = decision.factor * g.device.slowdown
            new_cluster = self.cluster.degrade(kind, factor)
        else:
            # wrong-schedule signal: same cluster, re-score the schedule
            # sweep against the observed profile
            kind = factor = None
            new_cluster = self.cluster
        try:
            result = self.plan_for(
                new_cluster, global_batch=self.cfg.global_batch,
                seq_len=self.cfg.seq_len, **self.adapt_search_kw)
        except RuntimeError as e:
            # no feasible plan on the (degraded) cluster: keep training on
            # the incumbent rather than killing the loop; cooldown so the
            # armed signal doesn't re-search every step
            self.policy.reject(self.step)
            self._emit(AdaptEvent(self.step, "skip",
                                  f"search failed: {e}", {}))
            return None
        gain = result.expected_gain
        self._emit(AdaptEvent(
            self.step, "replan", f"searched {result.evaluated} candidates",
            {"winner": result.plan.describe(),
             "iter_time": result.prediction.iter_time,
             "baseline_time": result.baseline_time,
             "expected_gain": (round(gain, 4) if gain is not None
                               else None)}))
        if not self.policy.gain_ok(result):
            self.policy.reject(self.step)
            self._emit(AdaptEvent(
                self.step, "skip",
                f"expected gain {gain:.4f} below min_gain "
                f"{self.policy.cfg.min_gain} — migration not worth it",
                {"expected_gain": round(gain, 4),
                 "min_gain": self.policy.cfg.min_gain}))
            return None
        # JSON-serializable directive: what every process must adopt
        return {"kind": kind, "factor": factor,
                "plan": result.plan.to_dict()}

    def _adapt_apply(self, directive: Dict[str, Any]) -> None:
        """EVERY process (leader and followers alike): commit a broadcast
        directive — rebuild the degraded cluster from (kind, factor),
        deserialize the leader's searched plan, and enter the collective
        adoption together."""
        from repro.adapt import AdaptEvent
        plan = ParallelPlan.from_dict(directive["plan"])
        new_cluster = (self.cluster.degrade(directive["kind"],
                                            directive["factor"])
                       if directive.get("kind") else self.cluster)
        self._adopt(_AdoptedPlan(plan), new_cluster, migrate="memory")
        self.policy.reset(self.step)
        self._adapt_seen = 0
        self._store_tick_state = None    # new plan: fresh delta basis
        self._emit(AdaptEvent(
            self.step, "migrate", "adopted the searched plan live",
            {"plan": plan.describe(),
             "migrations": dict(self.migrations)}))

    # ----------------------------------------------- schedule diagnostics --
    def schedule_health(self) -> Optional[Dict[str, float]]:
        """Observed vs predicted bubble for the executing plan — the
        signal that separates "slow kernels" (stage ticks up, bubble flat:
        refit costs) from "wrong schedule" (bubble above prediction:
        re-score schedules).  None before any observation or without a
        cluster+plan to predict against."""
        if self.cluster is None or not self._pipeline_active():
            return None
        observed = self.telemetry.bubble() if self.telemetry else None
        if observed is None and self.profile_store is not None:
            from repro.profile.model import ProfiledCostModel
            from repro.profile.runner import device_kind
            observed = ProfiledCostModel(self._merged_store()).observed_bubble(
                device_kind(), self.bundle.cfg, self.plan.schedule,
                self.plan.pp, self.plan.vpp, self.plan.micro_batches)
        if observed is None:
            return None
        observed *= self._inject_bubble
        # the predicted bubble is constant for a (plan, cluster) pair, and
        # the adaptive loop asks every step — simulate once per pair, not
        # per step (cache invalidates itself when replan swaps either)
        cached = self._pred_bubble
        if cached is not None and cached[0] is self.plan \
                and cached[1] is self.cluster:
            predicted = cached[2]
        else:
            from repro.core.predictor import PerformancePredictor
            predicted = PerformancePredictor(
                self.cluster, self.bundle.cfg,
                include_tp_comm=False).predict(self.plan).bubble_frac
            self._pred_bubble = (self.plan, self.cluster, predicted)
        return {"observed_bubble": observed, "predicted_bubble": predicted,
                "ratio": observed / max(predicted, 1e-9)}

    # --------------------------------------------- replan cost sourcing ---
    def _degrade_scales(self, new_cluster: ClusterSpec) -> Dict[str, float]:
        """Per-device-name time scales projecting the profile's
        REFERENCE-HEALTHY served times onto the new cluster: a kind whose
        effective TFLOPs sits f-times below the healthy reference
        (``_ref_tflops``, the construction-time cluster) serves its
        observations f-times slower.  Telemetry folds are normalized back
        to reference health by their ``obs_scale`` tag before this scale
        applies (ProfiledCostModel), so a slowdown the observations
        already contain — injected or real — is counted exactly once,
        never compounded."""
        out = {}
        for g in new_cluster.groups:
            ref = self._ref_tflops.get(g.device.name)
            now = g.device.effective_tflops
            if ref is not None and now > 0 and \
                    abs(ref - now) > 1e-12 * ref:
                out[g.device.name] = ref / now
        return out

    def _expire_stale_profiles(self) -> None:
        """Bounded staleness for departed islands: profile entries of a
        kind that left the cluster are KEPT ``profile_stale_steps`` steps
        — a rejoin inside the window plans on its warm profile instantly
        — then DROPPED from planning, so a kind that is gone for good
        stops biasing the search and a flapping node cannot thrash the
        planner with alternately-stale views."""
        if self.profile_store is None:
            return
        for kind in self.profile_store.stale_kinds(
                self.step, self.cfg.profile_stale_steps):
            n = self.profile_store.drop_device(kind)
            if self.obs is not None and self.obs.flight is not None:
                self.obs.flight.note(
                    "profile-stale", step=self.step, kind=kind, dropped=n,
                    keep_steps=self.cfg.profile_stale_steps)

    def profiled_cost_source(self, cluster: ClusterSpec):
        """The online profile as a planner cost source — once it is dense
        enough to trust (ROADMAP: profile-aware replan).

        Returns None below ``replan_profile_min_obs`` folded layer-time
        observations.  Every cluster device maps to this host's device
        kind: the observing host stands in for the whole cluster, the
        paper's profile-a-sample-predict-the-cluster methodology (a real
        multi-island deployment folds per-island kinds instead).  Device
        kinds ``cluster`` reports as degraded relative to the HEALTHY
        REFERENCE get their served times scaled by the degradation factor
        — served times are reference-healthy (telemetry folds normalized
        by their ``obs_scale`` tag), so the factor applies exactly once
        however much slowdown the folds already contained.  With an
        aggregator attached the source reads the CLUSTER-wide merged
        store (every process's telemetry folds), not this process's 1/N
        view."""
        self._expire_stale_profiles()   # departed kinds past their window
        store = self._merged_store()
        if store is None:
            return None
        # count only observations the replan search can actually consume:
        # entries for the trained architecture (a stale profile for some
        # other model must not open the gate)
        obs = [e for e in (store.entries(op="observed_layer_step")
                           + store.entries(op="layer_step")
                           + store.entries(op="observed_stage_tick"))
               if e.shape.get("arch") == self.bundle.cfg.name]
        if sum(e.value.get("n", 1.0) for e in obs) < \
                self.cfg.replan_profile_min_obs:
            return None
        from repro.profile.model import ProfiledCostModel
        from repro.profile.runner import device_kind
        dev = device_kind()
        return ProfiledCostModel(
            store, device_map={g.device.name: dev for g in cluster.groups},
            time_scale=self._degrade_scales(cluster))

    # ------------------------------------------- elastic replan (HETHUB) --
    def replan(self, new_cluster: ClusterSpec, *, global_batch: int,
               seq_len: int, migrate: str = "memory", **search_kw):
        """Node failure / degradation / elastic scale event: search a new
        plan on the surviving cluster, checkpoint-now, and migrate the
        live state onto the new plan without restarting.

        When the trainer has been folding observed step times and stage
        telemetry into its ``profile_store``, the search runs against them
        (measured costs, degradation-scaled) instead of the analytic model
        — unless the caller passes an explicit ``cost_source`` — and the
        incumbent plan is scored as the search baseline, so the winner is
        never predicted worse than staying put.

        ``migrate``: "memory" reshards optimizer+param state in memory
        (checkpoint round-trip only as a fallback); "checkpoint" forces
        the round-trip through the just-written checkpoint."""
        result = self.plan_for(new_cluster, global_batch=global_batch,
                               seq_len=seq_len, **search_kw)
        self._adopt(result, new_cluster, migrate=migrate)
        return result

    def plan_for(self, new_cluster: ClusterSpec, *, global_batch: int,
                 seq_len: int, **search_kw):
        """The search half of ``replan``, WITHOUT adopting the result:
        searches ``new_cluster`` under the trainer's observed cost source
        (degradation-scaled, cluster-wide via the aggregator) with the
        incumbent plan as the baseline.  The adaptation controller calls
        this first and gates ``_adopt`` on the result's
        ``expected_gain`` — searching is cheap, migrating is not."""
        if "cost_source" not in search_kw:
            src = self.profiled_cost_source(new_cluster)
            if src is not None:
                search_kw["cost_source"] = src
        if self.plan is not None:
            search_kw.setdefault("baseline_plan", self.plan)
        result = planner_mod.search(new_cluster, self.bundle.cfg,
                                    global_batch=global_batch,
                                    seq_len=seq_len, **search_kw)
        if self.obs is not None:
            self.obs.on_search(self.step if hasattr(self, "step") else 0,
                               result)
        return result

    def _adopt(self, result, new_cluster: ClusterSpec,
               migrate: str = "memory") -> None:
        """The commit half of ``replan``: checkpoint-now (crash safety),
        swap in the searched plan, rebuild the step, and live-migrate the
        optimizer+param state onto the new layout."""
        if migrate not in ("memory", "checkpoint"):
            raise ValueError(f"unknown migrate mode {migrate!r}")
        self.ckpt.wait()
        old_layout = self._state_layout()
        # durable pre-migration checkpoint in the OLD layout (crash safety
        # + the round-trip fallback's source)
        ckpt.save(self.cfg.ckpt_dir, self.step, self.state,
                  extra=self._ckpt_extra())
        self.cluster = new_cluster
        # kinds first seen on the new cluster join the healthy reference
        # at their current rating; kinds already referenced keep theirs
        # (the reference is what obs_scale tags and replan projections
        # are relative to)
        for g in new_cluster.groups:
            self._ref_tflops.setdefault(g.device.name,
                                        g.device.effective_tflops)
        self.plan = result.plan
        self.replans += 1
        self._build()
        t_mig = time.perf_counter()
        migrated = False
        if migrate == "memory":
            try:
                host = jax.device_get(self.state)
                host = ckpt.migrate(host, old_layout, self._state_layout())
                shardings = self._state_shardings(
                    jax.eval_shape(lambda: host))
                self.state = self._place(host, shardings)
                self.migrations["memory"] += 1
                migrated = True
            except Exception as e:  # noqa: BLE001 — any failure falls back
                # to the durable checkpoint round-trip; the in-memory
                # failure itself is a flight-recorder event (the fallback
                # hides it from the caller, the post-mortem needs it)
                if self.obs is not None and self.obs.flight is not None:
                    self.obs.flight.note("migration-error", step=self.step,
                                         error=repr(e))
                    self.obs.flight_dump("migration-failure")
        if not migrated:
            self._init_or_restore()   # restores + migrates the checkpoint
        if self.obs is not None:
            self.obs.on_migration(time.perf_counter() - t_mig, migrated)
