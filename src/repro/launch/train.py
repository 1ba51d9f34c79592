"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
        --steps 20 --global-batch 8 --seq 128

Runs the full production loop on whatever devices exist (CPU included):
planner (when a cluster is given) -> sharded init -> train loop with async
checkpointing, straggler telemetry and elastic-replan hooks.

``--pp N`` runs the HETHUB pipeline end-to-end: the automatic parallel
planner searches a plan over a paper-preset heterogeneous cluster, the
trainer executes it through the SPMD pipeline step with online stage
telemetry, and ``--degrade KIND:FACTOR[@STEP]`` injects a straggler
(default: after half the steps) to drive a live replan + state migration
mid-run.

``--adapt`` hands that decision to the autonomous adaptation controller
(repro.adapt): the injected degradation only distorts the telemetry, and
the policy detects it, replans, gain-gates, and live-migrates BY ITSELF —
no replan call in this driver.  Every decision prints as a structured
AdaptEvent line (docs/adaptation.md is the runbook).  Multi-process runs
aggregate per-pod telemetry automatically (repro.adapt.default_aggregator)
— no extra flags.

``--lose KIND@STEP`` / ``--join KIND@STEP`` inject elastic MEMBERSHIP
events: the named island leaves (or rejoins) the cluster mid-run, the
controller forces a replan onto the edited topology (dp-width and
pp-depth changes included) and live-migrates the state — no process
restart.  Both are repeatable, so ``--lose gpu-a@6 --join gpu-a@12``
exercises a full lose/re-elect/replan/rejoin round trip.
"""
from __future__ import annotations

import argparse
import json
import math
import time

import jax

from repro.launch import compile_cache
from repro.launch.mesh import make_train_mesh
from repro.models import registry
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


def degrade_spec(text: str):
    """Validated ``--degrade`` value: KIND:FACTOR[@STEP] -> (kind, factor,
    step or None).  A malformed spec fails at the flag with the expected
    shape spelled out, not deep in the run with a bare ValueError."""
    err = argparse.ArgumentTypeError(
        f"expected KIND:FACTOR[@STEP] (e.g. gpu-a:8@6), got {text!r}")
    spec, _, at = text.partition("@")
    kind, sep, factor_s = spec.partition(":")
    if not kind or not sep:
        raise err
    try:
        factor = float(factor_s)
        step = int(at) if at else None
    except ValueError:
        raise err from None
    if not (factor > 0 and math.isfinite(factor)):
        raise argparse.ArgumentTypeError(
            f"degrade FACTOR must be a finite number > 0, got {factor_s!r}")
    if step is not None and step < 0:
        raise argparse.ArgumentTypeError(
            f"degrade @STEP must be >= 0, got {at!r}")
    return kind, factor, step


def membership_spec(text: str):
    """Validated ``--lose``/``--join`` value: KIND@STEP -> (kind, step).
    The step is mandatory — a membership event is a scheduled fact, not a
    half-the-run default."""
    err = argparse.ArgumentTypeError(
        f"expected KIND@STEP (e.g. gpu-a@6), got {text!r}")
    kind, sep, at = text.partition("@")
    if not kind or not sep:
        raise err
    try:
        step = int(at)
    except ValueError:
        raise err from None
    if step < 0:
        raise argparse.ArgumentTypeError(
            f"membership @STEP must be >= 0, got {at!r}")
    return kind, step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b",
                    choices=list(registry.ARCH_IDS) + ["llama-100m"])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--layers", type=int, default=0,
                    help="override the arch's layer count (0 = default; "
                         "a pipeline needs enough layers to re-balance)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--pp", type=int, default=0,
                    help="run a planner-searched pp-stage pipeline with "
                         "online stage telemetry (0 = plain DP step)")
    ap.add_argument("--telemetry", default="auto",
                    choices=["auto", "callback", "timer", "off"])
    ap.add_argument("--degrade", type=degrade_spec, default=None,
                    help="KIND:FACTOR[@STEP] straggler injection (default "
                         "STEP: half the steps) -> live replan + migration "
                         "(needs --pp); with --adapt the injection only "
                         "distorts telemetry and the controller reacts")
    ap.add_argument("--lose", type=membership_spec, action="append",
                    default=[], metavar="KIND@STEP",
                    help="membership event: island KIND leaves the "
                         "cluster at STEP — the controller forces a "
                         "replan onto the survivors and live-migrates, "
                         "no restart (needs --pp; repeatable)")
    ap.add_argument("--join", type=membership_spec, action="append",
                    default=[], metavar="KIND@STEP",
                    help="membership event: island KIND (re)joins at "
                         "STEP — restores the healthy spec remembered by "
                         "an earlier --lose and replans back onto it "
                         "(needs --pp; repeatable)")
    ap.add_argument("--adapt", action="store_true",
                    help="autonomous adaptation: the repro.adapt policy "
                         "watches telemetry and replans/migrates itself")
    ap.add_argument("--adapt-min-gain", type=float, default=0.05,
                    help="ε gate: min predicted fractional iter-time gain "
                         "before a migration is adopted")
    ap.add_argument("--adapt-enter", type=float, default=2.0,
                    help="straggler hysteresis enter threshold (ratio of "
                         "a stage's tick time vs its healthy baseline)")
    ap.add_argument("--adapt-exit", type=float, default=0.0,
                    help="straggler hysteresis exit threshold; 0 derives "
                         "it from --adapt-enter (keeps the default band "
                         "shape, so any enter value is valid)")
    ap.add_argument("--adapt-patience", type=float, default=2.0,
                    help="armed observations required before triggering")
    ap.add_argument("--adapt-cooldown", type=int, default=8,
                    help="observed steps of silence after any trigger")
    # -- observability (repro.obs; docs/observability.md) ----------------
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace/Perfetto JSON timeline "
                         "(predicted + observed lanes, AdaptEvent "
                         "instants) to this path")
    ap.add_argument("--metrics-out", default=None,
                    help="write the append-only metrics JSONL stream to "
                         "this path")
    ap.add_argument("--events-out", default=None,
                    help="write the AdaptEvent log as JSONL to this path")
    ap.add_argument("--prom-out", default=None,
                    help="write a Prometheus textfile snapshot at exit")
    ap.add_argument("--flight-out", default=None,
                    help="flight-recorder dump path (default: "
                         "<ckpt-dir>/flight.json when any observability "
                         "output is enabled)")
    args = ap.parse_args()
    compile_cache.enable()

    if args.arch == "llama-100m":
        import dataclasses
        from repro.configs.llama3_8b import CONFIG
        cfg = dataclasses.replace(
            CONFIG, name="llama-100m", num_layers=12, d_model=768,
            n_heads=12, n_kv_heads=4, d_ff=2048, vocab_size=32000,
            param_dtype="float32", dtype="float32")
        if args.layers:
            cfg = dataclasses.replace(cfg, num_layers=args.layers)
        bundle = registry.bundle_for(cfg)
    else:
        overrides = {"num_layers": args.layers} if args.layers else {}
        bundle = registry.get_bundle(args.arch, smoke=args.smoke,
                                     **overrides)

    n_dev = len(jax.devices())
    cluster = plan = store = None
    # ONE search space for the initial plan, the manual degrade replan,
    # and the controller's autonomous replans — diverging constraints
    # between them would make replans explore a different space than the
    # plan they replace
    search_kw = dict(pp_options=[args.pp] if args.pp else None,
                     tp_options=[1], micro_bs_options=[1, 2],
                     require_fit=False, include_tp_comm=False)
    if args.pp:
        from repro.core import cluster as cluster_mod, planner
        from repro.profile.store import ProfileStore
        cluster = cluster_mod.ClusterSpec(groups=(
            cluster_mod.NodeGroup(cluster_mod.AMD, 1, accel_per_node=1),
            cluster_mod.NodeGroup(cluster_mod.GPU_A, 1, accel_per_node=1)))
        plan = planner.search(
            cluster, bundle.cfg, global_batch=args.global_batch,
            seq_len=args.seq, **search_kw).plan
        print(f"[train] plan: {plan.describe()}")
        # the telemetry folds land here, so the degrade replan below
        # searches against observed (scaled) costs once dense enough
        store = ProfileStore()
    # a pipelined plan gets its pod axis: each stage's blocks on its chips
    mesh = make_train_mesh(plan)
    degrade_kind, degrade_factor, degrade_step = None, 1.0, None
    if args.degrade is not None:
        degrade_kind, degrade_factor, degrade_step = args.degrade
        if degrade_step is None:
            degrade_step = args.steps // 2
    membership = sorted(
        [(step, "lost", kind) for kind, step in args.lose]
        + [(step, "joined", kind) for kind, step in args.join])
    if membership and not args.pp:
        ap.error("--lose/--join need --pp (a cluster to edit)")
    policy = aggregator = None
    # membership replans search the SAME constrained space as the initial
    # plan even without --adapt — the forced replan must not wander into
    # shapes the operator ruled out up front — EXCEPT pipeline depth: a
    # lost island can leave too few accelerators for the configured pp,
    # so the controller may go shallower (and back up on a rejoin)
    adapt_kw = dict(search_kw) if args.pp else {}
    if args.pp:
        adapt_kw["pp_options"] = list(range(1, args.pp + 1))
    if args.adapt:
        from repro.adapt import AdaptConfig, ReplanPolicy, default_aggregator
        exit_ = args.adapt_exit or args.adapt_enter * (
            AdaptConfig.straggler_exit / AdaptConfig.straggler_enter)
        policy = ReplanPolicy(AdaptConfig(
            min_gain=args.adapt_min_gain,
            straggler_enter=args.adapt_enter, straggler_exit=exit_,
            patience=args.adapt_patience, cooldown=args.adapt_cooldown))
        # multi-pod telemetry aggregation needs no extra flags: identity on
        # one process, process_allgather fan-in on a real multi-host mesh
        aggregator = default_aggregator()
    obs = None
    if args.trace_out or args.metrics_out or args.events_out \
            or args.prom_out:
        from repro.obs import Observability, RunMeta, install_sigterm
        flight_out = args.flight_out or f"{args.ckpt_dir}/flight.json"
        obs = Observability(
            trace_out=args.trace_out, metrics_out=args.metrics_out,
            events_out=args.events_out, prom_out=args.prom_out,
            flight_out=flight_out,
            run=RunMeta.new(plan=plan, arch=bundle.cfg.name))
        # dump the decision ring when the cluster scheduler kills us
        install_sigterm(obs.flight, flight_out)
        print(f"[train] observability on: run={obs.run.run_id} "
              f"plan_digest={obs.run.plan_digest}")
    t = Trainer(bundle, mesh,
                TrainerConfig(global_batch=args.global_batch,
                              seq_len=args.seq, ckpt_dir=args.ckpt_dir,
                              ckpt_every=args.ckpt_every,
                              telemetry=args.telemetry),
                cluster=cluster, plan=plan, profile_store=store,
                policy=policy, aggregator=aggregator,
                adapt_search_kw=adapt_kw, obs=obs,
                opt_cfg=AdamWConfig(lr=args.lr, warmup_steps=20))
    n_params = sum(x.size for x in jax.tree.leaves(t.state["params"]))
    print(f"[train] arch={bundle.cfg.name} params={n_params/1e6:.1f}M "
          f"devices={n_dev} start_step={t.step}")
    t0 = time.time()
    done = 0
    printed_events = 0
    try:
        while done < args.steps:
            chunk = min(args.log_every, args.steps - done)
            # land each chunk boundary on the next injection step
            inject_steps = ([degrade_step]
                            if degrade_step is not None else [])
            inject_steps += [s for s, _, _ in membership]
            for s in inject_steps:
                if done < s < done + chunk:
                    chunk = s - done
            r = t.run(chunk)
            done += chunk
            dt = time.time() - t0
            tok_s = done * args.global_batch * args.seq / dt
            print(f"[train] step={t.step} loss={r['losses'][-1]:.4f} "
                  f"tok/s={tok_s:.0f}")
            if degrade_kind and plan is not None and done >= degrade_step:
                if args.adapt:
                    # autonomous path: only distort the telemetry — the
                    # controller detects, replans, gain-gates and migrates
                    t.inject_degrade(degrade_kind, degrade_factor)
                    print(f"[train] injected degrade {degrade_kind}:"
                          f"{degrade_factor} at step {t.step} — controller "
                          f"is on its own now")
                else:
                    degraded = t.cluster.degrade(degrade_kind,
                                                 degrade_factor)
                    res = t.replan(degraded,
                                   global_batch=args.global_batch,
                                   seq_len=args.seq, **search_kw)
                    plan = res.plan
                    print(f"[train] degraded {degrade_kind}:"
                          f"{degrade_factor} -> replanned: "
                          f"{plan.describe()} (migrations={t.migrations})")
                degrade_kind = None
            while membership and done >= membership[0][0]:
                _, op, kind = membership.pop(0)
                if op == "lost":
                    t.lose_node(kind)
                else:
                    t.join_node(kind)
                print(f"[train] membership: island {kind} {op} at step "
                      f"{t.step} — controller replans on the new "
                      f"topology")
            for ev in t.adapt_log[printed_events:]:
                print(ev.format())
            printed_events = len(t.adapt_log)
            health = t.schedule_health()
            if health is not None:
                print(f"[train] bubble "
                      f"observed={health['observed_bubble']:.3f} "
                      f"predicted={health['predicted_bubble']:.3f}")
    finally:
        # artifacts survive a mid-run crash: whatever was recorded up to
        # the failure is flushed and attributable to this run
        if obs is not None:
            obs.write_events(t.adapt_log)
            obs.close()
    print(json.dumps({"final_loss": r["losses"][-1], "steps": t.step,
                      "params_m": round(n_params / 1e6, 1),
                      "replans": t.replans, "migrations": t.migrations,
                      "adapt_events": [e.to_dict() for e in t.adapt_log]}))


if __name__ == "__main__":
    main()
