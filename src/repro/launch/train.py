"""End-to-end training driver: the host-side ACE-Sync control loop.

Wires together every subsystem:
  telemetry -> clustering -> omega weights (eq 8)
  bandwidth -> eq (5) budget -> importance scores -> knapsack -> SyncPlan
  divergence (eq 9) -> sync-interval H adaptation
  H local steps per pod + 1 ACE-Sync round, checkpoints, heartbeats,
  straggler detection, elastic membership on pod failure/rejoin.

The loop is **non-blocking**: since the plan-as-data refactor the host
never stalls the device to replan.

  * The step counter is mirrored on the host (one device fetch at loop
    start) instead of a blocking ``device_get`` per iteration.
  * Replanning for device-capable strategies (ACE-Sync) launches ONE
    device computation (importance scoring + vectorized knapsack, see
    ``core/acesync.device_replan_fn``) and fetches only the tiny
    ``int32[G]`` assignment vector asynchronously; the loop keeps stepping
    on the old plan and swaps once the fetch lands (the replan-to-apply
    latency is recorded in ``replan_latencies``).
  * Per-step metrics and the divergence EMA are fetched LAGGED — the
    record for step t is materialised while step t+1 is already running
    on device, so the host read overlaps device compute.
  * A replan whose bucket signature crosses a size-class boundary is
    warmed SPECULATIVELY: the new signature's step is AOT-compiled in a
    background thread (``Trainer.warm_compile``) before the plan swap
    lands, so a class-ladder rung change never stalls the device on a
    foreground compile.

Surviving the fleet (see README "How the system survives preemption"):

  * Checkpoints carry the FULL training state: params/opt moments/EF error
    buffers/importance state ride in the state pytree, and the manifest
    extras carry the active SyncPlan, the scheduler's sync interval, the
    ClusterState centroids/assignments, the loop counters and the data-
    pipeline position — restore + continue replays bit-identically on the
    same mesh (``blocking_replans`` pins the replan application steps).
  * Elastic membership: a pod marked dead (heartbeat timeout or injected
    fault) triggers a transition to a P-1 mesh — a per-pod-count Trainer
    is built over the surviving devices, its ring hops / bucket signature
    re-derived through ``planexec``, its step AOT-warmed in a BACKGROUND
    thread (``Trainer.warm_compile``) while the loop keeps draining steps
    on the old fleet, and the swap (state transfer included) lands only
    once the new-P executable is ready: zero foreground recompiles across
    the transition.  A rejoin replays the same path back through the
    cached P-trainer.
  * Deterministic fault injection: a seeded
    :class:`~repro.runtime.faults.FaultSchedule` drives kill/rejoin/
    corruption/heartbeat-delay events at fixed host steps.

Runs on any mesh (including none) with any registered arch; reduced configs
train end-to-end on CPU (see examples/train_lm.py).
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import threading
import time
from typing import Dict, List, Optional, Union

import jax
import numpy as np

from repro import obs
from repro.configs.base import RunConfig
from repro.checkpoint.checkpointer import Checkpointer
from repro.core import acesync
from repro.core.trainer import Trainer
from repro.data.telemetry import make_profiles, snapshot
from repro.hierarchy import ClusterState
from repro.runtime import faults as F
from repro.runtime.fault_tolerance import (ElasticPlanner, HeartbeatMonitor,
                                           MeshPlan, StragglerDetector)
from repro.strategies import (STEP_ADVANCING, STEP_KINDS, SYNC_KINDS,
                              SyncStrategy, list_strategies)


def _device_ready(x) -> bool:
    """True when an async host fetch of ``x`` would not block."""
    ready = getattr(x, "is_ready", None)
    if ready is None:
        return True  # old jax: accept a (cheap, already-lagged) sync get
    try:
        return bool(ready())
    except Exception:  # pragma: no cover - defensive
        return True


def _to_host_async(x):
    try:
        x.copy_to_host_async()
    except Exception:  # pragma: no cover - old jax / committed host array
        pass
    return x


class TrainLoop:
    """Host control loop around the jitted per-pod steps."""

    def __init__(self, model, run: RunConfig, mesh=None,
                 strategy: Union[str, SyncStrategy] = "acesync",
                 n_edge_devices: int = 8, seed: int = 0,
                 fault_schedule: Optional[F.FaultSchedule] = None,
                 elastic: bool = True, blocking_replans: bool = False):
        self.model = model
        self.run = run
        self.mesh = mesh
        self.trainer = Trainer(model, run, mesh=mesh, strategy=strategy)
        self.strategy = self.trainer.strategy
        self.ckpt = Checkpointer(run.ckpt_dir)
        self.profiles = make_profiles(n_edge_devices, seed)
        sched = self.trainer.scheduler
        # live clustering: 1:1 clusters<->cross-tier pods on a hierarchical
        # mesh, the config's n_clusters otherwise
        self.clusters = ClusterState(
            n_edge_devices,
            sched.n_cross if sched.hier_enabled else run.acesync.n_clusters,
            hysteresis=getattr(run.acesync, "cluster_hysteresis", 0.15))
        self._plan_takes_clusters = "clusters" in inspect.signature(
            self.strategy.make_plan).parameters
        self.monitor = HeartbeatMonitor(max(self.trainer.n_pods, 1))
        self.straggler = StragglerDetector()
        # elastic membership: only flat pod meshes re-derive their shape
        # (a hierarchical mesh's edge axis is cluster topology, not
        # membership — ROADMAP follow-up)
        self.elastic = bool(
            elastic and mesh is not None
            and set(mesh.axis_names) == {"pod", "data", "model"})
        self.planner = (ElasticPlanner(MeshPlan(
            n_pods=mesh.shape["pod"], data=mesh.shape["data"],
            model=mesh.shape["model"])) if self.elastic else None)
        self.faults = fault_schedule
        #: deterministic mode: replan fetches, AOT warm-ups and elastic
        #: swaps are applied synchronously at their launch step, so two
        #: runs of the same config replay the same plan/H/membership
        #: trajectory step for step (the restart-replay soak pins this)
        self.blocking_replans = bool(blocking_replans)
        #: one record per host step: ``step``, ``H``, the step's metrics
        #: and ``dt``, the host loop time of the step on ``perf_counter``
        #: (its dispatch and the lagged metric flush of the step before).
        #: The heartbeat reads it; it is not the device's step time.
        self.history = []
        self.comm_bytes = 0.0
        self._plan = None
        self._steps_since_sync = 0
        self._H: Optional[int] = None   # persisted sync interval
        self._host_step = None          # host mirror of the device counter
        self._pending_replan = None     # (assign_dev, omega, launched_step)
        self._warming = None            # (plan, thread, launched_step)
        self._div_fetch = None          # lagged divergence EMA fetch
        self.replan_latencies = []      # steps from replan launch to apply
        self._pipeline = None           # the stream run_steps is draining
        # ---- elastic state ----
        self._trainers: Dict[int, Trainer] = {self.trainer.n_pods:
                                              self.trainer}
        self._elastic_pending = None    # (trainer, plan, pipe, th, step, P)
        self._hb_delay: Dict[int, int] = {}
        #: membership transitions applied: dicts with from/to pod counts,
        #: the swap step and whether the new-P step came from the warm
        #: AOT cache (benchmarks/soaks record this)
        self.membership_events: List[dict] = []

    @property
    def plan(self):
        """The SyncPlan currently being executed (None before the first
        refresh)."""
        return self._plan

    # ---- aggregated compile telemetry ----------------------------------
    def compile_count(self) -> int:
        """Foreground traced-and-compiled step variants across EVERY
        trainer this loop has built (elastic transitions build one per
        pod count) — the number the fault soaks pin flat across a
        membership change."""
        return sum(tr.compile_count() for tr in self._trainers.values())

    def warm_compile_count(self) -> int:
        """Background AOT compiles across every trainer."""
        return sum(tr.warm_compiles for tr in self._trainers.values())

    # ---- policy refresh (host side, every replan_every steps) ----------
    def _policy_inputs(self, step: int):
        """Telemetry snapshot -> (telemetry, fleet omega weights).

        The live :class:`~repro.hierarchy.ClusterState` re-clusters on
        each refresh (warm-started k-means + hysteresis, so jitter-only
        telemetry never flaps the assignment), and the per-device
        reliability weights come back already summed into fleet slots —
        cluster-major on a hierarchical mesh, pod-major on a flat one.
        Straggle factors from the heartbeat monitor multiply into the
        telemetry straggle before clustering, so persistently slow pods
        are down-weighted in omega instead of stalling the ring.
        Everything returned is device data; a re-cluster never adds a
        static jit key."""
        telem = snapshot(self.profiles, step)
        sf = self.straggler.straggle_factors(self.monitor)
        alive = sorted(sf) or [0]
        for i, t in enumerate(telem):
            # device i reports through the alive pod it is homed on —
            # dead pods drop out of the straggle feed entirely
            t["straggle"] *= sf.get(alive[i % len(alive)], 1.0)
        self.clusters.update(telem)
        sched = self.trainer.scheduler
        return telem, self.clusters.fleet_omega(
            telem, sched.n_cross, sched.n_edge)

    def refresh_plan(self, state, step: int):
        cfg = self.run.acesync
        telem, omega = self._policy_inputs(step)

        dev_fn = (self.strategy.device_plan_fn(self.trainer.scheduler, cfg)
                  if state is not None else None)
        if dev_fn is not None and self._plan is not None:
            # Non-blocking device replan: one jitted computation produces
            # the new plan vector; only the tiny int32[G] assignment is
            # pulled to the host, asynchronously.  The loop keeps stepping
            # on the current plan until the fetch lands (poll_replan).
            # Only the estimator's scalar state enters the computation —
            # never the param-sized error buffers riding in ACEState.
            budget = self.trainer.scheduler.budget_for(
                self.strategy.budget_bandwidth(telem, self.clusters))
            ace = state["ace"]
            imp0 = jax.tree.map(lambda x: x[0], ace.importance)
            assign = _to_host_async(
                dev_fn(imp0, ace.struct_feat[0], budget))
            self._pending_replan = (assign, omega, self._host_step or step)
            return self._plan
        # host path: the first plan, and strategies without a device solver.
        # Only the estimator's few-hundred-scalar state is sliced and
        # fetched — never the param-sized error buffers in ACEState (the
        # group metas / local sizes / leaf layout are likewise computed
        # once at Trainer construction, not re-derived per replan poll).
        imp = None
        if self.strategy.uses_importance and state is not None:
            ace = state["ace"]
            imp0 = jax.tree.map(lambda x: x[0], ace.importance)
            imp = np.asarray(jax.device_get(acesync.scores_from(
                imp0, ace.struct_feat[0], cfg))).tolist()
        kw = dict(importance=imp, telemetry=telem, omega=omega)
        if self._plan_takes_clusters:
            kw["clusters"] = self.clusters
        self._plan = self.strategy.make_plan(self.trainer.scheduler, **kw)
        return self._plan

    def _swap_plan(self, plan, launched) -> bool:
        self._plan = plan
        if self._host_step is not None:
            self.replan_latencies.append(self._host_step - launched)
        return True

    def poll_replan(self, block: bool = False) -> bool:
        """Apply a pending device replan if its async fetch has landed.
        Returns True when the plan was swapped.

        Signature warm-up: when the fetched assignment crosses a
        size-class boundary (a bucket signature the step cache has not
        compiled), the swap is DEFERRED — the new signature's step is
        AOT-compiled in a background thread (``Trainer.warm_compile``)
        while the loop keeps stepping on the current plan, and the swap
        lands on a later poll once the executable is ready.  A rung/class
        change therefore never stalls the device on a foreground
        compile."""
        if self._warming is not None:
            plan, th, launched = self._warming
            if self._pending_replan is not None \
                    and _device_ready(self._pending_replan[0]):
                # a newer assignment landed while this one was warming:
                # abandon the stale swap (the thread still finishes into
                # the AOT cache) and process the fresh fetch below
                self._warming = None
            else:
                if block:
                    th.join()
                if th.is_alive():
                    return False
                self._warming = None
                return self._swap_plan(plan, launched)
        if self._pending_replan is None:
            return False
        assign, omega, launched = self._pending_replan
        if not block and not _device_ready(assign):
            return False
        idx = np.asarray(jax.device_get(assign)).tolist()
        self._pending_replan = None
        plan = self.trainer.scheduler.plan_from_levels(
            idx, omega, adaptive=True)
        if self.trainer.step_is_warm(plan):
            return self._swap_plan(plan, launched)
        th = threading.Thread(target=self.trainer.warm_compile,
                              args=(plan,), daemon=True)
        th.start()
        self._warming = (plan, th, launched)
        if block:
            th.join()
            self._warming = None
            return self._swap_plan(plan, launched)
        return False

    def adapt_interval(self, state):
        """Sync-interval control (eq 9); a fixed H for static strategies.
        The divergence EMA is fetched lagged (the previous replan's launch
        satisfies this one) so the controller never blocks on the step in
        flight.  ``blocking_replans`` mode reads it synchronously instead
        — the H trajectory is then a pure function of the trajectory of
        states, which is what makes restart-replay bit-identical."""
        div_now = state["ace"].div_ema[0]
        if self.blocking_replans:
            return self.strategy.adapt(self.trainer.scheduler,
                                       float(jax.device_get(div_now)))
        prev = self._div_fetch
        self._div_fetch = _to_host_async(div_now)
        if prev is None:
            # no lagged sample yet: leave H untouched rather than feeding
            # the controller a fabricated zero divergence
            return (self.trainer.scheduler.sync_interval
                    if self.strategy.adapts_interval
                    else self.strategy.initial_interval(self.run.acesync))
        return self.strategy.adapt(self.trainer.scheduler,
                                   float(jax.device_get(prev)))

    # ---- preemption-safe checkpoint state -------------------------------
    def _plan_snapshot(self) -> Optional[dict]:
        p = self._plan
        if p is None:
            return None
        return {"level_idx": list(p.level_idx),
                "omega": [float(w) for w in p.omega],
                "sync_interval": int(p.sync_interval),
                "adaptive": bool(p.adaptive)}

    def ckpt_extras(self) -> dict:
        """Everything outside the state pytree a restart needs: the data-
        pipeline position, the active plan, the scheduler's adapted sync
        interval, the cluster controller's warm state and the loop
        counters.  All JSON-able — it rides in the checkpoint manifest."""
        return {
            "pipeline": (self._pipeline.snapshot()
                         if self._pipeline is not None else None),
            "plan": self._plan_snapshot(),
            "scheduler": self.trainer.scheduler.snapshot(),
            "clusters": self.clusters.snapshot(),
            "loop": {"steps_since_sync": int(self._steps_since_sync),
                     "H": None if self._H is None else int(self._H),
                     "n_pods": int(self.trainer.n_pods),
                     "comm_bytes": float(self.comm_bytes)},
        }

    def _restore_extras(self, extras: dict, pipeline):
        if extras.get("pipeline"):
            pipeline.restore(extras["pipeline"])
        if extras.get("scheduler"):
            self.trainer.scheduler.restore_snapshot(extras["scheduler"])
        if extras.get("clusters"):
            self.clusters.restore_snapshot(extras["clusters"])
        lp = extras.get("loop") or {}
        self._steps_since_sync = int(lp.get("steps_since_sync", 0))
        h = lp.get("H")
        self._H = None if h is None else int(h)
        self.comm_bytes = float(lp.get("comm_bytes", 0.0))
        ps = extras.get("plan")
        if ps:
            # rebuilt through the scheduler so bucket signature / ring
            # chunks / segment grids re-derive exactly as they would have
            # mid-run (the scheduler's sync_interval was restored above)
            self._plan = self.trainer.scheduler.plan_from_levels(
                ps["level_idx"], omega=ps["omega"],
                sync_interval=ps.get("sync_interval"),
                adaptive=bool(ps.get("adaptive", False)))

    # ---- fault injection & elastic membership ---------------------------
    def _apply_faults(self, step: int):
        if self.faults is None:
            return
        for ev in self.faults.due(step):
            if ev.kind == F.KILL_POD:
                self._on_pods_dead([ev.target])
            elif ev.kind == F.REJOIN_POD:
                self._on_pod_rejoin(ev.target)
            elif ev.kind == F.CORRUPT_CKPT:
                self.ckpt.wait()
                path = F.corrupt_checkpoint_leaf(
                    self.ckpt.dir, ev.target, seed=ev.step)
                if path:
                    print(f"FAULT step {step}: corrupted {path}",
                          flush=True)
            elif ev.kind == F.DELAY_HEARTBEAT:
                self._hb_delay[ev.target] = max(
                    self._hb_delay.get(ev.target, 0), ev.duration)

    def _on_pods_dead(self, pods):
        for p in pods:
            self.monitor.mark_dead(p)
        if not self.elastic:
            return
        plan = self.planner.on_pod_failure(pods)
        print(f"ELASTIC: pods {sorted(pods)} dead -> fleet P="
              f"{plan.n_pods}", flush=True)
        self._begin_transition(plan.n_pods)

    def _on_pod_rejoin(self, pod: int):
        self.monitor.register(pod)
        if not self.elastic:
            return
        plan = self.planner.on_pod_join(1)
        print(f"ELASTIC: pod {pod} rejoined -> fleet P={plan.n_pods}",
              flush=True)
        self._begin_transition(plan.n_pods)

    def _trainer_for(self, n_pods: int) -> Trainer:
        """The per-pod-count trainer (cached — a rejoin back to a pod
        count the loop has already run reuses the warm jit/AOT caches)."""
        tr = self._trainers.get(n_pods)
        if tr is not None:
            return tr
        mp = self.planner.plan
        shape = (n_pods, mp.data, mp.model)
        need = n_pods * mp.data * mp.model
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(shape, ("pod", "data", "model"),
                         devices=jax.devices()[:need])
        tr = Trainer(self.model, self.run, mesh=mesh,
                     strategy=self.strategy)
        self._trainers[n_pods] = tr
        return tr

    def _begin_transition(self, n_new: int):
        """Stage a membership change: build (or fetch) the new-P trainer,
        re-derive its plan through planexec (ring hops, bucket signature,
        omega at the new fleet size), re-balance the batch, and AOT-warm
        the new signature in a BACKGROUND thread.  The loop keeps
        stepping on the current fleet; the swap lands in
        :meth:`_poll_elastic` once the executable is ready — zero
        foreground recompiles across the transition."""
        if n_new == self.trainer.n_pods or not self.elastic:
            return
        old = self.trainer
        tr = self._trainer_for(n_new)
        # host state rides across: the adapted sync interval prices the
        # new plan exactly where the old fleet left off
        tr.scheduler.restore_snapshot(old.scheduler.snapshot())
        telem, omega = self._policy_inputs(self._host_step or 0)
        kw = dict(importance=None, telemetry=telem, omega=omega)
        if self._plan_takes_clusters:
            kw["clusters"] = self.clusters
        plan = self.strategy.make_plan(tr.scheduler, **kw)
        pipe = self._pipeline
        if pipe is not None:
            rows = self.planner.rebalanced_rows(
                pipe.shape.global_batch, old.n_pods)
            if rows != pipe.shape.global_batch:
                pipe = pipe.resized(rows)
        # make the fresh trainer warmable before it has ever stepped:
        # seed the arg specs the AOT lowering needs from spec pytrees
        kinds = tuple(old._arg_specs) or ("grad_sync",)
        state_specs = tr.state_specs()
        batch_specs = (self.model.input_specs(pipe.shape)
                       if pipe is not None else None)
        if batch_specs is not None:
            for kind in kinds:
                tr.seed_arg_specs(kind, state_specs, batch_specs)
        th = threading.Thread(target=tr.warm_compile, args=(plan,),
                              kwargs={"kinds": kinds}, daemon=True)
        th.start()
        self._elastic_pending = (tr, plan, pipe, th,
                                 self._host_step or 0, n_new)

    def _steady_sharding(self, tr: Trainer):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(tr.mesh, P(tr._fleet_dim))

    def _transfer_state(self, state, tr: Trainer):
        """Move the train state onto the new fleet: host round-trip with
        the leading pod-replica dim cut (pod loss — the dead pod's EF
        residual leaves with it) or tiled (rejoin — the new pod adopts an
        existing pod's residuals/moments), then device_put with the
        steady-state P(fleet) sharding the compiled step consumes, so the
        warmed AOT executable dispatches without a reshard or retrace."""
        n_new = tr.n_pods
        sh = self._steady_sharding(tr)

        def move(x):
            a = np.asarray(jax.device_get(x))
            if a.ndim and a.shape[0] != n_new:
                if a.shape[0] < n_new:
                    reps = [-(-n_new // a.shape[0])] + [1] * (a.ndim - 1)
                    a = np.tile(a, reps)[:n_new]
                else:
                    a = a[:n_new]
            return jax.device_put(a, sh)

        return jax.tree.map(move, state)

    def _poll_elastic(self, state, block: bool = False):
        """Finish a staged membership transition once its background
        AOT warm-up completes.  Returns the (possibly transferred)
        state."""
        if self._elastic_pending is None:
            return state
        tr, plan, pipe, th, launched, n_new = self._elastic_pending
        if block:
            th.join()
        if th.is_alive():
            return state
        self._elastic_pending = None
        state = self._transfer_state(state, tr)
        # pending replans were priced for the OLD fleet (omega length,
        # scheduler identity): drop them; the next refresh replans at P
        self._pending_replan = None
        self._warming = None
        self.trainer = tr
        self.mesh = tr.mesh
        if pipe is not None:
            self._pipeline = pipe
        self._plan = plan
        self.membership_events.append({
            "step": self._host_step, "launched_step": launched,
            "n_pods": n_new, "warm_steps": (self._host_step or 0) - launched,
            "served_from_warm_cache": tr.step_is_warm(plan)})
        print(f"ELASTIC: swapped to P={n_new} at step {self._host_step} "
              f"(warmed in background over "
              f"{(self._host_step or 0) - launched} steps)", flush=True)
        return state

    def _beat_pods(self) -> List[int]:
        out = []
        for pod in self.monitor.alive_pods():
            d = self._hb_delay.get(pod, 0)
            if d > 0:
                self._hb_delay[pod] = d - 1
                continue
            out.append(pod)
        return out

    # ---- main loop ------------------------------------------------------
    def _flush_metrics(self, inflight, log_every):
        metrics, rec, idx = inflight
        rec.update({k: float(jax.device_get(v)) for k, v in metrics.items()})
        self.history.append(rec)
        if log_every and idx % log_every == 0:
            print(f"step {rec['step']:5d} "
                  f"loss={rec.get('loss', float('nan')):.4f} "
                  f"H={rec['H']} host_dt={rec['dt']:.2f}s", flush=True)

    def run_steps(self, state, pipeline, n_steps: int,
                  log_every: int = 10):
        run = self.run
        cfg = run.acesync
        self._pipeline = pipeline
        H = (self._H if self._H is not None
             else self.strategy.initial_interval(cfg))
        # one synchronous fetch to seed the host step mirror
        self._host_step = int(jax.device_get(
            jax.tree.leaves(state["step"])[0].reshape(-1)[0]))
        if self._plan is None:
            self.refresh_plan(state, self._host_step)
            if self.blocking_replans:
                self.poll_replan(block=True)
        inflight = None
        for i in range(n_steps):
            step = self._host_step
            self._apply_faults(step)
            with obs.span("loop.elastic"):
                state = self._poll_elastic(state,
                                           block=self.blocking_replans)
            with obs.span("loop.poll"):
                self.poll_replan()
            if step and step % cfg.replan_every == 0:
                with obs.span("loop.replan"):
                    self.refresh_plan(state, step)
                    if self.blocking_replans:
                        self.poll_replan(block=True)
                    H = self.adapt_interval(state)
                self._H = H
            with obs.span("loop.data"):
                batch = next(self._pipeline)
            t0 = time.perf_counter()
            kinds = self.strategy.step_schedule(self._steps_since_sync, H)
            metrics = {}
            with obs.span("loop.dispatch"):
                for kind in kinds:
                    state, m = self.trainer.step(state, batch, self._plan,
                                                 kind)
                    metrics.update(m)
                    self.comm_bytes += self.strategy.wire_bytes(
                        self.trainer.scheduler, self._plan, kind)
                    if kind in STEP_ADVANCING:
                        self._host_step += 1
            if SYNC_KINDS & set(kinds):
                self._steps_since_sync = 0
            else:
                self._steps_since_sync += 1
            # lagged metrics: materialise step t's record while step t+1
            # is already dispatched — the host never waits on the step in
            # flight
            jax.tree.map(_to_host_async, metrics)
            if inflight is not None:
                with obs.span("loop.flush"):
                    self._flush_metrics(inflight, log_every)
            # host loop time of the step (dispatch and the lagged flush of
            # the step before), not its device time: the heartbeat's input
            dt = time.perf_counter() - t0
            with obs.span("loop.health"):
                for pod in self._beat_pods():
                    self.monitor.beat(pod, dt)
                newly_dead = self.monitor.check()
                if newly_dead:
                    self._on_pods_dead(newly_dead)
            inflight = (metrics, dict(step=step, dt=dt, H=H), i)
            done = self._host_step  # state now holds the post-step counter
            if run.ckpt_every and done % run.ckpt_every == 0:
                with obs.span("loop.ckpt"):
                    self.ckpt.save(done, state, extras=self.ckpt_extras())
        if inflight is not None:
            with obs.span("loop.flush"):
                self._flush_metrics(inflight, log_every)
        return state

    def restore_or_init(self, rng, pipeline):
        if self.ckpt.latest_step() is not None:
            tmpl = self.trainer.state_specs()
            sh = (self.trainer.state_shardings() if self.mesh is not None
                  else None)
            state, extras = self.ckpt.restore(tmpl, shardings=sh)
            self._restore_extras(extras, pipeline)
            restored_step = int(jax.device_get(
                jax.tree.leaves(state["step"])[0].reshape(-1)[0]))
            print(f"restored checkpoint @ step {restored_step}")
            return state
        state = self.trainer.init_state(rng)
        if self.mesh is not None:
            state = jax.device_put(state, self.trainer.state_shardings())
        return state


def trace_window(spec: Optional[str], steps: int):
    """``"A:B"`` -> (A, B) clamped to [0, steps]; None -> the whole run."""
    if not spec:
        return 0, steps
    lo, _, hi = spec.partition(":")
    a = max(0, min(int(lo) if lo else 0, steps))
    b = max(0, min(int(hi) if hi else steps, steps))
    if a >= b:
        raise ValueError(f"--trace-steps {spec!r}: no step in {a}:{b}")
    return a, b


def run_traced(sess, steps: int, trace_dir: str,
               window: Optional[str] = None) -> None:
    """Run ``steps`` host steps with the program's spans on and a
    profiler trace over host steps ``window``; write ``spans.json``
    (``obs.export()``) and, for each step kind run, the step's optimised
    HLO (``step_hlo.<kind>.txt``: instruction names to the ``op_name``
    scopes the trace's op events lack)."""
    a, b = trace_window(window, steps)
    os.makedirs(trace_dir, exist_ok=True)
    obs.enable()
    if a:
        sess.run(a)
    jax.profiler.start_trace(trace_dir)
    try:
        sess.run(b - a)
        jax.block_until_ready(sess.state)
    finally:
        jax.profiler.stop_trace()
    if steps > b:
        sess.run(steps - b)
    with open(os.path.join(trace_dir, "spans.json"), "w") as f:
        json.dump(obs.export(), f)
    for kind in STEP_KINDS:
        try:
            text = sess.trainer.step_hlo_text(sess.loop.plan, kind)
        except ValueError:
            continue        # a kind this run never stepped
        with open(os.path.join(trace_dir, f"step_hlo.{kind}.txt"), "w") as f:
            f.write(text)


def main():
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.session import TrainSession

    enable_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-350m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--strategy", default="acesync",
                    choices=list_strategies())
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the model's depth, keeping its widths")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=None,
                    help="checkpoint cadence in steps (default: RunConfig)")
    ap.add_argument("--trace-dir", default=None,
                    help="record the program's spans and a profiler trace "
                         "into this directory (spans.json, step_hlo.<kind>"
                         ".txt, the trace under plugins/profile/)")
    ap.add_argument("--trace-steps", default=None, metavar="A:B",
                    help="host steps the profiler records, A inclusive to "
                         "B exclusive (default: every step)")
    args = ap.parse_args()

    run_kw = {}
    if args.ckpt_every is not None:
        run_kw["ckpt_every"] = args.ckpt_every
    sess = TrainSession.from_config(
        args.arch, strategy=args.strategy, smoke=args.smoke,
        seq_len=args.seq_len, batch=args.batch, steps=args.steps,
        n_layers=args.layers, warmup_steps=10, ckpt_dir=args.ckpt_dir, **run_kw)
    if args.trace_dir is None:
        sess.run(args.steps)
    else:
        run_traced(sess, args.steps, args.trace_dir, args.trace_steps)
    sess.finish()
    losses = sess.losses
    print(json.dumps({"first_loss": losses[0], "last_loss": losses[-1],
                      "steps": len(losses),
                      "comm_bytes": sess.comm_bytes}))


if __name__ == "__main__":
    main()
