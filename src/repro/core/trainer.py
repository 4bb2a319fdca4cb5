"""Distributed trainer: assembles model + optimizer + ACE-Sync into per-pod
train steps (one shard_map manual over every mesh axis on pod meshes;
"data"/"model" auto under XLA SPMD on single-pod meshes).

Step kinds
----------
  grad_sync   loss/grad -> ACE-Sync compressed pod aggregation -> AdamW.
              The representative fused step (used by the dry-run).
  local       loss/grad -> AdamW, NO pod traffic (H>1 local steps; pods
              diverge on purpose — paper's edge-side accumulation).
  delta_sync  compress + aggregate (theta - anchor) across pods, reset the
              anchor (ACE-Sync local-update mode / FedAvg with EF).
  param_avg   plain omega-weighted parameter averaging (FedAvg baseline).

Strategies are first-class :class:`repro.strategies.SyncStrategy` objects
(paper Table 1's fullsync/topk/fedavg/acesync plus any registered
extension) — each one a (plan, step-kind schedule) policy over the same
machinery.  The trainer only executes step kinds; every strategy decision
(anchor state, plan construction, scheduling, H control) lives on the
strategy object resolved from the registry.

Plan-as-data: the compiled step takes the plan as an
:class:`~repro.core.planexec.ExecPlan` pytree argument — gather perms and
omega are device data, only the padded bucket signature is static — so it
is compiled once per (model, ladder, signature, kind) and steady-state
replans swap plan vectors through the warm jit cache with **zero**
retraces (tests/test_replan.py pins this).  Train state is donated
through every step (``donate_argnums``), so params / optimizer moments /
error-feedback buffers update in place instead of being copied each step.

State layout: every leaf carries a leading pod-replica dim (n_pods, ...)
sharded P("pod", ...), which is what lets pods hold *divergent* values
between syncs while remaining one SPMD program.
"""
from __future__ import annotations

import functools
import threading
from typing import Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.configs.base import RunConfig
from repro.core import acesync
from repro.core import planexec
from repro.core import sync as S
from repro.core import divergence as D
from repro.core.planexec import ExecPlan, build_exec_plan
from repro.core.scheduler import Scheduler, SyncPlan
from repro.models.shardctx import use_shard_ctx, sharding_for
from repro.optim import adamw
from repro.strategies import SyncStrategy, resolve_strategy

POD = S.POD_AXIS
EDGE = S.EDGE_AXIS


def _n_pods(mesh: Optional[Mesh]) -> int:
    """FLEET size: pod axis x the optional intra-cluster edge axis."""
    return S._pod_info(mesh)


def _n_edge(mesh: Optional[Mesh]) -> int:
    if mesh is None or EDGE not in mesh.axis_names:
        return 1
    return mesh.shape[EDGE]


def _pod_prefix(spec: P, rank: int, axes=POD) -> P:
    """P(axes, *spec) padded with None to the leaf rank — the fleet
    replica dim is sharded over ("pod", "edge") on hierarchical meshes
    (pod-major, matching the fleet slot indexing)."""
    rest = list(spec) + [None] * (rank - 1 - len(spec))
    return P(axes, *rest[: rank - 1])


def _array_spec(x):
    """ShapeDtypeStruct carrying the array's sharding — the ONE spec
    builder the AOT warm-up lowers against and the dry-run/plan specs
    reuse, so recorded call-time specs can never diverge from the warmed
    lowering."""
    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                sharding=getattr(x, "sharding", None))


class Trainer:
    #: max distinct assignments whose ExecPlan (device perm arrays) stays
    #: resident; beyond this the oldest is evicted and rebuilt on demand.
    _EXEC_CACHE_MAX = 8

    def __init__(self, model, run: RunConfig, mesh: Optional[Mesh] = None,
                 strategy: Union[str, SyncStrategy] = "acesync"):
        self.model = model
        self.run = run
        self.mesh = mesh
        self.strategy = resolve_strategy(strategy)
        self.strategy_name = self.strategy.name
        # n_pods is the FLEET size (pod x edge); a hierarchical mesh adds
        # the fast intra-cluster "edge" axis and hier-capable rungs sync
        # two-tier (intra aggregation + one payload per cluster crossing
        # the slow pod axis — see core/sync.py)
        self.n_pods = _n_pods(mesh)
        self.n_edge = _n_edge(mesh)
        self.fleet_axes = S.fleet_axes(mesh) or (POD,)
        self._fleet_dim = (self.fleet_axes if len(self.fleet_axes) > 1
                           else self.fleet_axes[0])
        self.param_specs = model.param_specs()
        self.param_shardings = model.param_shardings()
        self.metas = S.group_metas(self.param_specs)
        self.scheduler = Scheduler(run.acesync,
                                   [m.size for m in self.metas],
                                   self.n_pods, n_edge=self.n_edge)
        # per-group element counts of the layout the exchange runs on
        # (local shard sizes under the nested data/model-manual region),
        # and the block layout derived from them — both computed ONCE here
        # and threaded through every replan (TrainLoop / exec_plan) so a
        # replan poll never re-walks the param pytree
        self.local_sizes = S.local_group_sizes(
            self.param_specs, self.param_shardings, mesh)
        self.leaf_layout = planexec.leaf_layout(self.local_sizes,
                                                run.acesync.topk_block)
        self._step_cache: Dict = {}    # (levels, sig, block, kind) -> jit fn
        self._exec_cache: Dict = {}    # (levels, level_idx, adaptive) -> EP
        self._aot_cache: Dict = {}     # (static_key, kind) -> AOT Compiled
        self._arg_specs: Dict = {}     # kind -> (state_specs, batch_specs)
        # guards the build-and-evict sequences of the plan/AOT caches:
        # warm_compile runs them from a background thread while the
        # foreground step evicts the same dicts
        self._cache_lock = threading.Lock()
        #: AOT compilations performed by warm_compile (telemetry: the
        #: compiles the speculative replan warm-up moved off the
        #: foreground step; benchmarks record it)
        self.warm_compiles = 0

    # ------------------------------------------------------------------
    # state
    # ------------------------------------------------------------------
    def init_state(self, rng):
        params = self.model.init(rng)
        opt = adamw.init_opt_state(params)
        ace = acesync.init_state(rng, params, self.param_specs,
                                 self.run.acesync)
        state = {"params": params, "m": opt["m"], "v": opt["v"],
                 "step": jnp.zeros((), jnp.int32), "ace": ace}
        state.update(self.strategy.extra_state(params))
        # add the pod-replica leading dim
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (self.n_pods,) + x.shape),
            state)

    def state_specs(self):
        """ShapeDtypeStruct pytree of the train state (dry-run)."""
        params = self.param_specs
        ace = acesync.state_specs(params, self.run.acesync)
        state = {"params": params, "m": params, "v": params,
                 "step": jax.ShapeDtypeStruct((), jnp.int32), "ace": ace}
        state.update(self.strategy.extra_state_specs(params))
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((self.n_pods,) + s.shape, s.dtype),
            state)

    def state_shardings(self):
        """NamedSharding pytree matching :meth:`state_specs`."""
        mesh = self.mesh
        assert mesh is not None

        def leaf_spec(tmpl_spec, leaf):
            return sharding_for(mesh, _pod_prefix(tmpl_spec,
                                                  len(leaf.shape),
                                                  self._fleet_dim),
                                shape=leaf.shape)

        params_sh = jax.tree.map(
            lambda sp, l: leaf_spec(sp, l), self.param_shardings,
            jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                (self.n_pods,) + s.shape, s.dtype), self.param_specs),
            is_leaf=lambda x: isinstance(x, P))
        specs = self.state_specs()

        def other(leaf):
            return sharding_for(mesh, _pod_prefix(P(), len(leaf.shape),
                                                  self._fleet_dim),
                                shape=leaf.shape)

        sh = {"params": params_sh, "m": params_sh, "v": params_sh,
              "step": jax.tree.map(other, specs["step"]),
              "ace": jax.tree.map(other, specs["ace"])}
        # error buffers follow the param sharding
        sh["ace"] = sh["ace"]._replace(errors=params_sh)
        # strategy extra state (e.g. the anchor) is param-like by contract
        for key in self.strategy.extra_state_specs(self.param_specs):
            sh[key] = params_sh
        return sh

    def batch_shardings(self, shape):
        mesh = self.mesh
        sp = self.model.input_shardings(shape)
        specs = self.model.input_specs(shape)
        return jax.tree.map(
            lambda s, spec: sharding_for(mesh, s, shape=spec.shape),
            sp, specs, is_leaf=lambda x: isinstance(x, P))

    # ------------------------------------------------------------------
    # the per-pod step bodies
    # ------------------------------------------------------------------
    def _split_pod(self, tree):
        return jax.tree.map(lambda x: x[0], tree)

    def _join_pod(self, tree):
        return jax.tree.map(lambda x: x[None], tree)

    def _pmean(self, x):
        return jax.lax.pmean(x, self.fleet_axes) if self.n_pods > 1 else x

    def _grad_step(self, params, batch):
        run = self.run

        def loss_fn(p):
            # the backward pass is this scope's transpose:
            # transpose(jvp(forward)) in the ops' op_name
            with jax.named_scope("forward"):
                return self.model.loss(p, batch)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        with jax.named_scope("optimizer"):
            if run.grad_clip > 0:
                grads, gnorm = adamw.clip_by_global_norm(grads,
                                                         run.grad_clip)
            else:
                # grad_clip <= 0 disables clipping.  The global-norm scale
                # couples every grad leaf to the whole backward pass,
                # which serializes the backward-interleaved exchange: no
                # segment's collective can issue before the last backward
                # op.  The norm itself is still recorded (metrics only —
                # outputs never gate the rung collectives).
                gnorm = adamw.global_norm(grads)
        return loss, grads, gnorm

    def _optimize(self, params, grads, m, v, step):
        run = self.run
        with jax.named_scope("optimizer"):
            lr = adamw.cosine_schedule(step, base_lr=run.lr,
                                       warmup=run.warmup_steps,
                                       total=run.total_steps)
            return adamw.adamw_update(
                params, grads, {"m": m, "v": v}, step, lr=lr,
                beta1=run.beta1, beta2=run.beta2,
                weight_decay=run.weight_decay)

    def _body_grad_sync(self, state, batch, plan: ExecPlan):
        st = self._split_pod(state)
        loss, grads, gnorm = self._grad_step(st["params"], batch)
        run = self.run
        if run.acesync.overlap_apply:
            # rung-ordered apply: AdamW runs on each rung's bucket the
            # moment that rung's exchange lands (no data dependence on
            # the later rungs' collectives), so the optimizer FLOPs hide
            # behind the next rung's DCN transfer instead of waiting on a
            # whole-tree barrier after sync_tree.  Same elementwise math
            # as _optimize, on the exchange's (S, block) f32 rows.
            with jax.named_scope("optimizer"):
                lr = adamw.cosine_schedule(st["step"], base_lr=run.lr,
                                           warmup=run.warmup_steps,
                                           total=run.total_steps)
                bc1, bc2 = adamw.bias_corrections(st["step"], run.beta1,
                                                  run.beta2)

            def apply_rows(g_rows, aux_rows, scalars):
                p, m, v = aux_rows
                lr_s, bc1_s, bc2_s = scalars
                with jax.named_scope("optimizer"):
                    return adamw.update_rows(
                        p, g_rows, m, v, lr=lr_s, bc1=bc1_s, bc2=bc2_s,
                        beta1=run.beta1, beta2=run.beta2,
                        weight_decay=run.weight_decay)

            out, new_ace, metrics = acesync.sync_gradients(
                grads, st["ace"], plan, mesh=self.mesh,
                shardings=self.param_shardings, cfg=run.acesync,
                apply_fn=apply_rows,
                apply_aux=(st["params"], st["m"], st["v"]),
                apply_scalars=(lr, bc1, bc2))
            new_params, new_m, new_v = out
            new_st = dict(st, params=new_params, m=new_m, v=new_v,
                          step=st["step"] + 1, ace=new_ace)
        else:
            agg, new_ace, metrics = acesync.sync_gradients(
                grads, st["ace"], plan, mesh=self.mesh,
                shardings=self.param_shardings, cfg=run.acesync)
            new_params, opt = self._optimize(st["params"], agg, st["m"],
                                             st["v"], st["step"])
            new_st = dict(st, params=new_params, m=opt["m"], v=opt["v"],
                          step=st["step"] + 1, ace=new_ace)
        metrics = dict(metrics, loss=self._pmean(loss),
                       grad_norm=self._pmean(gnorm))
        return self._join_pod(new_st), metrics

    def _body_local(self, state, batch, plan: ExecPlan):
        st = self._split_pod(state)
        loss, grads, gnorm = self._grad_step(st["params"], batch)
        new_params, opt = self._optimize(st["params"], grads, st["m"],
                                         st["v"], st["step"])
        new_st = dict(st, params=new_params, m=opt["m"], v=opt["v"],
                      step=st["step"] + 1)
        metrics = {"loss": self._pmean(loss),
                   "grad_norm": self._pmean(gnorm)}
        return self._join_pod(new_st), metrics

    def _body_delta_sync(self, state, batch, plan: ExecPlan):
        """Compress/aggregate (theta - anchor); theta <- anchor + agg.

        With ``overlap_apply`` (default) the anchor update is rung-
        ordered the same way grad_sync's AdamW is: ``sync_tree``'s
        ``apply_fn`` path adds each rung's aggregated delta onto the
        anchor rows the moment that rung's exchange lands, so the anchor
        math of rung r hides behind rung r+1's DCN transfer instead of
        barriering on the whole tree."""
        st = self._split_pod(state)
        with jax.named_scope("exchange"):
            delta = jax.tree.map(lambda p, a: (p - a).astype(p.dtype),
                                 st["params"], st["anchor"])
            div = D.pod_divergence(st["params"], self.mesh)
        if self.run.acesync.overlap_apply:
            def apply_anchor(d_rows, aux_rows, _scalars):
                (a_rows,) = aux_rows
                with jax.named_scope("optimizer"):
                    return (a_rows + d_rows,)

            out, new_ace, metrics = acesync.sync_gradients(
                delta, st["ace"], plan, mesh=self.mesh,
                shardings=self.param_shardings, cfg=self.run.acesync,
                apply_fn=apply_anchor, apply_aux=(st["anchor"],))
            (new_params,) = out
        else:
            agg, new_ace, metrics = acesync.sync_gradients(
                delta, st["ace"], plan, mesh=self.mesh,
                shardings=self.param_shardings, cfg=self.run.acesync)
            with jax.named_scope("optimizer"):
                new_params = jax.tree.map(
                    lambda a, d: (a + d).astype(a.dtype), st["anchor"], agg)
        new_ace = new_ace._replace(
            div_ema=0.9 * st["ace"].div_ema + 0.1 * self._pmean(div))
        new_st = dict(st, params=new_params,
                      anchor=jax.tree.map(jnp.copy, new_params),
                      ace=new_ace)
        metrics = dict(metrics, divergence=self._pmean(div))
        return self._join_pod(new_st), metrics

    def _body_param_avg(self, state, batch, plan: ExecPlan):
        """FedAvg baseline: omega-weighted plain parameter average."""
        st = self._split_pod(state)
        omega = plan.omega
        div = D.pod_divergence(st["params"], self.mesh)

        def avg(p):
            if self.n_pods > 1:
                idx = jax.lax.axis_index(POD)
                if self.n_edge > 1:
                    idx = idx * self.n_edge + jax.lax.axis_index(EDGE)
                return jax.lax.psum(
                    p.astype(jnp.float32) * omega[idx],
                    self.fleet_axes).astype(p.dtype)
            return p

        new_params = jax.tree.map(avg, st["params"])
        new_st = dict(st, params=new_params)
        if "anchor" in new_st:
            new_st["anchor"] = jax.tree.map(jnp.copy, new_params)
        return self._join_pod(new_st), {"divergence": self._pmean(div)}

    _BODIES = {"grad_sync": _body_grad_sync, "local": _body_local,
               "delta_sync": _body_delta_sync, "param_avg": _body_param_avg}

    # ------------------------------------------------------------------
    # plan-as-data compiled step factory
    # ------------------------------------------------------------------
    def exec_plan(self, plan: Union[SyncPlan, ExecPlan]) -> ExecPlan:
        """Lower a host SyncPlan to its executable plan-vector form.

        Cached per distinct assignment (the gather perms are a cheap
        numpy build + one tiny upload); omega is refreshed on every call —
        it is device data and never keys the cache.  Adaptive plans use
        the padded size-class ladder so successive replans keep the same
        bucket signature and therefore the same compiled step.
        """
        if isinstance(plan, ExecPlan):
            return plan
        key = (plan.levels, plan.level_idx, plan.adaptive)
        ep = self._exec_cache.get(key)
        if ep is None:
            cfg = self.run.acesync
            growth = self.scheduler.pad_growth if plan.adaptive else None
            # backward-interleaved streaming: segment the exchange so each
            # piece's encode+collective issues as soon as its leaf range's
            # grads materialise in backward (0 = planexec.auto_segments)
            segments = planexec.config_segments(cfg)
            ep = build_exec_plan(plan, layout=self.leaf_layout,
                                 growth=growth, n_pods=self.n_pods,
                                 ring=planexec.ring_override(
                                     cfg.ring_chunks),
                                 bidir=cfg.ring_bidir,
                                 n_edge=self.n_edge,
                                 hier=planexec.hier_override(
                                     getattr(cfg, "hier_mode", 0)),
                                 segments=segments)
            # bounded: adaptive runs see a fresh assignment nearly every
            # replan, and each entry holds O(total_blocks) device perms —
            # evict oldest-first, rebuilding is a cheap numpy pass.  The
            # lock keeps the evict-and-insert atomic against the
            # background warm_compile thread.
            with self._cache_lock:
                while len(self._exec_cache) >= self._EXEC_CACHE_MAX:
                    self._exec_cache.pop(next(iter(self._exec_cache)))
                self._exec_cache[key] = ep
        return ep.with_omega(plan.omega)

    def jit_step(self, plan: Union[SyncPlan, ExecPlan],
                 kind: str = "grad_sync") -> Callable:
        """The compiled step for the plan's bucket signature: a jitted
        ``fn(state, batch, exec_plan) -> (state, metrics)`` with the train
        state donated.  One cache entry per (ladder, signature, kind) —
        replans that keep the signature reuse it with zero retraces."""
        ep = self.exec_plan(plan)
        key = (ep.static_key(), kind)
        fn = self._step_cache.get(key)
        if fn is not None:
            return fn
        body = functools.partial(self._BODIES[kind], self)
        mesh = self.mesh

        if mesh is None:
            fn = jax.jit(body, donate_argnums=(0,))
        elif POD not in mesh.axis_names:
            # single-pod mesh: no pod axis to shard_map over; the body's
            # nested data/model shard_maps still apply.
            def wrapped_sp(state, batch, plan_vec):
                with use_shard_ctx(mesh):
                    return body(state, batch, plan_vec)
            fn = jax.jit(wrapped_sp, donate_argnums=(0,))
        else:
            state_specs = self.state_specs()
            fleet = self._fleet_dim
            state_in = jax.tree.map(lambda l: P(fleet), state_specs)
            # plan vectors (gather perms + omega) ride replicated into the
            # per-pod manual region
            plan_in = jax.tree.map(lambda _: P(), ep)
            # fully manual over every mesh axis: each device runs its pod's
            # step on the pod's whole state and batch (data/model-
            # replicated compute).  A region manual over the fleet axes
            # only, with data/model auto, aborts XLA's SPMD partitioner on
            # meshes where both data and model exceed 1.

            def wrapped(state, batch, plan_vec):
                with use_shard_ctx(mesh, exclude=tuple(mesh.axis_names)):
                    return body(state, batch, plan_vec)

            smapped = jax.shard_map(
                wrapped, mesh=mesh,
                in_specs=(state_in, P(fleet), plan_in),
                out_specs=(state_in, P()), check_vma=False)
            fn = jax.jit(smapped, donate_argnums=(0,))
        # setdefault: a background warm_compile thread may race this
        # insert for the same key — both must end up sharing ONE jitted
        # fn, or compile_count() would sum whichever copy survived
        return self._step_cache.setdefault(key, fn)

    def _record_specs(self, kind: str, state, batch):
        """Remember the (state, batch) avals + shardings of this step
        kind once — what warm_compile AOT-lowers against (shapes never
        change within a run).  The batch arrives as an UNCOMMITTED host
        array the live dispatch auto-shards; recording its single-device
        placement verbatim would make every mesh AOT lowering fail on
        "incompatible devices" against the mesh-sharded state, so on a
        pod mesh the batch spec carries the fleet sharding the
        shard_mapped step actually consumes."""
        if kind in self._arg_specs:
            return
        if self.mesh is not None and POD in self.mesh.axis_names:
            # Steady-state shardings, not the live arrays': the step's
            # out_specs pin every state leaf to P(fleet), so leaves still
            # carrying their init-time data/model device_put layout (or an
            # uncommitted batch's single-device placement) would bake a
            # lowering the post-first-step state can never dispatch into.
            sh = NamedSharding(self.mesh, P(self._fleet_dim))

            def spec(x):
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
            self._arg_specs[kind] = (jax.tree.map(spec, state),
                                     jax.tree.map(spec, batch))
            return
        self._arg_specs[kind] = (jax.tree.map(_array_spec, state),
                                 jax.tree.map(_array_spec, batch))

    def seed_arg_specs(self, kind: str, state_like, batch_like):
        """Record the (state, batch) arg specs for ``kind`` WITHOUT a live
        step — ``state_like`` / ``batch_like`` may be ShapeDtypeStruct
        pytrees (only shape/dtype are read on a pod mesh).  The elastic
        membership path uses this to make a freshly-built new-P trainer
        :meth:`warm_compile`-able before it has ever stepped, so the whole
        P-change transition compiles in the background."""
        self._record_specs(kind, state_like, batch_like)

    def step(self, state, batch, plan: Union[SyncPlan, ExecPlan],
             kind: str = "grad_sync"):
        """Execute one step kind under ``plan``.  The plan rides as data;
        the compiled step is resolved from the signature-keyed cache —
        or from the AOT cache when :meth:`warm_compile` already built
        this signature's executable in the background."""
        ep = self.exec_plan(plan)
        self._record_specs(kind, state, batch)
        key = (ep.static_key(), kind)
        warmed = self._aot_cache.get(key)
        if warmed is not None:
            # LRU touch: re-insert so eviction (oldest-first insertion
            # order) never drops the signature currently being stepped
            with self._cache_lock:
                if key in self._aot_cache:
                    self._aot_cache[key] = self._aot_cache.pop(key)
            try:
                return warmed(state, batch, ep)
            except (TypeError, ValueError):
                # arg aval/sharding drifted from the warmed lowering —
                # raised by argument validation BEFORE dispatch, so the
                # donated state is untouched: drop the stale executable
                # and fall back.  Anything else (e.g. a runtime fault
                # after dispatch, when the donated buffers are already
                # gone) propagates — re-running would only mask it.
                self._aot_cache.pop(key, None)
        fn = self.jit_step(ep, kind)
        if not obs.enabled():
            return fn(state, batch, ep)
        # traced: every dispatch that compiled is counted; one into an
        # empty jit cache (the usual compile) is a span of its own
        cached = self._fn_cache_size(fn)
        if cached:
            out = fn(state, batch, ep)
        else:
            with obs.span("trainer.compile"):
                out = fn(state, batch, ep)
        if self._fn_cache_size(fn) > cached:
            obs.count("step.compiles")
        return out

    def step_fn(self, plan: Union[SyncPlan, ExecPlan],
                kind: str = "grad_sync") -> Callable:
        """A ``fn(state, batch)`` closure over the plan's vectors — the
        legacy call shape (tests/benchmarks).  NOTE: the train state is
        donated; callers must rebind ``state`` on every call."""
        ep = self.exec_plan(plan)
        fn = self.jit_step(ep, kind)
        return lambda state, batch: fn(state, batch, ep)

    def plan_arg_specs(self, plan: Union[SyncPlan, ExecPlan]):
        """ShapeDtypeStruct pytree of the plan argument (dry-run lowering);
        plan vectors are replicated on the mesh when one is present."""
        ep = self.exec_plan(plan)

        def spec(a):
            sh = (NamedSharding(self.mesh, P())
                  if self.mesh is not None else None)
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)

        return jax.tree.map(spec, ep)

    @staticmethod
    def _fn_cache_size(fn) -> int:
        try:
            return fn._cache_size()
        except Exception:       # pragma: no cover - very old jax
            return 1

    def compile_count(self) -> int:
        """Total traced-and-compiled variants across the step cache — the
        number tests/test_replan.py pins flat across replans.  AOT
        executables from :meth:`warm_compile` are counted separately
        (``warm_compiles``): they never stall the foreground step, which
        is what this count gates.  The list() snapshot keeps the
        iteration safe against a background warm thread inserting via
        jit_step mid-count."""
        return sum(self._fn_cache_size(fn)
                   for fn in list(self._step_cache.values()))

    # ------------------------------------------------------------------
    # speculative signature warm-up (replan-time background compile)
    # ------------------------------------------------------------------
    def step_is_warm(self, plan: Union[SyncPlan, ExecPlan],
                     kinds: Optional[Tuple[str, ...]] = None) -> bool:
        """Whether stepping under ``plan`` would hit a compiled
        executable for every step kind seen so far (``kinds`` narrows
        the check)."""
        ep = self.exec_plan(plan)
        for kind in (kinds if kinds is not None else self._arg_specs):
            key = (ep.static_key(), kind)
            if key in self._aot_cache:
                continue
            fn = self._step_cache.get(key)
            if fn is None or self._fn_cache_size(fn) == 0:
                return False
        return True

    def warm_compile(self, plan: Union[SyncPlan, ExecPlan],
                     kinds: Optional[Tuple[str, ...]] = None) -> bool:
        """AOT-compile the step for ``plan``'s bucket signature against
        the recorded argument specs — safe to run from a background
        thread, so the host replan loop can warm an incoming signature
        BEFORE swapping the plan in and a class-ladder rung change never
        stalls the device on a foreground compile (ROADMAP follow-up).
        Returns True when every requested kind is warm afterwards."""
        with obs.span("trainer.warm_compile"):
            return self._warm_compile(plan, kinds)

    def _warm_compile(self, plan, kinds) -> bool:
        ep = self.exec_plan(plan)
        ok = True
        for kind in (kinds if kinds is not None else tuple(self._arg_specs)):
            key = (ep.static_key(), kind)
            if key in self._aot_cache:
                continue
            fn = self._step_cache.get(key)
            if fn is not None and self._fn_cache_size(fn) > 0:
                continue        # the jit cache already holds it
            specs = self._arg_specs.get(kind)
            if specs is None:
                ok = False      # never stepped this kind: nothing to lower
                continue
            fn = self.jit_step(ep, kind)
            try:
                # plan vectors ride replicated on the mesh — lowering with
                # their live (single-device, committed) placements would
                # conflict with the mesh-sharded state
                compiled = fn.lower(
                    specs[0], specs[1], self.plan_arg_specs(ep)).compile()
            except Exception:   # pragma: no cover - defensive: a failed
                ok = False      # warm-up degrades to a foreground compile
                continue
            with self._cache_lock:
                while len(self._aot_cache) >= self._EXEC_CACHE_MAX:
                    self._aot_cache.pop(next(iter(self._aot_cache)))
                self._aot_cache[key] = compiled
            self.warm_compiles += 1
        return ok

    def step_hlo_text(self, plan: Union[SyncPlan, ExecPlan],
                      kind: str = "grad_sync") -> str:
        """The optimised HLO text of the step executable for ``plan``,
        compiled against the recorded argument specs as
        :meth:`warm_compile` does (a persistent-cache hit once the step
        ran).  Each instruction's ``op_name`` carries the step's named
        scopes: ``forward``, its transpose (the backward pass),
        ``optimizer`` and ``exchange``."""
        ep = self.exec_plan(plan)
        specs = self._arg_specs.get(kind)
        if specs is None:
            raise ValueError(f"no {kind!r} step has run: nothing to lower")
        return self.jit_step(ep, kind).lower(
            specs[0], specs[1], self.plan_arg_specs(ep)).compile().as_text()

    # convenience plans per strategy ------------------------------------
    def default_plan(self, importance=None, bandwidth_mbps: float = 50.0,
                     omega=None) -> SyncPlan:
        """Strategy-owned plan from a synthetic one-device telemetry
        snapshot (the host loop passes real telemetry instead)."""
        return self.strategy.make_plan(
            self.scheduler, importance=importance,
            telemetry=[{"bandwidth_mbps": bandwidth_mbps}], omega=omega)
