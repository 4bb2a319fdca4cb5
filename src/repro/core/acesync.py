"""ACE-Sync public API: state container + the jittable gradient-sync pass
that fuses error feedback (eq 7), compression (eq 6), hierarchical
aggregation (eq 8) and the online importance-estimator update (eqs 3-4).

Usage inside a per-pod train step (see core/trainer.py):

    agg_grads, new_ace = acesync.sync_gradients(
        grads, ace_state, plan, mesh=mesh, shardings=param_shardings,
        cfg=run.acesync)

All heavy tensors (error buffers) are sharded like the parameters; the
estimator state is a few hundred scalars.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple, Union

import jax
import jax.numpy as jnp

from repro.configs.base import ACESyncConfig
from repro.core import importance as imp
from repro.core import sync as S
from repro.core.planexec import ExecPlan
from repro.core.scheduler import Scheduler, SyncPlan


class ACEState(NamedTuple):
    errors: dict            # pytree like params (error-feedback residuals)
    importance: imp.ImportanceState
    struct_feat: jax.Array  # (G, N_STRUCT) static structural features
    div_ema: jax.Array      # divergence EMA scalar
    mse_ema: jax.Array      # estimator fit quality


def init_state(rng, params_like, param_specs, cfg: ACESyncConfig,
               error_dtype=jnp.float32) -> ACEState:
    metas = S.group_metas(param_specs)
    struct = imp.structural_features(
        [{"depth": m.depth, "size": m.size, "kind": m.kind} for m in metas])
    errors = jax.tree.map(
        lambda p: jnp.zeros(p.shape, error_dtype), params_like)
    return ACEState(
        errors=errors,
        importance=imp.init_state(rng, len(metas), cfg.importance_hidden),
        struct_feat=struct,
        div_ema=jnp.zeros((), jnp.float32),
        mse_ema=jnp.zeros((), jnp.float32))


def state_specs(params_specs, cfg: ACESyncConfig,
                error_dtype=jnp.float32) -> ACEState:
    """ShapeDtypeStruct version of init_state (dry-run, no allocation)."""
    metas = S.group_metas(params_specs)
    G = len(metas)
    rng = jax.random.PRNGKey(0)
    small = jax.eval_shape(
        lambda: init_state(rng, jax.tree.map(
            lambda s: jnp.zeros((), s.dtype), params_specs),
            params_specs, cfg))
    errors = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, error_dtype), params_specs)
    return small._replace(errors=errors)


def sync_gradients(grads, state: ACEState, plan: Union[SyncPlan, ExecPlan],
                   *, mesh, shardings, cfg: ACESyncConfig,
                   apply_fn=None, apply_aux=(), apply_scalars=()
                   ) -> Tuple[dict, ACEState, Dict[str, jax.Array]]:
    """The ACE-Sync round. Returns (aggregated grads, new state, metrics).

    With ``apply_fn`` given (see :func:`repro.core.sync.sync_tree`) the
    aggregate is consumed rung by rung — the first return value is then
    the tuple of updated ``apply_aux`` trees instead of the aggregated
    gradients, and the optimizer work overlaps the later rungs'
    exchanges.  The whole round runs under the ``exchange`` named scope
    (``importance`` for the estimator's stats and step; ``core/sync.py``
    names the rest)."""
    with jax.named_scope("exchange"):
        # --- per-group stats for the importance estimator ---
        with jax.named_scope("importance"):
            mean_abs, var, nrm = S.grad_group_stats(grads)
            if S._pod_info(mesh) > 1:
                # one fleet collective for all three (G,) stat vectors —
                # stacked, a single pmean reduces each element exactly as
                # three would
                axes = S.fleet_axes(mesh)
                mean_abs, var, nrm = jax.lax.pmean(
                    jnp.stack([mean_abs, var, nrm]), axes)
            ist = imp.update_stats(state.importance, mean_abs, var, nrm)
            # online supervision: the observed (normalised) gradient-norm
            # momentum is the ground-truth importance signal for this
            # window
            target = ist.norm_mom / jnp.maximum(jnp.max(ist.norm_mom), 1e-12)
            ist, mse = imp.train_step(ist, state.struct_feat, target,
                                      alpha=cfg.alpha, lr=cfg.importance_lr)

        # --- error feedback + compression + pod aggregation ---
        agg, new_errors = S.sync_tree(grads, state.errors, plan, mesh=mesh,
                                      shardings=shardings, gamma=cfg.gamma,
                                      block=cfg.topk_block,
                                      bidir=cfg.ring_bidir,
                                      fixed_bits=cfg.accum_bits,
                                      apply_fn=apply_fn,
                                      apply_aux=apply_aux,
                                      apply_scalars=apply_scalars)

        new_state = state._replace(errors=new_errors, importance=ist,
                                   mse_ema=0.99 * state.mse_ema + 0.01 * mse)
        metrics = {"imp_mse": mse, "grad_norm_mean": jnp.mean(nrm)}
    return agg, new_state, metrics


def current_scores(state: ACEState, cfg: ACESyncConfig) -> jax.Array:
    """Importance scores I(theta_i) (G,) — jittable; consumed by the
    device-resident replan (and, lagged, by host-side telemetry)."""
    return scores_from(state.importance, state.struct_feat, cfg)


def scores_from(importance: imp.ImportanceState, struct_feat,
                cfg: ACESyncConfig) -> jax.Array:
    """Scores from the estimator state alone.  The host replan path calls
    this with just ``ace.importance`` / ``ace.struct_feat`` sliced out, so
    a replan poll never tree-maps over the param-sized error buffers
    riding in the full :class:`ACEState` (host-side replan overhead)."""
    temp = imp.temporal_features(importance)
    return imp.scores(importance.params, temp, struct_feat, cfg.alpha)


def device_replan_fn(scheduler: Scheduler, cfg: ACESyncConfig):
    """The device-resident control plane: one jitted computation
    ``(importance_state, struct_feat, budget_bytes) -> int32[G]`` fusing
    the importance scoring (eqs. 3-4) with the vectorized greedy knapsack,
    so a replan never pulls ``grad_group_stats`` (or anything else) to the
    host — the host fetches only the tiny assignment vector,
    asynchronously.  The inputs are the estimator's few-hundred-scalar
    state, NOT the full ACEState (whose error buffers are param-sized).

    Cached per (scheduler, cfg) — the solver's static tables depend on the
    scheduler's (sizes, ladder, acct_pods) and the closure bakes in
    ``cfg.alpha``."""
    cache = getattr(scheduler, "_device_replan_fns", None)
    if cache is None:
        cache = scheduler._device_replan_fns = {}
    fn = cache.get(cfg)
    if fn is None:
        solver = scheduler.device_solver()

        @jax.jit
        def fn(imp_state, struct_feat, budget_bytes):
            temp = imp.temporal_features(imp_state)
            scores = imp.scores(imp_state.params, temp, struct_feat,
                                cfg.alpha)
            return solver(scores, jnp.asarray(budget_bytes, jnp.float32))

        cache[cfg] = fn
    return fn
