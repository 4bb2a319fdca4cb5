"""Retrace-free adaptive replanning (plan-as-data) regression tests.

Pins the three contracts of the planexec refactor:

  1. steady-state replans — distinct level assignments sharing a bucket
     signature — trigger ZERO new train-step compilations (the jit cache
     is keyed on the signature, the perms ride as device data);
  2. the plan vectors are live data: the same compiled step produces
     different (and correct) results under different assignments;
  3. plan-vector execution is output-identical to the legacy static-plan
     path on the seed configs (sync_tree accepts both forms).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKE_ARCHS
from repro.configs.base import ACESyncConfig, RunConfig, ShapeConfig
from repro.core import sync as S
from repro.core import planexec
from repro.core.compression import Level
from repro.core.planexec import (ExecPlan, bucket_signature,
                                 build_exec_plan, pad_block_class)
from repro.core.scheduler import Scheduler, SyncPlan
from repro.core.trainer import Trainer
from repro.data.pipeline import TokenPipeline
from repro.models.registry import build_model

SHAPE = ShapeConfig("replan", 32, 2, "train")


def _trainer(strategy="acesync"):
    cfg = SMOKE_ARCHS["paper-350m"]
    run = RunConfig(model=cfg, shape=SHAPE, total_steps=30, warmup_steps=2,
                    lr=1e-3)
    model = build_model(cfg, run)
    tr = Trainer(model, run, mesh=None, strategy=strategy)
    return tr, TokenPipeline(model, SHAPE, seed=0)


def _same_sig_variants(sched, base_plan, n=3):
    """Distinct assignments sharing ``base_plan``'s compiled-step
    signature — bucket_sig AND (for backward-segmented plans) the
    per-segment seg_sig — via level swaps between equal-block groups."""
    from repro.core.planexec import n_blocks
    idx = list(base_plan.level_idx)
    blocks = [n_blocks(s) for s in sched.sizes]
    variants, seen = [], {tuple(idx)}
    for i in range(len(idx)):
        for j in range(i + 1, len(idx)):
            if blocks[i] == blocks[j] and idx[i] != idx[j]:
                cand = list(idx)
                cand[i], cand[j] = cand[j], cand[i]
                if tuple(cand) in seen:
                    continue
                plan = sched.plan_from_levels(cand, sync_interval=1,
                                              adaptive=True)
                if (plan.bucket_sig == base_plan.bucket_sig
                        and plan.seg_sig == base_plan.seg_sig):
                    variants.append(plan)
                    seen.add(tuple(cand))
            if len(variants) >= n:
                return variants
    return variants


class TestRetraceFree:
    def test_distinct_replans_zero_recompiles(self):
        """>= 3 distinct replans through the compiled step add zero jit
        cache entries after warmup.

        Under the default backward-segmented lowering the compiled-step
        identity is (bucket_sig, seg_sig), so the base assignment mixes
        two rungs inside each segment to admit within-segment swaps;
        cross-segment moves are a NEW signature by design and go through
        the background warm path instead (TestSpeculativeWarm)."""
        tr, pipe = _trainer()
        state = tr.init_state(jax.random.PRNGKey(0))
        plan0 = tr.default_plan(bandwidth_mbps=30.0)
        assert plan0.adaptive and plan0.bucket_sig is not None
        assert plan0.seg_sig is not None, \
            "default lowering should be backward-segmented"
        names = [l.name for l in tr.scheduler.levels]
        a, b = names.index("INT8"), names.index("INT4")
        idx = [a if i % 2 == 0 else b
               for i in range(len(tr.scheduler.sizes))]
        plan = tr.scheduler.plan_from_levels(idx, sync_interval=1,
                                             adaptive=True)
        state, _ = tr.step(state, next(pipe), plan, "grad_sync")
        warm = tr.compile_count()
        assert warm >= 1

        variants = _same_sig_variants(tr.scheduler, plan, n=3)
        assert len(variants) >= 3, \
            "seed config must admit 3 same-signature assignment swaps"
        for p in variants:
            assert p.level_idx != plan.level_idx
            state, m = tr.step(state, next(pipe), p, "grad_sync")
            assert np.isfinite(float(m["loss"]))
        assert tr.compile_count() == warm, \
            f"replanning retraced: {warm} -> {tr.compile_count()}"
        # the inverse permutations differ between the swaps: they ride as
        # data, like the perms
        invs = {b"".join(np.asarray(x).tobytes()
                         for x in tr.exec_plan(p).inv)
                for p in (plan, *variants)}
        assert len(invs) == len(variants) + 1

    def test_omega_is_data_too(self):
        """Changing aggregation weights never recompiles either."""
        tr, pipe = _trainer("fullsync")
        state = tr.init_state(jax.random.PRNGKey(0))
        p1 = tr.scheduler.full_plan(omega=None)
        state, _ = tr.step(state, next(pipe), p1, "grad_sync")
        warm = tr.compile_count()
        p2 = tr.scheduler.full_plan(omega=(1.0,))
        state, _ = tr.step(state, next(pipe), p2, "grad_sync")
        assert tr.compile_count() == warm

    def test_plan_vectors_are_live(self):
        """Same compiled step, different perms -> different sync results:
        the plan is data, not a baked constant."""
        r = np.random.RandomState(0)
        tree = {"a": jnp.asarray(r.randn(2048).astype(np.float32)),
                "b": jnp.asarray(r.randn(2048).astype(np.float32))}
        errors = jax.tree.map(jnp.zeros_like, tree)
        cfg = ACESyncConfig()
        levels = tuple(Level(*l) for l in cfg.levels)
        names = [l.name for l in levels]
        iF, iS = names.index("FULL"), names.index("SKIP")
        sizes = [2048, 2048]

        def run(ep):
            f = jax.jit(lambda t, e, p: S.sync_tree(
                t, e, p, mesh=None, shardings=None, gamma=1.0))
            return f(tree, errors, ep)

        p_ab = build_exec_plan(
            SyncPlan((iF, iS), levels, (1.0,), 1), sizes)
        p_ba = build_exec_plan(
            SyncPlan((iS, iF), levels, (1.0,), 1), sizes)
        assert p_ab.sig == p_ba.sig
        agg1, _ = run(p_ab)
        agg2, _ = run(p_ba)
        # FULL transmits (bf16), SKIP zeroes — and they swap with the perm
        assert float(jnp.abs(agg1["a"]).max()) > 0
        assert float(jnp.abs(agg1["b"]).max()) == 0
        assert float(jnp.abs(agg2["a"]).max()) == 0
        assert float(jnp.abs(agg2["b"]).max()) > 0


class TestSpeculativeWarm:
    def test_warm_compile_avoids_foreground_compile(self):
        """AOT-warming an unseen bucket signature lets the next step run
        it WITHOUT adding a jit-cache entry (the foreground compile the
        replan-time background warm removes)."""
        tr, pipe = _trainer("fullsync")
        state = tr.init_state(jax.random.PRNGKey(0))
        sched = tr.scheduler
        p_full = sched.full_plan()
        state, _ = tr.step(state, next(pipe), p_full, "grad_sync")
        warm = tr.compile_count()
        # a different signature: everything on the INT8 rung
        names = [l.name for l in sched.levels]
        p_int8 = sched.plan_from_levels(
            [names.index("INT8")] * len(sched.sizes))
        assert not tr.step_is_warm(p_int8)
        assert tr.warm_compile(p_int8)
        assert tr.step_is_warm(p_int8)
        assert tr.warm_compiles >= 1
        state, m = tr.step(state, next(pipe), p_int8, "grad_sync")
        assert np.isfinite(float(m["loss"]))
        assert tr.compile_count() == warm, \
            "warmed signature still compiled in the foreground"

    def test_warm_compile_without_specs_is_noop(self):
        tr, _ = _trainer("fullsync")
        plan = tr.scheduler.full_plan()
        # nothing stepped yet: no argument specs to lower against
        assert tr.warm_compile(plan, kinds=("grad_sync",)) is False

    def test_loop_defers_swap_until_warm(self):
        """poll_replan on a cold signature keeps the old plan, launches
        the background warm, and swaps on a later poll — the hosted-loop
        form of the satellite."""
        from repro.launch.train import TrainLoop
        cfg = SMOKE_ARCHS["paper-350m"]
        run = RunConfig(model=cfg, shape=SHAPE, total_steps=16,
                        warmup_steps=2, lr=1e-3, ckpt_every=0,
                        acesync=ACESyncConfig(replan_every=3,
                                              sync_interval_init=2))
        model = build_model(cfg, run)
        loop = TrainLoop(model, run, mesh=None, strategy="acesync")
        pipe = TokenPipeline(model, SHAPE, seed=0)
        state = loop.restore_or_init(jax.random.PRNGKey(0), pipe)
        state = loop.run_steps(state, pipe, 8, log_every=0)
        plan0 = loop.plan
        # hand-roll a pending replan onto a signature the cache has not
        # seen (force every group onto SIGN1)
        sched = loop.trainer.scheduler
        names = [l.name for l in sched.levels]
        assign = jnp.asarray([names.index("SIGN1")] * len(sched.sizes),
                             jnp.int32)
        loop._pending_replan = (assign, None, loop._host_step)
        swapped = loop.poll_replan()
        if not swapped:                     # cold signature: deferred
            assert loop.plan is plan0 and loop._warming is not None
            assert loop.poll_replan(block=True)
        assert loop.plan is not plan0
        assert all(i == names.index("SIGN1") for i in loop.plan.level_idx)
        # every step kind the loop has actually scheduled is warm, and
        # stepping them under the new plan adds no foreground compiles
        kinds = tuple(loop.trainer._arg_specs)
        assert kinds and loop.trainer.step_is_warm(loop.plan, kinds)
        warm = loop.trainer.compile_count()
        for kind in kinds:
            state, _ = loop.trainer.step(state, next(pipe), loop.plan,
                                         kind)
        assert loop.trainer.compile_count() == warm


class TestAsyncReplanLoop:
    def test_device_replan_applies_in_loop(self, tmp_path):
        """The host loop's non-blocking replan path end-to-end: the device
        knapsack runs, the assignment vector lands asynchronously, the
        plan swaps, and training stays finite."""
        from repro.launch.train import TrainLoop
        cfg = SMOKE_ARCHS["paper-350m"]
        run = RunConfig(model=cfg, shape=SHAPE, total_steps=16,
                        warmup_steps=2, lr=1e-3, ckpt_every=0,
                        ckpt_dir=str(tmp_path),
                        acesync=ACESyncConfig(replan_every=3,
                                              sync_interval_init=2))
        model = build_model(cfg, run)
        loop = TrainLoop(model, run, mesh=None, strategy="acesync")
        pipe = TokenPipeline(model, SHAPE, seed=0)
        state = loop.restore_or_init(jax.random.PRNGKey(0), pipe)
        state = loop.run_steps(state, pipe, 14, log_every=0)
        assert len(loop.replan_latencies) >= 2, \
            "async device replans should have been applied"
        assert all(lat >= 0 for lat in loop.replan_latencies)
        assert loop.plan is not None and loop.plan.adaptive
        losses = [h["loss"] for h in loop.history if "loss" in h]
        assert len(losses) == 14 and np.isfinite(losses).all()


class TestPlanVectorParity:
    def test_exec_plan_matches_static_plan(self):
        """Plan-vector execution (padded, perms as data) is output-
        identical to the legacy static-plan trace on the seed ladder."""
        cfg = ACESyncConfig()
        levels = tuple(Level(*l) for l in cfg.levels)
        names = [l.name for l in levels]
        r = np.random.RandomState(3)
        tree = {k: jnp.asarray(r.randn(n).astype(np.float32))
                for k, n in [("a", 1000), ("b", 2048), ("c", 231),
                             ("d", 4096), ("e", 500), ("f", 300)]}
        errors = jax.tree.map(lambda x: jnp.ones_like(x) * 0.05, tree)
        idx = tuple(names.index(n) for n in
                    ["FULL", "INT8", "INT4", "SIGN1", "TOPK10_INT8",
                     "SKIP"])
        plan = SyncPlan(idx, levels, (1.0,), 1)
        sizes = [int(np.prod(v.shape)) for v in tree.values()]

        agg_s, err_s = S.sync_tree(tree, errors, plan, mesh=None,
                                   shardings=None, gamma=0.9)
        ep = build_exec_plan(plan, sizes, growth=1.125)
        agg_d, err_d = S.sync_tree(tree, errors, ep, mesh=None,
                                   shardings=None, gamma=0.9)
        for k in tree:
            np.testing.assert_allclose(np.asarray(agg_s[k]),
                                       np.asarray(agg_d[k]),
                                       rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(np.asarray(err_s[k]),
                                       np.asarray(err_d[k]),
                                       rtol=1e-6, atol=1e-6)

    def test_overlap_apply_matches_barrier_apply(self):
        """The rung-ordered apply (AdamW on each rung's bucket rows via
        sync_tree's apply_fn path, the new default) must match the
        whole-tree _optimize barrier path: same grads, same plan, same
        state -> same params / moments / EF residuals.  Guards the
        pack/gather invariants (intra-block tail padding and the shared
        zero row at index NB stay inert across rungs)."""
        cfg = SMOKE_ARCHS["paper-350m"]

        def run(overlap):
            run_cfg = RunConfig(model=cfg, shape=SHAPE, total_steps=30,
                                warmup_steps=2, lr=1e-3,
                                acesync=ACESyncConfig(
                                    overlap_apply=overlap))
            model = build_model(cfg, run_cfg)
            tr = Trainer(model, run_cfg, mesh=None, strategy="acesync")
            pipe = TokenPipeline(model, SHAPE, seed=0)
            state = tr.init_state(jax.random.PRNGKey(0))
            plan = tr.default_plan(bandwidth_mbps=30.0)
            for _ in range(3):
                state, m = tr.step(state, next(pipe), plan, "grad_sync")
            return state, m

        s_overlap, m_overlap = run(True)
        s_barrier, m_barrier = run(False)
        assert float(m_overlap["loss"]) == float(m_barrier["loss"])
        for key in ("params", "m", "v"):
            for a, b in zip(jax.tree.leaves(s_overlap[key]),
                            jax.tree.leaves(s_barrier[key])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-6, atol=1e-6,
                                           err_msg=key)
        for a, b in zip(jax.tree.leaves(s_overlap["ace"].errors),
                        jax.tree.leaves(s_barrier["ace"].errors)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    def test_delta_sync_overlap_matches_barrier_apply(self):
        """delta_sync's anchor update rung-ordered (sync_tree apply_fn
        path, the new default) must match the whole-tree barrier path:
        same state -> same params / anchor / EF residuals (the ROADMAP
        'anchor path still barriers' item)."""
        cfg = SMOKE_ARCHS["paper-350m"]

        def run(overlap):
            run_cfg = RunConfig(model=cfg, shape=SHAPE, total_steps=30,
                                warmup_steps=2, lr=1e-3,
                                acesync=ACESyncConfig(
                                    overlap_apply=overlap))
            model = build_model(cfg, run_cfg)
            tr = Trainer(model, run_cfg, mesh=None, strategy="fedavg")
            pipe = TokenPipeline(model, SHAPE, seed=0)
            state = tr.init_state(jax.random.PRNGKey(0))
            plan = tr.default_plan(bandwidth_mbps=30.0)
            for kind in ("local", "delta_sync", "local", "delta_sync"):
                state, m = tr.step(state, next(pipe), plan, kind)
            return state, m

        s_o, m_o = run(True)
        s_b, m_b = run(False)
        assert float(m_o["divergence"]) == float(m_b["divergence"])
        for key in ("params", "anchor"):
            for a, b in zip(jax.tree.leaves(s_o[key]),
                            jax.tree.leaves(s_b[key])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-6, atol=1e-6,
                                           err_msg=key)
        for a, b in zip(jax.tree.leaves(s_o["ace"].errors),
                        jax.tree.leaves(s_b["ace"].errors)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    def test_trainer_step_parity_across_plan_forms(self):
        """trainer.step under a SyncPlan equals stepping its ExecPlan."""
        tr, pipe = _trainer()
        batch = next(pipe)
        plan = tr.default_plan(bandwidth_mbps=30.0)
        s1 = tr.init_state(jax.random.PRNGKey(0))
        s2 = tr.init_state(jax.random.PRNGKey(0))
        out1, m1 = tr.step(s1, batch, plan, "grad_sync")
        out2, m2 = tr.step(s2, batch, tr.exec_plan(plan), "grad_sync")
        assert float(m1["loss"]) == float(m2["loss"])
        l1 = jax.tree.leaves(out1["params"])[0]
        l2 = jax.tree.leaves(out2["params"])[0]
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2))


class TestBucketSignature:
    def test_pad_class_properties(self):
        for growth in (1.125, 1.5, 2.0):
            prev = 0
            for nb in range(0, 2000, 7):
                c = pad_block_class(nb, growth)
                assert c >= nb
                assert c >= prev or nb == 0
                if nb > 0:
                    assert c <= max(int(np.ceil(nb * growth)), nb + 1)
                prev = c
        assert pad_block_class(0, 2.0) == 0
        assert pad_block_class(5, 1.0) == 5      # growth 1.0: exact sizes
        assert pad_block_class(5, 2.0) == 8      # power-of-two classes

    def test_signature_absorbs_jitter(self):
        """Small bucket-size jitter between replans stays in class."""
        sig1 = bucket_signature([0, 0, 1], [100 * 1024, 80 * 1024, 1024],
                                2, growth=1.125)
        sig2 = bucket_signature([0, 0, 1], [101 * 1024, 79 * 1024, 1024],
                                2, growth=1.125)
        assert sig1 == sig2

    def test_exec_plan_pads_with_zero_block(self):
        levels = (Level("INT8", 1.0, 8), Level("SKIP", 0.0, 0))
        plan = SyncPlan((0,), levels, (1.0,), 1)
        ep = build_exec_plan(plan, [3000], growth=2.0)
        assert ep.sig == (4, 0)                  # 3 blocks -> class 4
        assert ep.total_blocks == 3
        perm = np.asarray(ep.perms[0])
        assert perm.shape == (4,)
        assert list(perm[:3]) == [0, 1, 2]
        assert perm[3] == ep.total_blocks        # pad -> the zero block


class TestInversePermutation:
    """``ExecPlan.inv`` maps every layout row to the row of bucket space
    (the rung buckets end to end) that holds it."""

    LEVELS = tuple(Level(*l) for l in ACESyncConfig().levels)
    SIZES = [1000, 2048, 231, 4096, 3000, 300, 5000]
    RUNGS = ("FULL", "INT8", "SKIP", "INT8", "FULL", "INT4", "INT8")

    def _plan(self, rungs):
        names = [l.name for l in self.LEVELS]
        return SyncPlan(tuple(names.index(n) for n in rungs), self.LEVELS,
                        (0.5, 0.5), 1)

    @pytest.mark.parametrize("kw", [
        dict(), dict(growth=2.0), dict(growth=1.125, segments=2),
        dict(n_pods=2, ring=2), dict(growth=2.0, segments=3)],
        ids=["exact", "class-padded", "2-segment", "ring", "3-segment"])
    def test_inv_inverts_the_perms(self, kw):
        ep = build_exec_plan(self._plan(self.RUNGS), self.SIZES, **kw)
        if ep.segmented:
            ranges = zip(ep.seg_nb, ep.perms, ep.seg_sig, ep.inv)
        else:
            ranges = [(ep.total_blocks, ep.perms, ep.sig, ep.inv[0])]
        for nb, perms, sig, inv in ranges:
            inv = np.asarray(inv)
            assert inv.shape == (nb,) and inv.dtype == np.int32
            seen, off = [], 0
            for perm, S in zip(perms, [S for S in sig if S]):
                perm = np.asarray(perm)
                real = perm[perm != nb]
                np.testing.assert_array_equal(
                    inv[real], off + np.arange(real.shape[0]))
                seen.append(real)
                off += S
            # every real row in exactly one bucket, never in a pad row
            np.testing.assert_array_equal(np.sort(np.concatenate(seen)),
                                          np.arange(nb))
            assert inv.max() < off

    def test_inv_shape_depends_only_on_the_layout(self):
        shapes = set()
        for rungs in (self.RUNGS, ("INT8",) * 7, ("SKIP", "FULL") * 3
                      + ("INT4",)):
            ep = build_exec_plan(self._plan(rungs), self.SIZES,
                                 growth=1.125, segments=2)
            shapes.add(tuple(np.asarray(x).shape for x in ep.inv))
        assert len(shapes) == 1

    def test_a_row_in_no_bucket_is_refused(self):
        layout = planexec.leaf_layout([2048, 1024])
        with pytest.raises(ValueError, match="exactly one rung bucket"):
            # rung 1 holds leaf 1 but its bucket was given no rows
            planexec._rung_perms((0, 1), layout.nbs, layout.starts,
                                 (2, 0), 0, layout.total_blocks, 0, 2, 2)


class TestChunkGrid:
    LEVELS = (Level("INT8", 1.0, 8), Level("FULL", 1.0, 16),
              Level("SKIP", 0.0, 0))

    def test_chunks_in_static_key_and_pytree_aux(self):
        plan = SyncPlan((0,), (self.LEVELS[0], self.LEVELS[2]), (0.5, 0.5),
                        1)
        ep = build_exec_plan(plan, [8 * 1024], n_pods=2, ring=4)
        assert ep.chunks == (4, 0)
        assert ep.chunks in (ep.static_key()[2],) \
            and ep.static_key()[2] == ep.chunks
        # aux data: a tree-map does not touch the chunk grid
        ep2 = jax.tree.map(lambda x: x, ep)
        assert ep2.chunks == ep.chunks and ep2.sig == ep.sig

    def test_forced_ring_rounds_sig_to_chunk_multiple(self):
        plan = SyncPlan((0,), (self.LEVELS[0], self.LEVELS[2]), (0.5, 0.5),
                        1)
        ep = build_exec_plan(plan, [3 * 1024], n_pods=2, ring=2)
        assert ep.chunks[0] == 2
        assert ep.sig[0] == 4                   # 3 blocks -> 2-chunk pad
        perm = np.asarray(ep.perms[0])
        assert perm[3] == ep.total_blocks       # pad -> the zero block

    def test_heuristic_small_buckets_stay_one_shot(self):
        from repro.core.planexec import ring_chunk_count
        lvl = self.LEVELS[0]
        assert ring_chunk_count(lvl, 4, 2) == 0          # ~4KB payload
        assert ring_chunk_count(lvl, 0, 2) == 0
        assert ring_chunk_count(lvl, 10 ** 4, 1) == 0    # single pod
        # the deterministic accumulation unlocked auto rings on EVERY pod
        # count (P >= 3 folds in exact fixed-point / canonical order, so
        # cross-pod bit-determinism holds) — a DCN-bound rung rings on
        # the 3- and 4-pod meshes too
        assert ring_chunk_count(lvl, 10 ** 4, 3) >= 2
        assert ring_chunk_count(lvl, 10 ** 4, 4) >= 2
        assert ring_chunk_count(lvl, 10 ** 4, 4, ring=2) == 2  # forced ok

    def test_ring_hops_bidirectional_split(self):
        """The bidirectional ring's critical path is two half-rings of
        ceil((P-1)/2) hops; unidirectional keeps P-1."""
        from repro.core.planexec import ring_hops
        for P in range(2, 9):
            assert ring_hops(P, bidir=False) == P - 1
            assert ring_hops(P, bidir=True) == -(-(P - 1) // 2)
        assert ring_hops(1) == 0
        # per-hop wire time is P-independent, so the chosen K matches
        # across directions once a rung rings in both
        from repro.core.planexec import ring_chunk_count
        lvl = self.LEVELS[0]
        k_bi = ring_chunk_count(lvl, 64 * 1024, 4, bidir=True)
        k_uni = ring_chunk_count(lvl, 64 * 1024, 4, bidir=False)
        assert k_bi == k_uni >= 2

    def test_bidir_in_static_key(self):
        """Flipping the ring direction changes the lowered ppermute
        pattern, so it must key the compiled step."""
        plan = SyncPlan((0,), (self.LEVELS[0], self.LEVELS[2]), (0.5, 0.5),
                        1)
        ep_b = build_exec_plan(plan, [8 * 1024], n_pods=2, ring=4,
                               bidir=True)
        ep_u = build_exec_plan(plan, [8 * 1024], n_pods=2, ring=4,
                               bidir=False)
        assert ep_b.bidir and not ep_u.bidir
        assert ep_b.static_key() != ep_u.static_key()
        # aux data: a tree-map round-trips the flag
        ep2 = jax.tree.map(lambda x: x, ep_u)
        assert ep2.bidir == ep_u.bidir

    def test_heuristic_rings_dcn_bound_buckets(self):
        from repro.core.planexec import RING_MAX_CHUNKS, ring_chunk_count
        lvl = self.LEVELS[0]
        # a 64 MB int8 bucket is >> the DCN latency floor
        k = ring_chunk_count(lvl, 64 * 1024, 2)
        assert 2 <= k <= RING_MAX_CHUNKS
        assert k & (k - 1) == 0                  # power-of-two class
        # deterministic in the padded signature: same inputs, same grid
        assert k == ring_chunk_count(lvl, 64 * 1024, 2)

    def test_psum_and_skip_never_ring(self):
        from repro.core.planexec import ring_chunk_count
        assert ring_chunk_count(self.LEVELS[1], 10 ** 5, 2) == 0
        assert ring_chunk_count(self.LEVELS[2], 10 ** 5, 2) == 0
        # even forced
        assert ring_chunk_count(self.LEVELS[1], 10 ** 5, 2, ring=4) == 0

    def test_exec_grid_shared_with_scheduler_pricing(self):
        """Scheduler._finalize and build_exec_plan derive the signature
        from the same exec_grid, chunk rounding included — analytic bytes
        track the executed collectives."""
        cfg = ACESyncConfig(ring_chunks=2)
        sched = Scheduler(cfg, [3 * 1024, 2048], n_pods=2)
        plan = sched.full_plan()
        ep = build_exec_plan(plan, sched.sizes, n_pods=2, ring=2,
                             growth=None)
        assert plan.bucket_sig == ep.sig
        assert plan.ring_chunks == ep.chunks


class TestRungGrowthSchedule:
    def test_large_rungs_get_finer_classes(self):
        from repro.core.planexec import (MIN_RUNG_GROWTH, pad_block_class,
                                         rung_growth,
                                         scheduled_block_class)
        base = 1.125
        # expected (mean over sizes) padding of big rungs: the scheduled
        # ladder's ~3.1% classes beat the flat 12.5% ones (pointwise a
        # flat ladder value can land luckily close, so compare in
        # expectation)
        sizes = range(900, 1150)
        sched = np.mean([(scheduled_block_class(nb, base) - nb) / nb
                         for nb in sizes])
        flat = np.mean([(pad_block_class(nb, base) - nb) / nb
                        for nb in sizes])
        assert sched < flat / 2, (sched, flat)
        # floor regime: padding bounded by ~2x MIN_RUNG_GROWTH's excess
        assert all((scheduled_block_class(nb, base) - nb) / nb
                   <= 2 * (MIN_RUNG_GROWTH - 1.0) for nb in sizes)
        # ...but never finer than the floor: classes must stay wide
        # enough to absorb replan jitter (no per-replan retraces)
        assert rung_growth(10 ** 5, base) == MIN_RUNG_GROWTH

    def test_tiny_rungs_get_coarser_classes(self):
        from repro.core.planexec import RUNG_GROWTH_KNEE, rung_growth
        assert rung_growth(3, 1.125) == 2.0
        assert rung_growth(10, 1.125) == 1.125
        # the whole sub-knee band keeps the flat base: padding bytes are
        # negligible there and narrower classes would only add retraces
        assert rung_growth(RUNG_GROWTH_KNEE, 1.125) == 1.125
        assert rung_growth(1.0, None) is None

    def test_class_map_is_monotone_partition(self):
        """The scheduled class function is a single-ladder partition:
        monotone, idempotent, with above-knee ladder gaps wide enough
        that +-1-block replan jitter cannot force a recompile per replan
        (exhaustive over every nb — the earlier per-nb-growth scheme had
        width-1 and non-monotone classes the strided test missed)."""
        from repro.core.planexec import (MIN_RUNG_GROWTH, RUNG_GROWTH_KNEE,
                                         scheduled_block_class)
        base = 1.125
        prev = 0
        for nb in range(1, 4096):
            cls = scheduled_block_class(nb, base)
            assert cls >= nb
            assert cls >= prev, nb                        # monotone
            assert scheduled_block_class(cls, base) == cls  # idempotent
            prev = cls
        # ladder gaps above the knee: >= ~(base-1)*KNEE blocks, growing
        # to ~3.1% of the class in the floor regime
        c = scheduled_block_class(RUNG_GROWTH_KNEE + 1, base)
        while c < 4096:
            nxt = scheduled_block_class(c + 1, base)
            assert nxt - c >= (base - 1.0) * RUNG_GROWTH_KNEE - 1, (c, nxt)
            if c >= 256:
                assert nxt - c >= 0.5 * (MIN_RUNG_GROWTH - 1) * c, (c, nxt)
            c = nxt

    def test_schedule_classes_bounded(self):
        """The byte-weighted padding bound shrinks with rung size down to
        the MIN_RUNG_GROWTH floor; no class more than doubles its rung."""
        from repro.core.planexec import rung_growth, scheduled_block_class
        for nb in (3, 9, 30, 100, 400, 1500):
            cls = scheduled_block_class(nb, 1.125)
            assert nb <= cls <= 2 * nb, nb
            if nb > 64:  # past the knee: padding well under the flat 12.5%
                assert cls <= np.ceil(nb * 1.07), nb
        assert rung_growth(1500, 1.125) <= rung_growth(100, 1.125) \
            < rung_growth(30, 1.125) < rung_growth(3, 1.125)


class TestSchedulerPlanSig:
    def test_scheduler_attaches_signature(self):
        cfg = ACESyncConfig()
        sched = Scheduler(cfg, [4096, 8192, 1024], n_pods=2)
        full = sched.full_plan()
        assert full.bucket_sig is not None and not full.adaptive
        ada = sched.plan([1.0, 0.5, 0.2], 30.0)
        assert ada.adaptive
        # adaptive signature is padded: never below the exact one
        exact = bucket_signature(ada.level_idx, sched.sizes,
                                 len(sched.levels))
        assert all(p >= e for p, e in zip(ada.bucket_sig, exact))

    def test_padded_pricing_at_least_analytic(self):
        cfg = ACESyncConfig()
        sched = Scheduler(cfg, [10 ** 5] * 5, n_pods=2)
        plan = sched.plan([0.5] * 5, 25.0)
        assert sched.plan_wire_bytes(plan) >= \
            sched.plan_wire_bytes(plan, padded=False)

    def test_sig_not_priced_under_foreign_block(self):
        """A signature counted in the scheduler's block size must not be
        priced at a different block size — pricing falls back to the
        caller's sizes instead."""
        from repro.codecs import plan_wire_bytes
        cfg = ACESyncConfig(topk_block=512)
        sched = Scheduler(cfg, [4096, 2048], n_pods=2)
        plan = sched.full_plan()
        assert plan.bucket_block == 512
        # priced with the default 1024-block: rebuilt from sizes, equal to
        # the exact per-leaf block-aligned total (sizes are multiples)
        got = plan_wire_bytes(plan, sched.sizes, 2)
        assert got == plan.levels[plan.level_idx[0]].wire_bytes(6144, 2)
