"""Hierarchical sync semantics (single-device path; the multi-pod path is
covered by tests/test_multipod.py in a subprocess with 8 virtual devices)."""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKE_ARCHS
from repro.configs.base import ACESyncConfig, RunConfig, ShapeConfig
from repro.core import sync as S
from repro.core.compression import Level
from repro.core.planexec import build_exec_plan, n_blocks
from repro.core.scheduler import Scheduler, SyncPlan
from repro.core.trainer import Trainer
from repro.data.pipeline import TokenPipeline
from repro.kernels.decode import FIXED_POINT_BITS
from repro.models.registry import build_model
from repro.optim import adamw


def _tree(seed=0):
    r = np.random.RandomState(seed)
    return {"a": jnp.asarray(r.randn(64, 32).astype(np.float32)),
            "b": jnp.asarray(r.randn(2000).astype(np.float32))}


def _plan(levels_by_group, omega=(1.0,)):
    cfg = ACESyncConfig()
    sched_levels = [Level(*l) for l in cfg.levels]
    names = [l.name for l in sched_levels]
    idx = tuple(names.index(n) for n in levels_by_group)
    return SyncPlan(idx, tuple(sched_levels), omega, 1)


class TestSyncTree:
    def test_full_level_identity(self):
        tree = _tree()
        errors = jax.tree.map(jnp.zeros_like, tree)
        plan = _plan(["FULL", "FULL"])
        agg, new_e = S.sync_tree(tree, errors, plan, mesh=None,
                                 shardings=None, gamma=1.0)
        for k in tree:
            np.testing.assert_allclose(np.asarray(agg[k]),
                                       np.asarray(tree[k]), rtol=1e-2,
                                       atol=1e-2)  # bf16 wire
            # residual is only bf16 rounding
            assert float(jnp.abs(new_e[k]).max()) < 0.02

    def test_skip_buffers_into_error(self):
        tree = _tree()
        errors = jax.tree.map(jnp.zeros_like, tree)
        plan = _plan(["SKIP", "SKIP"])
        agg, new_e = S.sync_tree(tree, errors, plan, mesh=None,
                                 shardings=None, gamma=1.0)
        for k in tree:
            assert float(jnp.abs(agg[k]).max()) == 0.0
            np.testing.assert_allclose(np.asarray(new_e[k]),
                                       np.asarray(tree[k]), rtol=1e-6)

    def test_topk_residual_partition(self):
        """agg + residual == gamma-weighted EF input (lossless split)."""
        tree = _tree(1)
        errors = jax.tree.map(lambda x: jnp.ones_like(x) * 0.1, tree)
        gamma = 0.7
        plan = _plan(["TOPK10_INT8", "TOPK25_INT8"])
        agg, new_e = S.sync_tree(tree, errors, plan, mesh=None,
                                 shardings=None, gamma=gamma)
        for k in tree:
            ef = np.asarray(tree[k]) + gamma * np.asarray(errors[k])
            np.testing.assert_allclose(np.asarray(agg[k] + new_e[k]), ef,
                                       rtol=1e-4, atol=1e-4)

    def test_error_feedback_accumulates_over_steps(self):
        cfg = ACESyncConfig()
        tree = {"w": jnp.asarray(
            np.random.RandomState(3).randn(4096).astype(np.float32))}
        e = {"w": jnp.zeros(4096, jnp.float32)}
        plan = _plan(["TOPK10_INT8"])
        total = jnp.zeros(4096)
        for _ in range(120):
            agg, e = S.sync_tree(tree, e, plan, mesh=None, shardings=None,
                                 gamma=1.0)
            total = total + agg["w"]
        rel = float(jnp.linalg.norm(total / 120 - tree["w"])
                    / jnp.linalg.norm(tree["w"]))
        assert rel < 0.1, rel


class TestBucketedSync:
    """The codec-refactor behavior: same-level leaves share one fused
    buffer; every wire format in the widened ladder syncs a mixed tree."""

    def _mixed(self, seed=5):
        r = np.random.RandomState(seed)
        return {"a": jnp.asarray(r.randn(1000).astype(np.float32)),
                "b": jnp.asarray(r.randn(64, 32).astype(np.float32)),
                "c": jnp.asarray(r.randn(3, 7, 11).astype(np.float32)),
                "d": jnp.asarray(r.randn(2048).astype(np.float32)),
                "e": jnp.asarray(r.randn(500).astype(np.float32)),
                "f": jnp.asarray(r.randn(300).astype(np.float32))}

    def test_widened_ladder_mixed_plan(self):
        cfg = ACESyncConfig()
        tree = self._mixed()
        errors = jax.tree.map(lambda x: jnp.ones_like(x) * 0.05, tree)
        gamma = 0.9
        plan = _plan(["FULL", "INT8", "INT4", "SIGN1", "TOPK10_INT8",
                      "SKIP"])
        agg, new_e = S.sync_tree(tree, errors, plan, mesh=None,
                                 shardings=None, gamma=gamma)
        for k in tree:
            assert agg[k].shape == tree[k].shape
            assert agg[k].dtype == tree[k].dtype
            ef = np.asarray(tree[k]) + gamma * np.asarray(errors[k])
            if k == "f":  # SKIP: everything lands in the residual
                assert float(jnp.abs(agg[k]).max()) == 0.0
                np.testing.assert_allclose(np.asarray(new_e[k]), ef,
                                           rtol=1e-5, atol=1e-5)
            else:  # lossless transmit/residual split per leaf
                np.testing.assert_allclose(np.asarray(agg[k] + new_e[k]),
                                           ef, rtol=1e-4, atol=1e-4)

    def test_same_level_leaves_bucket_together(self):
        """Leaves sharing a level are compressed as one buffer: entries of
        leaf 'b' land in blocks spanning the a/b boundary, and the result
        still splits back exactly (invariant per leaf)."""
        tree = {"a": jnp.asarray(np.random.RandomState(0)
                                 .randn(1500).astype(np.float32)),
                "b": jnp.asarray(np.random.RandomState(1)
                                 .randn(1500).astype(np.float32))}
        errors = jax.tree.map(jnp.zeros_like, tree)
        plan = _plan(["TOPK10_INT8", "TOPK10_INT8"])
        agg, new_e = S.sync_tree(tree, errors, plan, mesh=None,
                                 shardings=None, gamma=1.0)
        for k in tree:
            np.testing.assert_allclose(np.asarray(agg[k] + new_e[k]),
                                       np.asarray(tree[k]), rtol=1e-5,
                                       atol=1e-5)

    def test_pallas_path_matches_oracle_path(self):
        """sync_tree(use_pallas=True) routes through the fused kernels
        (interpret on CPU) and stays equivalent to the oracle path up to
        documented bisection-tie tolerance."""
        tree = self._mixed(9)
        errors = jax.tree.map(jnp.zeros_like, tree)
        plan = _plan(["INT8", "INT4", "SIGN1", "TOPK10_INT8", "INT8",
                      "SKIP"])
        agg_o, e_o = S.sync_tree(tree, errors, plan, mesh=None,
                                 shardings=None, gamma=1.0,
                                 use_pallas=False)
        agg_p, e_p = S.sync_tree(tree, errors, plan, mesh=None,
                                 shardings=None, gamma=1.0,
                                 use_pallas=True)
        for k in tree:
            a, b = np.asarray(agg_o[k]), np.asarray(agg_p[k])
            close = np.isclose(a, b, rtol=1e-4, atol=1e-4)
            assert (~close).mean() <= 1e-3, k
            ef = np.asarray(tree[k])
            np.testing.assert_allclose(np.asarray(agg_p[k] + e_p[k]), ef,
                                       rtol=1e-4, atol=1e-4)

    def test_wire_bytes_of_plan_buckets(self):
        """Pricing matches the static-shape exchange: every leaf is
        block-aligned (1500 -> 2 blocks), same-level leaves share one
        buffer/collective, and the bucket is priced at its block total."""
        sizes = [1500, 1500, 2048]
        plan = _plan(["TOPK10_INT8", "TOPK10_INT8", "INT8"])
        got = S.wire_bytes_of_plan(plan, sizes, 2)
        lv = {l.name: l for l in plan.levels}
        expect = lv["TOPK10_INT8"].wire_bytes(4 * 1024, 2) \
            + lv["INT8"].wire_bytes(2048, 2)
        assert got == expect


def _scatter_unpack_sync(tree, errors, ep, *, gamma, apply_fn=None,
                         aux=(), scalars=()):
    """The return path the exchange had before the inverse gather, kept as
    the oracle: per leaf range, each rung's outputs scattered into zeroed
    (NB+1, block) buffers through the rung's perm, then the leaves sliced
    back out.  One pod, the jnp codecs."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    e_leaves = treedef.flatten_up_to(errors)
    a_leaves = [treedef.flatten_up_to(a) for a in aux]
    block = ep.block
    omega = jnp.ones((1,), jnp.float32)
    if ep.segmented:
        ranges = [(ep.seg_leaves[s], ep.seg_leaves[s + 1], ep.perms[s],
                   ep.seg_sig[s]) for s in range(len(ep.seg_sig))]
    else:
        ranges = [(0, len(leaves), ep.perms, ep.sig)]

    def unpack(buf, like):
        flat, outs, boff = buf[:-1].reshape(-1), [], 0
        for leaf in like:
            n = math.prod(leaf.shape)
            outs.append(flat[boff * block:boff * block + n]
                        .reshape(leaf.shape).astype(leaf.dtype))
            boff += n_blocks(n, block)
        return outs

    outs = [[] for _ in range(len(aux) if apply_fn else 1)]
    errs = []
    for lo, hi, perms, sig in ranges:
        zrow = jnp.zeros((1, block), jnp.float32)
        fb = jnp.concatenate([S._leaf_blocks(leaves[lo:hi], block), zrow])
        eb = jnp.concatenate([S._leaf_blocks(e_leaves[lo:hi], block),
                              zrow])
        abufs = [jnp.concatenate([S._leaf_blocks(a[lo:hi], block), zrow])
                 for a in a_leaves]
        agg = jnp.zeros(fb.shape, jnp.float32)
        err = jnp.zeros(fb.shape, jnp.float32)
        live = [(r, n) for r, n in enumerate(sig) if n]
        for (r, n), perm in zip(live, perms):
            b_agg, b_err = S._rung_exchange(
                ep.levels[r].codec, fb, eb, perm, omega, omega[0],
                chunks=0, bidir=True, gamma=gamma, n_pods=1, block=block,
                use_pallas=False, fixed_bits=FIXED_POINT_BITS)
            err = err.at[perm].set(b_err.reshape(n, block))
            if apply_fn is None:
                agg = agg.at[perm].set(b_agg.reshape(n, block))
            else:
                rows = apply_fn(b_agg.reshape(n, block),
                                tuple(ab[perm] for ab in abufs), scalars)
                abufs = [ab.at[perm].set(nr) for ab, nr in zip(abufs, rows)]
        errs += unpack(err, e_leaves[lo:hi])
        if apply_fn is None:
            outs[0] += unpack(agg, leaves[lo:hi])
        else:
            for o, ab, a in zip(outs, abufs, a_leaves):
                o += unpack(ab, a[lo:hi])
    trees = [jax.tree_util.tree_unflatten(treedef, o) for o in outs]
    return (tuple(trees) if apply_fn else trees[0],
            jax.tree_util.tree_unflatten(treedef, errs))


def _bits(x):
    return np.asarray(x).view(np.uint32)


class TestReturnPath:
    """The exchange's outputs return to the leaves by one gather per leaf
    through the plan's inverse permutation, bit for bit what scattering
    into zeroed buffers and slicing them apart gave."""

    SIZES = {"a": (1000,), "b": (64, 32), "c": (3, 7, 11), "d": (4096,),
             "e": (3000,), "f": (300,), "g": (0, 3)}
    RUNGS = ("FULL", "INT8", "SKIP", "INT8", "FULL", "INT8", "FULL")

    @pytest.mark.parametrize("apply", [False, True],
                             ids=["agg", "apply"])
    @pytest.mark.parametrize("segments", [1, 2],
                             ids=["barriered", "2-segment"])
    @pytest.mark.parametrize("growth", [None, 2.0],
                             ids=["exact", "class-padded"])
    def test_gather_matches_scatter_unpack(self, apply, segments, growth):
        r = np.random.RandomState(11)
        tree = {k: jnp.asarray(r.randn(*v).astype(np.float32))
                for k, v in self.SIZES.items()}
        errors = jax.tree.map(
            lambda x: jnp.asarray(r.randn(*x.shape).astype(np.float32))
            * 0.05, tree)
        plan = _plan(self.RUNGS)
        sizes = [math.prod(v) for v in self.SIZES.values()]
        assert any(n % 1024 for n in sizes)
        ep = build_exec_plan(plan, sizes, growth=growth, segments=segments)
        assert ep.segmented == (segments == 2)
        ranges = (zip(ep.seg_nb, ep.perms) if ep.segmented
                  else [(ep.total_blocks, ep.perms)])
        # class padding adds pad rows, each gathering the zero row NB
        n_pad = sum(int(np.sum(np.asarray(p) == nb))
                    for nb, ps in ranges for p in ps)
        assert (n_pad > 0) == (growth is not None)
        kw = dict(gamma=0.9)
        if apply:
            aux = (jax.tree.map(lambda x: x * 0.5, tree),
                   jax.tree.map(lambda x: x * 0.1, errors),
                   jax.tree.map(lambda x: jnp.abs(x) * 0.01, tree))
            scalars = (jnp.float32(3e-4), jnp.float32(0.1),
                       jnp.float32(0.05))

            def apply_fn(g_rows, aux_rows, sc):
                p, m, v = aux_rows
                return adamw.update_rows(p, g_rows, m, v, lr=sc[0],
                                         bc1=sc[1], bc2=sc[2])

            kw.update(apply_fn=apply_fn, aux=aux, scalars=scalars)
            got, got_e = S.sync_tree(
                tree, errors, ep, mesh=None, shardings=None, gamma=0.9,
                apply_fn=apply_fn, apply_aux=aux, apply_scalars=scalars)
        else:
            got, got_e = S.sync_tree(tree, errors, ep, mesh=None,
                                     shardings=None, gamma=0.9)
        want, want_e = _scatter_unpack_sync(tree, errors, ep, **kw)
        for a, b in zip(jax.tree.leaves((got, got_e)),
                        jax.tree.leaves((want, want_e))):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_array_equal(_bits(a), _bits(b))

    @pytest.mark.parametrize("strategy", ["fullsync", "acesync"])
    def test_step_has_no_scatter_in_the_exchange(self, strategy):
        cfg = SMOKE_ARCHS["paper-350m"]
        shape = ShapeConfig("return_path", 32, 2, "train")
        run = RunConfig(model=cfg, shape=shape, total_steps=30,
                        warmup_steps=2, lr=1e-3)
        model = build_model(cfg, run)
        tr = Trainer(model, run, mesh=None, strategy=strategy)
        sched = tr.scheduler
        if strategy == "fullsync":
            plan = sched.full_plan()
        else:
            names = [l.name for l in sched.levels]
            idx = [names.index("INT8" if g % 2 else "FULL")
                   for g in range(len(sched.sizes))]
            plan = sched.plan_from_levels(idx, sync_interval=1,
                                          adaptive=True)
        state = tr.init_state(jax.random.PRNGKey(0))
        tr.step(state, next(TokenPipeline(model, shape, seed=0)), plan,
                "grad_sync")
        hlo = tr.step_hlo_text(plan)
        names = re.findall(r'op_name="([^"]*)"', hlo)
        assert any("exchange/unpack" in n for n in names)
        scatters = [line for line in hlo.splitlines()
                    if re.search(r"\sscatter\(", line)
                    and re.search(r'op_name="[^"]*exchange/', line)]
        assert not scatters, scatters[:3]


class TestGroupMeta:
    def test_metas_cover_leaves(self):
        tree = {"embed": jnp.zeros((10, 4)),
                "blocks": {"attn": {"wq": jnp.zeros((2, 4, 4))},
                           "ffn": {"w_gate": jnp.zeros((2, 4, 8))}}}
        metas = S.group_metas(tree)
        assert len(metas) == len(jax.tree.leaves(tree))
        kinds = {m.name: m.kind for m in metas}
        assert kinds["embed"] == "embed"
        assert [m for m in metas if "wq" in m.name][0].kind == "attn"
        assert [m for m in metas if "w_gate" in m.name][0].kind == "mlp"

    def test_stats_shapes(self):
        tree = _tree()
        ma, var, nrm = S.grad_group_stats(tree)
        assert ma.shape == (2,) and var.shape == (2,) and nrm.shape == (2,)


class TestScheduler:
    def test_eq5_monotone_bandwidth(self):
        cfg = ACESyncConfig()
        sched = Scheduler(cfg, [10 ** 6] * 4, n_pods=2)
        from repro.core.scheduler import kept_fraction, compression_level
        fracs = [kept_fraction(cfg, bw) for bw in (5, 50, 200)]
        assert fracs[0] < fracs[1] < fracs[2]  # low bw -> keep less
        comps = [compression_level(cfg, bw) for bw in (5, 50, 200)]
        assert comps[0] > comps[1] > comps[2]  # eq (5) verbatim

    def test_plan_bytes_shrink_with_bandwidth(self):
        cfg = ACESyncConfig()
        sched = Scheduler(cfg, [10 ** 6] * 6, n_pods=2)
        imp = [0.5] * 6
        p_low, p_high = sched.plan(imp, 5.0), sched.plan(imp, 200.0)
        b_low = sched.plan_wire_bytes(p_low)
        b_high = sched.plan_wire_bytes(p_high)
        full = sched.fullsync_wire_bytes()
        assert b_low < b_high
        # the knapsack respects the eq-(5) budget on the analytic floor;
        # the executed (padded) volume exceeds it by at most the size-class
        # growth of the bucket ladder
        assert sched.plan_wire_bytes(p_high, padded=False) <= full
        growth = sched.pad_growth
        assert b_high <= sched.plan_wire_bytes(p_high, padded=False) \
            * growth + len(sched.levels) * 1024 * 4

    def test_adapt_interval_eq9(self):
        cfg = ACESyncConfig(sync_interval_init=4)
        sched = Scheduler(cfg, [10 ** 5], n_pods=2)
        h1 = sched.adapt_interval(divergence=1.0, div_ref=1.0)   # high -> /2
        assert h1 == 2
        h2 = sched.adapt_interval(divergence=0.0001, div_ref=1.0)  # low -> x2
        assert h2 == 4
