"""Hierarchical cloud-edge coordination: clustering, telemetry, omega.

Fast host-side contracts of the ``repro/hierarchy`` subsystem — k-means
determinism + empty-cluster handling, reliability-weight sanity, the
counter-hashed telemetry replay (pinned golden values), ClusterState
hysteresis — plus the slow subprocess pins of the two-tier exchange: the
analytic ``plan_wire_bytes`` / ``plan_intra_bytes`` accounting equals the
traced HLO collective bytes on BOTH tiers of a simulated heterogeneous
mesh, per-fleet-member aggregates stay bit-identical across cluster
re-assignments, and telemetry-driven replans that re-cluster mid-run add
zero steady-state recompiles."""
import math
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

from repro.core.clustering import (cluster_devices, kmeans,
                                   normalise_profiles, reliability_weights)
from repro.data.telemetry import (bandwidth_at, latency_at, make_profiles,
                                  snapshot, transfer_seconds)
from repro.hierarchy import ClusterState


def _partition(assignments):
    """Cluster labels -> frozenset of frozensets of member indices."""
    by = {}
    for i, a in enumerate(assignments):
        by.setdefault(a, set()).add(i)
    return frozenset(frozenset(v) for v in by.values())


# ---------------------------------------------------------------------------
# k-means
# ---------------------------------------------------------------------------


class TestKMeans:
    def test_converges_on_separated_blobs(self):
        r = np.random.RandomState(0)
        centers = np.array([[0.0, 0.0], [10.0, 0.0], [0.0, 10.0]])
        x = np.concatenate([c + 0.1 * r.randn(20, 2) for c in centers])
        assign, cent = kmeans(x, 3)
        # each blob lands in exactly one cluster
        for b in range(3):
            blob = assign[b * 20:(b + 1) * 20]
            assert len(set(blob.tolist())) == 1, blob
        # and the three blobs get three distinct clusters
        assert len(set(assign.tolist())) == 3
        assert np.isfinite(cent).all()

    def test_empty_cluster_reseeded_from_farthest_point(self):
        # 9 identical points + 1 far outlier with k=3: naive Lloyd's leaves
        # a cluster empty forever; the re-seed must give the outlier (the
        # worst-served point) its own centroid
        x = np.zeros((10, 2))
        x[-1] = [100.0, 100.0]
        assign, cent = kmeans(x, 3)
        assert assign[-1] != assign[0]
        assert np.isfinite(cent).all()
        # the outlier's centroid sits on the outlier
        np.testing.assert_allclose(cent[assign[-1]], x[-1])

    def test_permutation_determinism(self):
        profiles = snapshot(make_profiles(12, seed=5), step=3)
        base = cluster_devices(profiles, 3)
        perm = [7, 0, 11, 4, 2, 9, 1, 10, 5, 8, 3, 6]
        permuted = cluster_devices([profiles[i] for i in perm], 3)
        # device profiles[perm[j]] sits at position j of the permuted run:
        # the induced partition over ORIGINAL indices must be identical
        unpermuted = [None] * len(base)
        for j, i in enumerate(perm):
            unpermuted[i] = permuted[j]
        assert _partition(unpermuted) == _partition(base)

    def test_warm_start_keeps_stable_partition(self):
        x = normalise_profiles(snapshot(make_profiles(10, seed=2), 0))
        a1, c1 = kmeans(x, 3)
        a2, c2 = kmeans(x, 3, init=c1)
        assert _partition(a1.tolist()) == _partition(a2.tolist())


# ---------------------------------------------------------------------------
# reliability weights (paper eq. 8)
# ---------------------------------------------------------------------------


class TestReliabilityWeights:
    def test_softmax_normalised_and_cluster_shared(self):
        telem = snapshot(make_profiles(8, seed=1), 0)
        assign = cluster_devices(telem, 3)
        w = reliability_weights(telem, assign)
        assert all(v > 0 for v in w)
        assert math.isclose(sum(w), 1.0, rel_tol=1e-9)
        # weights are shared within a cluster
        by = {}
        for wi, a in zip(w, assign):
            by.setdefault(a, set()).add(round(wi, 12))
        assert all(len(v) == 1 for v in by.values())

    def test_single_cluster_is_uniform(self):
        telem = snapshot(make_profiles(5, seed=3), 0)
        w = reliability_weights(telem, [0] * 5)
        np.testing.assert_allclose(w, [0.2] * 5, rtol=1e-12)

    def test_zero_bandwidth_device_is_downweighted_not_nan(self):
        telem = [dict(bandwidth_mbps=100.0, latency_ms=50.0, straggle=1.0)
                 for _ in range(3)]
        telem.append(dict(bandwidth_mbps=0.0, latency_ms=50.0, straggle=1.0))
        w = reliability_weights(telem, [0, 0, 0, 1])
        assert all(math.isfinite(v) and v >= 0 for v in w)
        assert math.isclose(sum(w), 1.0, rel_tol=1e-9)
        assert w[3] < w[0] * 1e-3  # effectively muted, never NaN


# ---------------------------------------------------------------------------
# telemetry replay (counter-hashed, deterministic)
# ---------------------------------------------------------------------------


class TestTelemetry:
    def test_pure_function_of_args(self):
        profiles = make_profiles(4, seed=7)
        for p in profiles:
            for step in (0, 1, 123, 10_000):
                assert bandwidth_at(p, step, 7) == bandwidth_at(p, step, 7)
                assert latency_at(p, step, 7) == latency_at(p, step, 7)
        # interleaved call ORDER must not matter (the seed bug this
        # replaces: a shared np.random.RandomState made every call
        # order-dependent)
        a = [bandwidth_at(profiles[0], s, 7) for s in range(8)]
        b = list(reversed([bandwidth_at(profiles[0], s, 7)
                           for s in reversed(range(8))]))
        assert a == b

    def test_golden_values(self):
        profiles = make_profiles(4, seed=7)
        golden = [
            (0, 0, bandwidth_at, 6.028853474056805),
            (0, 123, bandwidth_at, 6.836170237407475),
            (0, 0, latency_at, 271.6884870714287),
            (0, 123, latency_at, 261.45078419990637),
            (1, 0, bandwidth_at, 169.5390496402137),
            (1, 123, bandwidth_at, 177.53702353965846),
            (1, 0, latency_at, 178.7867003927135),
            (1, 123, latency_at, 198.63491350657725),
        ]
        for dev, step, fn, want in golden:
            got = fn(profiles[dev], step, 7)
            assert got == pytest.approx(want, rel=1e-12), (dev, step, fn)

    def test_bounds_and_snapshot_keys(self):
        profiles = make_profiles(16, seed=0)
        for step in (0, 50, 500):
            for t in snapshot(profiles, step):
                assert 5.0 <= t["bandwidth_mbps"] <= 200.0
                assert 10.0 <= t["latency_ms"] <= 300.0
                assert t["straggle"] >= 1.0

    def test_transfer_seconds_pricing(self):
        # 1 MB at 100 Mbps + 20 ms propagation = 80 ms wire + 20 ms
        assert transfer_seconds(1_000_000, 100.0, 20.0) == \
            pytest.approx(0.1, rel=1e-12)
        assert transfer_seconds(0, 100.0, 20.0) == \
            pytest.approx(0.02, rel=1e-12)


# ---------------------------------------------------------------------------
# ClusterState: hysteresis + fleet mapping
# ---------------------------------------------------------------------------


class TestClusterState:
    def test_no_flap_under_jitter_only_telemetry(self):
        # well-separated bandwidth tiers + per-step jitter: re-clustering
        # every step must never move a device once assigned
        profiles = make_profiles(12, seed=4)
        cs = ClusterState(12, k=3, hysteresis=0.15)
        for step in range(0, 120, 5):
            cs.update(snapshot(profiles, step))
        assert cs.updates == 24
        assert cs.churn == 0
        assert cs.reclusters == 0

    def test_zero_hysteresis_tracks_plain_kmeans_moves(self):
        # hysteresis=0 accepts every proposed move: the filter, not the
        # proposal machinery, is what suppresses flapping
        profiles = make_profiles(12, seed=4)
        strict = ClusterState(12, k=3, hysteresis=0.0)
        for step in range(0, 120, 5):
            strict.update(snapshot(profiles, step))
        assert strict.updates == 24  # runs fine; churn may or may not be 0

    def test_drift_eventually_reclusters(self):
        # a device whose profile jumps decisively must cross the
        # hysteresis band and move
        telem = [dict(bandwidth_mbps=200.0, latency_ms=20.0, jitter=0.1,
                      straggle=1.0) for _ in range(4)]
        telem += [dict(bandwidth_mbps=6.0, latency_ms=280.0, jitter=0.1,
                       straggle=1.5) for _ in range(4)]
        cs = ClusterState(8, k=2, hysteresis=0.15)
        cs.update(telem)
        before = list(cs.assignments)
        moved = dict(telem[0])            # device 7 becomes a fast device
        telem2 = telem[:7] + [moved]
        cs.update(telem2)
        assert cs.assignments[7] == before[0]
        assert cs.churn >= 1 and cs.reclusters >= 1

    def test_fleet_slots_round_robin(self):
        cs = ClusterState(8, k=2)
        cs.assignments = [0, 0, 0, 0, 1, 1, 1, 1]
        slots = cs.fleet_slots(n_cross=2, n_edge=2)
        assert slots == [0, 1, 0, 1, 2, 3, 2, 3]

    def test_fleet_omega_normalised_and_fills_empty_slots(self):
        telem = snapshot(make_profiles(8, seed=6), 0)
        cs = ClusterState(8, k=2)
        cs.update(telem)
        om = cs.fleet_omega(telem, 2, 2)
        assert len(om) == 4
        assert math.isclose(sum(om), 1.0, rel_tol=1e-9)
        assert all(v > 0 for v in om)
        # 3 devices onto a 2x4 fleet: the 5+ empty slots get positive fill
        cs3 = ClusterState(3, k=2)
        cs3.update(telem[:3])
        om_wide = cs3.fleet_omega(telem[:3], 2, 4)
        assert len(om_wide) == 8
        assert math.isclose(sum(om_wide), 1.0, rel_tol=1e-9)
        assert all(v > 0 for v in om_wide)

    def test_policies_and_bottleneck(self):
        from repro.configs.base import ACESyncConfig
        telem = snapshot(make_profiles(10, seed=8), 0)
        cs = ClusterState(10, k=3)
        cs.update(telem)
        pols = cs.policies(telem, ACESyncConfig())
        assert sum(len(p.members) for p in pols) == 10
        assert math.isclose(sum(p.omega for p in pols), 1.0, rel_tol=1e-9)
        assert all(0.0 < p.kept_fraction <= 1.0 for p in pols)
        assert cs.bottleneck_bandwidth(telem) == \
            min(p.bandwidth_mbps for p in pols)
        mean_bw = sum(t["bandwidth_mbps"] for t in telem) / len(telem)
        assert cs.bottleneck_bandwidth(telem) <= mean_bw

    def test_update_before_query_raises(self):
        cs = ClusterState(4, k=2)
        with pytest.raises(RuntimeError):
            cs.fleet_omega([], 2, 2)


# ---------------------------------------------------------------------------
# scheduler guard (satellite: loud failure on degenerate omega)
# ---------------------------------------------------------------------------


def test_scheduler_rejects_nonpositive_omega_sum():
    from repro.configs.base import ACESyncConfig
    from repro.core.scheduler import Scheduler
    sched = Scheduler(ACESyncConfig(), [1024, 2048], n_pods=2)
    with pytest.raises(ValueError, match="positive finite sum"):
        sched.full_plan((0.0, 0.0))
    with pytest.raises(ValueError, match="positive finite sum"):
        sched.full_plan((1.0, float("nan")))
    # a valid omega still normalises
    plan = sched.full_plan((1.0, 3.0))
    assert plan.omega == pytest.approx((0.25, 0.75))


def test_scheduler_hier_pricing_cuts_cross_tier_bytes():
    """A hierarchical scheduler prices hier-capable rungs at the cluster
    count: cross-tier bytes drop vs the flat fleet, and the intra tier
    picks up the (cheap, fast-link) difference."""
    from repro.configs.base import ACESyncConfig
    from repro.core.scheduler import Scheduler
    sizes = [4096, 8192, 2048]
    flat = Scheduler(ACESyncConfig(), sizes, n_pods=4)
    hier = Scheduler(ACESyncConfig(), sizes, n_pods=4, n_edge=2)
    assert not flat.hier_enabled
    assert hier.hier_enabled and hier.n_cross == 2
    imp = [1.0, 2.0, 0.5]
    pf = flat.plan(imp, 50.0)
    ph = hier.plan(imp, 50.0)
    assert ph.hier is not None and any(ph.hier)
    assert not any(pf.hier or ())
    # same signature -> strictly fewer cross-tier bytes, non-zero intra
    if pf.bucket_sig == ph.bucket_sig and pf.level_idx == ph.level_idx:
        assert hier.plan_wire_bytes(ph) < flat.plan_wire_bytes(pf)
    assert hier.plan_intra_bytes(ph) > 0
    assert flat.plan_intra_bytes(pf) == 0
    # forcing flat (hier_mode=-1) restores single-tier pricing
    forced = Scheduler(ACESyncConfig(hier_mode=-1), sizes, n_pods=4,
                       n_edge=2)
    assert not forced.hier_enabled
    pfo = forced.plan(imp, 50.0)
    assert not any(pfo.hier or ())


# ---------------------------------------------------------------------------
# two-tier exchange: traced-HLO pin on a simulated heterogeneous mesh
# ---------------------------------------------------------------------------

HIER_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import planexec
from repro.core import sync as S
from repro.core.compression import Level
from repro.core.scheduler import SyncPlan
from repro.launch.mesh import make_mesh
from benchmarks import hlo_cost

MESH_SHAPE, MESH_AXES = (2, 2, 2), ("pod", "edge", "data")
mesh = make_mesh(MESH_SHAPE, MESH_AXES)
FLEET, N_CROSS, N_EDGE = 4, 2, 2

# dense-quantiser ladder: every rung supports the two-tier path
levels = (Level("INT8", 1.0, 8), Level("INT4", 1.0, 4))
sizes = [2048, 3000, 1500]
idx = (0, 1, 0)
omega_a = (0.1, 0.2, 0.3, 0.4)

r = np.random.RandomState(0)
tree = {f"p{i}": jnp.asarray(r.randn(n).astype(np.float32))
        for i, n in enumerate(sizes)}
errors = jax.tree.map(jnp.zeros_like, tree)

# force INTRA_INT8 so the intra tier is an all_gather with exact byte
# accounting (FULL's bf16 psum gets f32-promoted by XLA on CPU)
ep = planexec.build_exec_plan(
    SyncPlan(idx, levels, omega_a, 1), [int(x.size) for x in tree.values()],
    n_pods=FLEET, n_edge=N_EDGE, hier=planexec.hier_override(2))
assert ep.hier and all(h == planexec.INTRA_INT8 for h in ep.hier
                       if h), ep.hier
assert any(h for h in ep.hier), "no two-tier rung chosen"


def inner(t, e, p):
    return S.sync_tree(t, e, p, mesh=mesh, shardings=None, gamma=1.0,
                       inside_manual=True)


pspec = jax.tree.map(lambda _: P(), tree)
smapped = jax.shard_map(
    inner, mesh=mesh,
    in_specs=(pspec, pspec, jax.tree.map(lambda _: P(), ep)),
    out_specs=(pspec, pspec),
    check_vma=False)
fn = jax.jit(smapped)

agg_a, err_a = fn(tree, errors, ep)

# --- per-fleet-member bit-identity (pod-uniformity of the aggregate) ----
for k in tree:
    a = np.asarray(jax.device_get(agg_a[k]))
    assert np.isfinite(a).all(), k

# the aggregate is replicated across the fleet: re-run under a CHANGED
# cluster assignment (different omega slotting) — same compiled fn (omega
# is device data), still finite, and deterministically different
omega_b = (0.4, 0.3, 0.2, 0.1)
agg_b, _ = fn(tree, errors, ep.with_omega(jnp.asarray(omega_b,
                                                      jnp.float32)))
agg_b2, _ = fn(tree, errors, ep.with_omega(jnp.asarray(omega_b,
                                                       jnp.float32)))
for k in tree:
    b1 = np.asarray(jax.device_get(agg_b[k]))
    b2 = np.asarray(jax.device_get(agg_b2[k]))
    assert (b1 == b2).all(), f"{k}: nondeterministic across identical runs"
    assert not (b1 == np.asarray(jax.device_get(agg_a[k]))).all(), \
        f"{k}: omega change had no effect"
assert fn._cache_size() == 1, \
    f"re-clustering retraced the step: {fn._cache_size()} traces"

# --- traced-HLO pin: analytic == traced on BOTH tiers -------------------
txt = fn.lower(tree, errors, ep).compile().as_text()
rep = hlo_cost.analyze(txt, MESH_SHAPE, MESH_AXES)
# price the EXECUTED grid: sig/hier of the lowered plan, cross tier at
# the cluster count, intra tier at the edge-group width
cross_analytic = planexec.sig_wire_bytes(ep.sig, ep.levels, FLEET,
                                         hier=ep.hier, n_cross=N_CROSS)
intra_analytic = planexec.sig_intra_bytes(ep.sig, ep.levels, N_EDGE,
                                          hier=ep.hier)
cross_traced = rep.collective_bytes.get("pod", 0.0)
intra_traced = rep.collective_bytes.get("edge", 0.0)
assert cross_traced == float(cross_analytic), \
    f"cross tier: analytic {cross_analytic} != traced {cross_traced}"
assert intra_traced == float(intra_analytic), \
    f"intra tier: analytic {intra_analytic} != traced {intra_traced}"
# no sync traffic on the data axis or the combined flat fleet axis
for ax, b in rep.collective_bytes.items():
    if ax not in ("pod", "edge"):
        assert b == 0.0, (ax, b)
print("HIER_PIN_OK", int(cross_analytic), int(intra_analytic))
"""


RECLUSTER_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
from repro.configs.base import ACESyncConfig
from repro.launch.mesh import make_mesh
from repro.launch.session import TrainSession

mesh = make_mesh((2, 2, 2), ("pod", "edge", "data"))
ace = ACESyncConfig(replan_every=3, sync_interval_init=2)
sess = TrainSession.from_config(
    "paper-350m", strategy="acesync_hier", mesh=mesh, seq_len=64,
    batch=4, steps=400, warmup_steps=10, ckpt_every=0, n_edge_devices=16,
    ckpt_dir="/tmp/repro_recluster_ckpt", acesync=ace)
sess.run(8, log_every=0)
tr = sess.trainer
assert tr.n_pods == 4 and tr.n_edge == 2
assert tr.scheduler.hier_enabled
# stabilise, then land any in-flight replan/AOT warm-up
for _ in range(6):
    before = tr.compile_count()
    sess.run(6, log_every=0)
    if tr.compile_count() == before:
        break
sess.loop.poll_replan(block=True)
compiles = tr.compile_count()
updates_before = sess.loop.clusters.updates
sess.run(18, log_every=0)          # 6 replans, each re-clustering
sess.loop.poll_replan(block=True)
assert sess.loop.clusters.updates > updates_before, "no re-cluster ran"
assert tr.compile_count() == compiles, (
    f"steady-state replans recompiled: {tr.compile_count()} != {compiles}")
# fleet members hold bit-identical params after compressed two-tier syncs
params = jax.device_get(sess.state["params"])
for path, leaf in jax.tree_util.tree_leaves_with_path(params):
    arr = np.asarray(leaf)
    for m in range(1, arr.shape[0]):
        assert (arr[m] == arr[0]).all(), jax.tree_util.keystr(path)
assert all(np.isfinite(l) for l in sess.losses)
print("RECLUSTER_OK", sess.loop.clusters.updates, tr.compile_count())
"""


def _run_sub(script):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + root
    env.setdefault("REPRO_FORCE_INTERPRET", "1")
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)


@pytest.mark.slow
def test_two_tier_hlo_pin_subprocess():
    r = _run_sub(HIER_SCRIPT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "HIER_PIN_OK" in r.stdout


@pytest.mark.slow
def test_recluster_replans_zero_recompiles_subprocess():
    r = _run_sub(RECLUSTER_SCRIPT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "RECLUSTER_OK" in r.stdout
