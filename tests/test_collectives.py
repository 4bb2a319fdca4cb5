"""Traced-HLO contract of the bucketed codec sync (8 virtual devices).

Two acceptance properties of the codec refactor, pinned on the lowered
HLO of a multi-pod ``sync_tree``:

  1. at most ONE pod collective per DISTINCT codec level in the plan
     (same-level leaves bucket into one buffer; each codec packs its whole
     payload pytree into one uint8 wire buffer);
  2. the analytic accounting (``wire_bytes_of_plan`` — what the Scheduler,
     knapsack and Table 1 price) EQUALS the traced collective bytes on the
     pod axis, for every codec including the bf16 psum of FULL (the seed
     priced bf16 but psum'd f32 — the drift this refactor removed).

XLA locks the device count at first use, so this runs in a subprocess with
XLA_FLAGS set, like tests/test_multipod.py."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import sync as S
from repro.core.compression import Level
from repro.core.scheduler import SyncPlan
from repro.launch.mesh import make_mesh
from benchmarks import hlo_cost

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
levels = (Level("FULL", 1.0, 16), Level("INT8", 1.0, 8),
          Level("TOPK10", 0.10, 8), Level("SIGN1", 1.0, 1),
          Level("SKIP", 0.0, 0))
# 6 leaves, two sharing TOPK10 -> 4 distinct collective-bearing levels
level_names = ["FULL", "INT8", "TOPK10", "TOPK10", "SIGN1", "SKIP"]
names = [l.name for l in levels]
idx = tuple(names.index(n) for n in level_names)
sizes = [2048, 3000, 1500, 1500, 2300, 700]   # non-block-multiples too
plan = SyncPlan(idx, levels, (0.5, 0.5), 1)

r = np.random.RandomState(0)
tree = {f"p{i}": jnp.asarray(r.randn(n).astype(np.float32))
        for i, n in enumerate(sizes)}
errors = jax.tree.map(jnp.zeros_like, tree)


def inner(t, e):
    return S.sync_tree(t, e, plan, mesh=mesh, shardings=None, gamma=1.0,
                       inside_manual=True)


smapped = jax.shard_map(
    inner, mesh=mesh,
    in_specs=(jax.tree.map(lambda _: P(), tree),
              jax.tree.map(lambda _: P(), errors)),
    out_specs=(jax.tree.map(lambda _: P(), tree),
               jax.tree.map(lambda _: P(), errors)),
    check_vma=False)
fn = jax.jit(smapped)

# --- run it: EF invariant survives the real multi-pod exchange ----------
agg, new_e = fn(tree, errors)
for k in tree:
    a = np.asarray(jax.device_get(agg[k]))
    assert np.isfinite(a).all(), k
    if k != "p5":  # non-SKIP leaves: per-pod own+residual == ef, and with
        # identical per-pod inputs the aggregate equals own
        np.testing.assert_allclose(np.asarray(agg[k] + new_e[k]),
                                   np.asarray(tree[k]), rtol=1e-4,
                                   atol=1e-4)

# --- traced-HLO assertions ---------------------------------------------
txt = fn.lower(tree, errors).compile().as_text()
rep = hlo_cost.analyze(txt, (2, 2, 2), ("pod", "data", "model"))
n_distinct_wire_levels = 4  # FULL, INT8, TOPK10 (bucketed x2), SIGN1
pod_count = rep.collective_count.get("pod", 0)
assert 1 <= pod_count <= n_distinct_wire_levels, \
    f"pod collectives {pod_count} > {n_distinct_wire_levels}: " \
    f"{dict(rep.collective_count)}"

analytic = S.wire_bytes_of_plan(plan, sizes, n_pods=2)
traced = rep.collective_bytes.get("pod", 0.0)
# XLA's bf16 normalization pass promotes the FULL bucket's bf16
# all-reduce to f32 on backends without native bf16 reduction (this CPU
# container); on TPU it stays bf16.  Accept exactly those two totals —
# every all_gather codec must match to the byte either way.
full_part = levels[0].wire_bytes(sizes[0], 2)
assert traced in (float(analytic), float(analytic + full_part)), \
    f"analytic {analytic} (or promoted {analytic + full_part}) " \
    f"!= traced {traced}"
# no sync traffic may leak onto the fast axes
for ax, b in rep.collective_bytes.items():
    if "pod" not in ax:
        assert b == 0.0, (ax, b)
print("COLLECTIVES_OK", pod_count, int(analytic))
"""


@pytest.mark.slow
def test_bucketed_sync_collectives_subprocess():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + root
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "COLLECTIVES_OK" in r.stdout


RING_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import sync as S
from repro.core.compression import Level
from repro.core.planexec import build_exec_plan, sig_wire_bytes
from repro.core.scheduler import SyncPlan
from repro.launch.mesh import make_mesh
from benchmarks import hlo_cost

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
# every ring-capable codec rings; FULL/SKIP stay on their one-shot path
levels = (Level("INT8", 1.0, 8), Level("TOPK10", 0.10, 8),
          Level("SIGN1", 1.0, 1), Level("INT4", 1.0, 4),
          Level("FULL", 1.0, 16), Level("SKIP", 0.0, 0))
idx = tuple(range(6))
sizes = [6000, 8192, 4100, 6000, 2048, 700]
plan = SyncPlan(idx, levels, (0.6, 0.4), 1)

r = np.random.RandomState(7)
tree = {f"p{i}": jnp.asarray(r.randn(n).astype(np.float32))
        for i, n in enumerate(sizes)}
errors = jax.tree.map(lambda x: jnp.ones_like(x) * 0.03, tree)
K = 2
ep_ring = build_exec_plan(plan, sizes, n_pods=2, ring=K)
ep_one = build_exec_plan(plan, sizes, n_pods=2, ring=0)
assert ep_ring.chunks == (K, K, K, K, 0, 0), ep_ring.chunks
assert ep_one.chunks == (0,) * 6, ep_one.chunks
# chunk rounding only pads rungs whose class is not a K multiple
assert all(s % K == 0 for s, c in zip(ep_ring.sig, ep_ring.chunks) if c)


def run(ep):
    def inner(t, e):
        return S.sync_tree(t, e, ep, mesh=mesh, shardings=None,
                           gamma=0.9, inside_manual=True)
    smapped = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), tree),
                  jax.tree.map(lambda _: P(), errors)),
        out_specs=(jax.tree.map(lambda _: P(), tree),
                   jax.tree.map(lambda _: P(), errors)),
        check_vma=False)
    return jax.jit(smapped)


fn_ring, fn_one = run(ep_ring), run(ep_one)

# --- exchange parity: ring == one-shot ----------------------------------
agg_r, err_r = fn_ring(tree, errors)
agg_o, err_o = fn_one(tree, errors)
for k in tree:
    # residuals are device-local (no exchange in the loop): bit-exact
    np.testing.assert_array_equal(np.asarray(jax.device_get(err_r[k])),
                                  np.asarray(jax.device_get(err_o[k])),
                                  err_msg=k)
    # aggregates: the same omega-weighted two-term sums; XLA fusion may
    # re-associate the dense FMA by 1 ulp
    np.testing.assert_allclose(np.asarray(jax.device_get(agg_r[k])),
                               np.asarray(jax.device_get(agg_o[k])),
                               rtol=3e-7, atol=3e-7, err_msg=k)

# --- traced-HLO: exactly K ppermutes per ringing rung, same pod bytes ---
import re
txt = fn_ring.lower(tree, errors).compile().as_text()
rep = hlo_cost.analyze(txt, (2, 2, 2), ("pod", "data", "model"))
n_ring_rungs = sum(1 for c in ep_ring.chunks if c)
expect_permutes = K * (2 - 1) * n_ring_rungs
got_permutes = len(re.findall(
    r"=\s+\S+\s+collective-permute(?:-start)?\(", txt))
assert got_permutes == expect_permutes, (got_permutes, expect_permutes)
# pod collectives overall: K ppermutes per ringing rung + 1 for FULL
assert rep.collective_count.get("pod", 0) == expect_permutes + 1, \
    dict(rep.collective_count)
for ax, b in rep.collective_bytes.items():
    if "pod" not in ax:
        assert b == 0.0, (ax, b)

analytic = sig_wire_bytes(ep_ring.sig, ep_ring.levels, 2)
traced = rep.collective_bytes.get("pod", 0.0)
# XLA promotes FULL's bf16 all-reduce to f32 on CPU (see SCRIPT above)
full_part = levels[4].wire_bytes(ep_ring.sig[4] * 1024, 2)
assert traced in (float(analytic), float(analytic + full_part)), \
    (analytic, traced)
# the ring moves exactly the one-shot all_gather receive volume; only the
# K-multiple rounding of the signature pads, and that is priced in sig
txt_o = fn_one.lower(tree, errors).compile().as_text()
rep_o = hlo_cost.analyze(txt_o, (2, 2, 2), ("pod", "data", "model"))
analytic_o = sig_wire_bytes(ep_one.sig, ep_one.levels, 2)
traced_o = rep_o.collective_bytes.get("pod", 0.0)
assert traced_o in (float(analytic_o), float(analytic_o + full_part)), \
    (analytic_o, traced_o)
ring_pad = analytic - analytic_o
assert 0 <= ring_pad <= sum(
    lv.wire_bytes((K - 1) * 1024, 2)
    for lv, c in zip(levels, ep_ring.chunks) if c), ring_pad
print("RING_OK", got_permutes, int(analytic))
"""


@pytest.mark.slow
def test_ring_exchange_collectives_subprocess():
    """The chunked ring pipeline: bit-parity with the one-shot exchange,
    exactly K ppermutes per ringing rung in the lowered HLO, and analytic
    == traced wire bytes (the ring moves the all_gather receive volume)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + root
    r = subprocess.run([sys.executable, "-c", RING_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "RING_OK" in r.stdout


# The P >= 3 deterministic-accumulation contract, soaked on real pod
# meshes.  Parameterised via env vars (XLA locks the device count per
# process): REPRO_TEST_PODS, REPRO_TEST_MESH, REPRO_TEST_DEVS,
# REPRO_TEST_RING ("auto" or a forced K).
DET_SCRIPT = r"""
import os
P = int(os.environ["REPRO_TEST_PODS"])
MESH = tuple(int(x) for x in os.environ["REPRO_TEST_MESH"].split(","))
RING = os.environ.get("REPRO_TEST_RING", "auto")
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + os.environ["REPRO_TEST_DEVS"])
import re
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as Spec

from repro.core import sync as S
from repro.core.compression import Level
from repro.core.planexec import build_exec_plan, ring_hops, sig_wire_bytes
from repro.core.scheduler import SyncPlan
from repro.launch.mesh import make_mesh
from benchmarks import hlo_cost

mesh = make_mesh(MESH, ("pod", "data", "model"))
levels = (Level("INT8", 1.0, 8), Level("TOPK10", 0.10, 8),
          Level("SIGN1", 1.0, 1), Level("INT4", 1.0, 4),
          Level("FULL", 1.0, 16), Level("SKIP", 0.0, 0))
idx = tuple(range(6))
# the INT8 rung is big enough to be DCN-bound (its decode time clears
# the ppermute launch overhead on BOTH the bidir and the longer unidir
# critical path), so the AUTO heuristic rings it even without a forced K
# (the acceptance pin)
sizes = [2048 * 1024 if RING == "auto" else 6144,
         8192, 4096, 6144, 2048, 700]
omega = tuple(np.arange(1, P + 1, dtype=np.float64) / (P * (P + 1) / 2))
plan = SyncPlan(idx, levels, omega, 1)
ring = None if RING == "auto" else int(RING)
ep_ring = build_exec_plan(plan, sizes, n_pods=P, ring=ring, bidir=True)
ep_uni = build_exec_plan(plan, sizes, n_pods=P, ring=ring, bidir=False)
ep_one = build_exec_plan(plan, sizes, n_pods=P, ring=0)
assert ep_ring.chunks[0] >= 2, (RING, ep_ring.chunks)
assert all(c == 0 for c in ep_ring.chunks[4:]), ep_ring.chunks
assert ep_uni.chunks == ep_ring.chunks  # per-hop wire time is P-free

r = np.random.RandomState(11)
tree = {f"p{i}": jnp.asarray(r.randn(P, n).astype(np.float32))
        for i, n in enumerate(sizes)}          # per-pod DISTINCT grads
errors0 = jax.tree.map(jnp.zeros_like, tree)


def runner(ep):
    def inner(t, e):
        t = jax.tree.map(lambda x: x.reshape(x.shape[1:]), t)
        e = jax.tree.map(lambda x: x.reshape(x.shape[1:]), e)
        a, ne = S.sync_tree(t, e, ep, mesh=mesh, shardings=None,
                            gamma=0.9, inside_manual=True)
        return (jax.tree.map(lambda x: x[None], a),
                jax.tree.map(lambda x: x[None], ne))
    pod = jax.tree.map(lambda _: Spec("pod"), tree)
    smapped = jax.shard_map(inner, mesh=mesh, in_specs=(pod, pod),
                            out_specs=(pod, pod), check_vma=False)
    return jax.jit(smapped)


fn_ring, fn_uni, fn_one = runner(ep_ring), runner(ep_uni), runner(ep_one)

# --- multi-step soak: EF errors carried, params mirror accumulated -----
err_r, err_u, err_o = errors0, errors0, errors0
params = {k: np.zeros_like(np.asarray(tree[k])) for k in tree}
for t in range(3):
    g = jax.tree.map(lambda x: x * (1.0 + 0.25 * t), tree)
    agg_r, err_r = fn_ring(g, err_r)
    agg_u, err_u = fn_uni(g, err_u)
    agg_o, err_o = fn_one(g, err_o)
    for k in tree:
        ar = np.asarray(jax.device_get(agg_r[k]))
        au = np.asarray(jax.device_get(agg_u[k]))
        ao = np.asarray(jax.device_get(agg_o[k]))
        for p in range(1, P):
            assert (ar[0] == ar[p]).all(), (k, t, "ring cross-pod drift")
            assert (ao[0] == ao[p]).all(), (k, t, "one-shot cross-pod")
        # deterministic accumulation: ring == one-shot == either
        # direction, bit for bit (order cannot matter)
        assert (ar == ao).all(), (k, t, "ring != one-shot")
        assert (ar == au).all(), (k, t, "bidir != unidir")
        params[k] += ar
for k in tree:  # N steps of identical aggregates -> identical params
    for p in range(1, P):
        assert (params[k][0] == params[k][p]).all(), (k, "param drift")

# --- HLO pins: ppermute count, direction split, analytic == traced -----
n_ring = sum(1 for c in ep_ring.chunks if c)
txt = fn_ring.lower(tree, errors0).compile().as_text()
rep = hlo_cost.analyze(txt, MESH, ("pod", "data", "model"))
got = len(re.findall(r"=\s+\S+\s+collective-permute(?:-start)?\(", txt))
expect = sum(c * (P - 1) for c in ep_ring.chunks if c)
assert got == expect, (got, expect)
pairs = set(re.findall(r"source_target_pairs=\{[^}]*\}", txt))
assert len(pairs) == (2 if P >= 3 else 1), pairs  # both DCN directions
txt_u = fn_uni.lower(tree, errors0).compile().as_text()
pairs_u = set(re.findall(r"source_target_pairs=\{[^}]*\}", txt_u))
assert len(pairs_u) == 1, pairs_u                 # forward ring only
assert len(re.findall(r"=\s+\S+\s+collective-permute(?:-start)?\(",
                      txt_u)) == expect
# hops split: two half-rings of ceil((P-1)/2)
assert ring_hops(P, True) == -(-(P - 1) // 2)

analytic = sig_wire_bytes(ep_ring.sig, ep_ring.levels, P)
traced = rep.collective_bytes.get("pod", 0.0)
# XLA promotes FULL's bf16 all-reduce to f32 on backends without native
# bf16 reduction (this CPU container): accept the analytic total with
# the bf16 ring-all-reduce term swapped for its f32 version (float math
# mirrors hlo_cost; the (P-1)/P thirds are fractional at P = 3)
full_n = ep_ring.sig[4] * 1024
full_f32 = 2.0 * (P - 1) / P * 4 * full_n
full_bf16 = levels[4].wire_bytes(full_n, P)
assert (abs(traced - analytic) < 2.0
        or abs(traced - (analytic - full_bf16 + full_f32)) < 2.0), \
    (analytic, traced)
for ax, b in rep.collective_bytes.items():
    if "pod" not in ax:
        assert b == 0.0, (ax, b)
print("DET_OK", P, got, int(analytic))
"""


def _run_det(n_pods, mesh, devs, ring):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + root
    env["REPRO_TEST_PODS"] = str(n_pods)
    env["REPRO_TEST_MESH"] = mesh
    env["REPRO_TEST_DEVS"] = str(devs)
    env["REPRO_TEST_RING"] = ring
    r = subprocess.run([sys.executable, "-c", DET_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DET_OK" in r.stdout


@pytest.mark.slow
def test_p3_deterministic_ring_soak_auto_heuristic():
    """P = 3 pods: the AUTO roofline heuristic rings the DCN-bound rung
    (the 2-pod fence is gone), a multi-step EF soak keeps per-pod
    aggregates/params bit-identical for every codec, ring == one-shot ==
    unidirectional bit for bit, K*(P-1) ppermutes split over BOTH DCN
    directions, analytic == traced wire bytes."""
    _run_det(3, "3,2,2", 12, "auto")


@pytest.mark.slow
def test_p4_deterministic_ring_soak_forced():
    """P = 4 pods, forced 2-chunk ring (satellite pin: a forced ring on
    P >= 3 routes through the deterministic fold, not the legacy
    arrival-order float fold): same bit-determinism contract, asymmetric
    half-rings (2 forward + 1 backward hop)."""
    _run_det(4, "4,2,1", 8, "2")


# Backward-interleaved streaming: the structural pin.  A segment's
# collective must be issuable BEFORE the rest of the backward finishes —
# i.e. its transitive operand cone in the lowered HLO excludes the
# shallow layers' gradient ops.  We mark the shallowest layer with
# jnp.sin: reverse-mode emits `cosine` only in THAT layer's grad path,
# so "cone contains cosine" == "depends on the final backward segment".
CONE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import re
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import sync as S
from repro.core.compression import Level
from repro.core.planexec import build_exec_plan
from repro.core.scheduler import SyncPlan
from repro.launch.mesh import make_mesh

# pod-only 2-device mesh: every collective in the module is a pod
# collective, no axis bookkeeping needed
mesh = make_mesh((2, 1, 1), ("pod", "data", "model"))
# 6 chained (D, D) layers; the FIRST (shallowest) applies sin, so its
# backward — and ONLY its backward — emits a `cosine` op.  Reverse-mode
# produces the DEEP grads first, cos-free.
D = 32
levels = (Level("INT8", 1.0, 8), Level("INT4", 1.0, 4))
idx = (0, 1, 0, 1, 0, 1)
sizes = [D * D] * 6
plan = SyncPlan(idx, levels, (0.5, 0.5), 1)
ep_seg = build_exec_plan(plan, sizes, n_pods=2, segments=2)
ep_flat = build_exec_plan(plan, sizes, n_pods=2, segments=1)
assert ep_seg.segmented and not ep_flat.segmented

r = np.random.RandomState(3)
params = {f"p{i}": jnp.asarray(r.randn(D, D).astype(np.float32) / D)
          for i in range(6)}
errors = jax.tree.map(jnp.zeros_like, params)
x = jnp.asarray(r.randn(8, D).astype(np.float32))


def make_fn(ep):
    def inner(ps, es, xb):
        def loss(ps):
            h = jnp.sin(xb @ ps["p0"])
            for i in range(1, 6):
                h = h @ ps[f"p{i}"]
            return jnp.mean(h * h)
        grads = jax.grad(loss)(ps)
        return S.sync_tree(grads, es, ep, mesh=mesh, shardings=None,
                           gamma=1.0, inside_manual=True)
    pp = jax.tree.map(lambda _: P(), params)
    smapped = jax.shard_map(inner, mesh=mesh, in_specs=(pp, pp, P()),
                            out_specs=(pp, pp), check_vma=False)
    return jax.jit(smapped)


COLL = re.compile(r"=\s+\S+\s+(all-gather|all-reduce|all-to-all|"
                  r"reduce-scatter|collective-permute)(-start)?\(")
TOK = re.compile(r"%[\w.\-]+")


def cone_report(txt):
    # Def-use graph over %name tokens, scoped per computation (names are
    # only unique within one); a reference to another computation
    # (calls=/to_apply=/...) pulls in everything defined inside it.
    # Returns, per collective, whether its transitive cone has a cosine.
    comp_names = set(m.group(1) for m in re.finditer(
        r"^(?:ENTRY\s+)?(%[\w.\-]+)\s*\(", txt, re.M))
    deps, is_cos, comp_defs, colls = {}, set(), {}, []
    comp = None
    for line in txt.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?(%[\w.\-]+)\s*\(", line)
        if m and line.rstrip().endswith("{"):
            comp = m.group(1)
            comp_defs.setdefault(comp, [])
            continue
        if " = " not in line or comp is None:
            continue
        lhs, rhs = line.split(" = ", 1)
        dm = TOK.search(lhs)
        if not dm:
            continue
        node = (comp, dm.group(0))
        deps[node] = [("COMP", t) if t in comp_names else (comp, t)
                      for t in TOK.findall(rhs)]
        comp_defs[comp].append(node)
        if re.search(r"\bcosine\(", rhs):
            is_cos.add(node)
        if COLL.search(line):
            colls.append(node)
    for c, defs in comp_defs.items():
        deps[("COMP", c)] = defs
    memo = {}
    def has_cos(n):
        if n in memo:
            return memo[n]
        memo[n] = False  # cycle guard (while bodies)
        memo[n] = n in is_cos or any(has_cos(d) for d in deps.get(n, ()))
        return memo[n]
    assert is_cos, "no cosine in HLO -- marker layer missing?"
    assert colls, "no collectives found"
    return [has_cos(c) for c in colls]


fn_seg, fn_flat = make_fn(ep_seg), make_fn(ep_flat)

# streaming must be free: segment-streamed == barriered bit for bit
agg_s, err_s = fn_seg(params, errors, x)
agg_f, err_f = fn_flat(params, errors, x)
for k in params:
    assert (np.asarray(agg_s[k]) == np.asarray(agg_f[k])).all(), k
    assert (np.asarray(err_s[k]) == np.asarray(err_f[k])).all(), k

# ... and with NONZERO error buffers: zero errors vacuously mask the EF
# combine (gamma * e contributes nothing), so run the same parity check
# mid-soak, where the residual path carries live ulp-sensitive state.
errors_nz = jax.tree.map(
    lambda p: jnp.asarray(0.3 * r.randn(*p.shape).astype(np.float32)),
    params)
agg_s, err_s = fn_seg(params, errors_nz, x)
agg_f, err_f = fn_flat(params, errors_nz, x)
for k in params:
    assert (np.asarray(agg_s[k]) == np.asarray(agg_f[k])).all(), (k, "nz")
    assert (np.asarray(err_s[k]) == np.asarray(err_f[k])).all(), (k, "nz")

rep_seg = cone_report(fn_seg.lower(params, errors, x).compile().as_text())
rep_flat = cone_report(
    fn_flat.lower(params, errors, x).compile().as_text())

# Segmented: the deep segment's collectives issue from cos-free cones —
# XLA may start them while the shallow backward still runs.  (At least
# one cone DOES contain cosine: the shallow segment's own — the sanity
# check that the marker threads through at all.)  With the coalesced
# wire exchange each segment's payload rungs share ONE all_gather, so
# the counts are per segment, not per rung.
n_free = sum(1 for c in rep_seg if not c)
assert n_free >= 1, rep_seg
assert sum(rep_seg) >= 1, rep_seg
assert len(rep_seg) >= 2, rep_seg
# Barriered: the single packed buffer makes EVERY collective depend on
# the last gradient — the false dependence this scheduling removes.
assert all(rep_flat), rep_flat
assert len(rep_flat) >= 1, rep_flat
print("CONE_OK", len(rep_seg), n_free, len(rep_flat))
"""


@pytest.mark.slow
def test_backward_interleaved_collective_cones_subprocess():
    """Structural pin of the backward-interleaved schedule: with
    segments=2, at least one rung collective's HLO operand cone excludes
    the shallowest layer's gradient (marked via sin -> cosine), so it can
    issue before the backward finishes; the barriered plan's collectives
    all carry the false last-gradient dependence.  Also asserts
    segment-streamed == barriered bit-parity on the same inputs, with
    both zero and nonzero EF error buffers (zero errors mask the
    residual path)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + root
    r = subprocess.run([sys.executable, "-c", CONE_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "CONE_OK" in r.stdout
