"""Multi-pod trainer integration (8 virtual devices, (2,2,2) mesh).

XLA locks the device count at first use, so these run in a subprocess with
XLA_FLAGS set; the child script asserts and prints MULTIPOD_OK."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import SMOKE_ARCHS
from repro.configs.base import RunConfig, ShapeConfig
from repro.models.registry import build_model
from repro.core.trainer import Trainer
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
shape = ShapeConfig("t", 64, 8, "train")
cfg = SMOKE_ARCHS["qwen3-8b"]
run = RunConfig(model=cfg, shape=shape, total_steps=20, warmup_steps=2,
                lr=1e-3)
model = build_model(cfg, run)
tr = Trainer(model, run, mesh=mesh, strategy="acesync")
state = jax.device_put(tr.init_state(jax.random.PRNGKey(0)),
                       tr.state_shardings())
batch = jax.device_put(model.make_batch(jax.random.PRNGKey(1), shape),
                       tr.batch_shardings(shape))
plan = tr.default_plan(bandwidth_mbps=30.0)
fn = tr.step_fn(plan, "grad_sync")
losses = []
for _ in range(8):
    state, metrics = fn(state, batch)
    losses.append(float(metrics["loss"]))
assert losses[-1] < losses[0], losses
# grad-sync keeps pods aligned
p0 = np.asarray(jax.device_get(jax.tree.leaves(state["params"])[0]))
assert np.allclose(p0[0], p0[1], atol=1e-5), "pods diverged under grad_sync"

# local steps diverge pods, delta_sync realigns them
fn_local = tr.step_fn(plan, "local")
batch2 = jax.device_put(model.make_batch(jax.random.PRNGKey(2), shape),
                        tr.batch_shardings(shape))
state, _ = fn_local(state, batch2)  # different per-pod data -> divergence
p1 = np.asarray(jax.device_get(jax.tree.leaves(state["params"])[0]))
assert not np.allclose(p1[0], p1[1], atol=1e-7), "pods should diverge"
fn_delta = tr.step_fn(plan, "delta_sync")
state, m = fn_delta(state, batch2)
p2 = np.asarray(jax.device_get(jax.tree.leaves(state["params"])[0]))
assert np.allclose(p2[0], p2[1], atol=1e-5), "delta_sync must realign"
assert m["divergence"] >= 0.0

# fullsync == acesync-with-FULL-plan agreement on first step
tr2 = Trainer(model, run, mesh=mesh, strategy="fullsync")
state2 = jax.device_put(tr2.init_state(jax.random.PRNGKey(0)),
                        tr2.state_shardings())
fn2 = tr2.step_fn(tr2.default_plan(), "grad_sync")
state2, m2 = fn2(state2, batch)
assert abs(m2["loss"] - losses[0]) < 1e-3
print("MULTIPOD_OK")
"""


@pytest.mark.slow
def test_multipod_trainer_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MULTIPOD_OK" in r.stdout


P3_SOAK_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=12"
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import SMOKE_ARCHS
from repro.configs.base import ACESyncConfig, RunConfig, ShapeConfig
from repro.models.registry import build_model
from repro.core.trainer import Trainer
from repro.launch.mesh import make_mesh

mesh = make_mesh((3, 2, 2), ("pod", "data", "model"))
shape = ShapeConfig("t", 64, 6, "train")
cfg = SMOKE_ARCHS["paper-350m"]
# forced 2-chunk ring on every ring-capable rung: on a 3-pod mesh every
# exchange (ring AND one-shot) folds deterministically, so pods fed
# DIFFERENT data must stay BIT-identical under grad_sync — the drift the
# old arrival-order float fold allowed
run = RunConfig(model=cfg, shape=shape, total_steps=20, warmup_steps=2,
                lr=1e-3, acesync=ACESyncConfig(ring_chunks=2))
model = build_model(cfg, run)
tr = Trainer(model, run, mesh=mesh, strategy="acesync")
state = jax.device_put(tr.init_state(jax.random.PRNGKey(0)),
                       tr.state_shardings())
plan = tr.default_plan(bandwidth_mbps=30.0)
assert any(c >= 2 for c in tr.exec_plan(plan).chunks), \
    tr.exec_plan(plan).chunks
fn = tr.step_fn(plan, "grad_sync")
for s in range(4):
    batch = jax.device_put(
        model.make_batch(jax.random.PRNGKey(s + 1), shape),
        tr.batch_shardings(shape))
    state, metrics = fn(state, batch)
    assert np.isfinite(float(metrics["loss"]))
# per-pod parameter hashes: every leaf bit-identical across the 3 pods
for path, leaf in jax.tree_util.tree_flatten_with_path(
        state["params"])[0]:
    a = np.asarray(jax.device_get(leaf))
    for p in (1, 2):
        assert (a[0] == a[p]).all(), (path, "pods drifted")
print("P3_SOAK_OK")
"""


# Backward-interleaved streaming at the trainer level: a multi-step EF
# soak with overlap_backward on vs off must land BIT-identical params on
# every pod (the segment split is numerics-neutral by blockwise codec
# math; anything else is a streaming bug).  The contract is pinned on
# the kernel path (REPRO_FORCE_INTERPRET=1, matching CI) with FMA
# instructions off (--xla_cpu_max_isa=AVX): XLA:CPU contracts a*b + c
# into one FMA wherever its fusion puts the multiply and the add in one
# loop, so the differently-segmented on/off programs would round the
# rung-ordered AdamW's moment updates (beta*m + (1-beta)*g) differently
# at the ulp level.  Without FMA every op rounds on its own and the
# result is a function of the math alone — sync_tree itself is bit-exact
# seg-vs-flat even with nonzero error buffers (pinned in
# tests/test_collectives.py).  Parameterised via env
# vars like tests/test_collectives.py's DET_SCRIPT (XLA locks the device
# count per process).  The companion retrace contract — zero steady-state
# recompiles across replans that change the rung schedule, including
# segmented ones — is pinned in tests/test_replan.py.
OVERLAP_SOAK_SCRIPT = r"""
import os
MESH = tuple(int(x) for x in os.environ["REPRO_TEST_MESH"].split(","))
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + os.environ["REPRO_TEST_DEVS"]
                           + " --xla_cpu_max_isa=AVX")
import jax
import numpy as np
from repro.configs import SMOKE_ARCHS
from repro.configs.base import ACESyncConfig, RunConfig, ShapeConfig
from repro.models.registry import build_model
from repro.core.trainer import Trainer
from repro.launch.mesh import make_mesh

mesh = make_mesh(MESH, ("pod", "data", "model"))
shape = ShapeConfig("t", 64, 6, "train")
cfg = SMOKE_ARCHS["paper-350m"]


def soak(overlap):
    run = RunConfig(model=cfg, shape=shape, total_steps=20,
                    warmup_steps=2, lr=1e-3,
                    acesync=ACESyncConfig(overlap_backward=overlap))
    model = build_model(cfg, run)
    tr = Trainer(model, run, mesh=mesh, strategy="acesync")
    plan = tr.default_plan(bandwidth_mbps=30.0)
    assert tr.exec_plan(plan).segmented == overlap, overlap
    state = jax.device_put(tr.init_state(jax.random.PRNGKey(0)),
                           tr.state_shardings())
    fn = tr.step_fn(plan, "grad_sync")
    for s in range(4):
        batch = jax.device_put(
            model.make_batch(jax.random.PRNGKey(s + 1), shape),
            tr.batch_shardings(shape))
        state, metrics = fn(state, batch)
        assert np.isfinite(float(metrics["loss"])), (overlap, s)
    return state


st_on, st_off = soak(True), soak(False)
n = 0
for (path, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(st_on["params"])[0],
        jax.tree_util.tree_flatten_with_path(st_off["params"])[0]):
    aa = np.asarray(jax.device_get(a))
    bb = np.asarray(jax.device_get(b))
    assert (aa == bb).all(), (path, "overlap changed the math")
    for p in range(1, MESH[0]):
        assert (aa[0] == aa[p]).all(), (path, "pods drifted")
    n += 1
assert n > 0
print("OVERLAP_SOAK_OK", n)
"""


def _run_overlap_soak(mesh, devs):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["REPRO_TEST_MESH"] = mesh
    env["REPRO_TEST_DEVS"] = str(devs)
    # Pin the kernel path: the parity contract is on the production
    # encode kernels, not the oracle path's whole-program XLA:CPU fusion
    # (see the comment above OVERLAP_SOAK_SCRIPT).
    env["REPRO_FORCE_INTERPRET"] = "1"
    r = subprocess.run([sys.executable, "-c", OVERLAP_SOAK_SCRIPT],
                       env=env, capture_output=True, text=True,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OVERLAP_SOAK_OK" in r.stdout


@pytest.mark.slow
def test_overlap_backward_bit_parity_p2():
    """4-step EF soak on (2,2,2): params with overlap_backward on == off,
    bit for bit, and bit-identical across pods."""
    _run_overlap_soak("2,2,2", 8)


@pytest.mark.slow
def test_overlap_backward_bit_parity_p3():
    """Same contract on a 3-pod mesh, where every exchange folds through
    the deterministic fixed-point path."""
    _run_overlap_soak("3,2,2", 12)


@pytest.mark.slow
def test_p3_trainer_grad_sync_param_hash_soak():
    """Multi-step grad_sync on a simulated 3-pod mesh with a forced ring:
    per-pod parameters stay BIT-identical (the deterministic P >= 3
    accumulation contract at the trainer level)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    r = subprocess.run([sys.executable, "-c", P3_SOAK_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "P3_SOAK_OK" in r.stdout


# Preemption soak: kill the driver at step k (checkpoints at 5/10, the
# newest one bit-rotted on disk + a crashed writer's .tmp left behind), a
# FRESH loop restores from the newest checkpoint that VERIFIES and
# continues — landing params, EF residuals and plan state BIT-identical
# to the uninterrupted run on the same mesh.  ``blocking_replans`` pins
# replan application to fixed steps so the plan/H trajectory is a pure
# function of the state trajectory.
PREEMPT_SOAK_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import tempfile
import jax, numpy as np
from repro.configs.base import ACESyncConfig
from repro.launch.mesh import make_mesh
from repro.launch.session import TrainSession
import repro.runtime.faults as F

STEPS = 14
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))


def mk(d):
    return TrainSession.from_config(
        "paper-350m", strategy="acesync", mesh=mesh, steps=STEPS,
        seq_len=32, batch=4, ckpt_dir=d, ckpt_every=5,
        blocking_replans=True, acesync=ACESyncConfig(replan_every=4))


def host(tree):
    return [np.asarray(jax.device_get(l)) for l in jax.tree.leaves(tree)]


# run A: uninterrupted
dA = tempfile.mkdtemp()
a = mk(dA); a.run(STEPS, log_every=100); a.finish()

# run B: preempted after step 11 (checkpoints landed at 5 and 10)
dB = tempfile.mkdtemp()
b1 = mk(dB); b1.run(11, log_every=100); b1.finish()
# the preemption tore a write and bit-rotted the newest checkpoint:
os.makedirs(os.path.join(dB, "step_00000099.tmp"))
d10 = os.path.join(dB, "step_00000010")
biggest = max((n for n in os.listdir(d10) if n.startswith("leaf_")),
              key=lambda n: os.path.getsize(os.path.join(d10, n)))
idx = int(biggest.split("_")[1].split(".")[0])
assert F.corrupt_checkpoint_leaf(dB, idx, step=10)

# fresh process-equivalent: new session over the same ckpt dir
b2 = mk(dB)
b2.init()
restored = int(jax.device_get(
    jax.tree.leaves(b2.state["step"])[0].reshape(-1)[0]))
assert restored == 5, f"should fall back to step 5, got {restored}"
assert 10 in b2.loop.ckpt.corrupt_steps
b2.run(STEPS - restored, log_every=100)
b2.finish()

for la, lb in zip(host(a.state["params"]), host(b2.state["params"])):
    assert (la == lb).all(), "params diverged after restart-replay"
for la, lb in zip(host(a.state["ace"].errors),
                  host(b2.state["ace"].errors)):
    assert (la == lb).all(), "EF residuals diverged after restart-replay"
assert a.loop._plan.level_idx == b2.loop._plan.level_idx
assert a.loop._plan.sync_interval == b2.loop._plan.sync_interval
assert a.loop._steps_since_sync == b2.loop._steps_since_sync
assert (a.loop.trainer.scheduler.sync_interval
        == b2.loop.trainer.scheduler.sync_interval)
print("PREEMPT_SOAK_OK")
"""


@pytest.mark.slow
def test_preemption_restart_replay_bit_identical():
    """Kill at step k, restore (with fallback past a corrupt newest
    checkpoint), continue: bit-identical params + EF residuals + plan
    state vs the uninterrupted run."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    r = subprocess.run([sys.executable, "-c", PREEMPT_SOAK_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PREEMPT_SOAK_OK" in r.stdout


# Elastic soak: P=3 -> pod 2 preempted at step 4 -> P=2 -> rejoin at
# step 8 -> P=3.  Each transition re-derives the mesh/ring through a
# per-pod-count trainer whose step is AOT-warmed in the background, so
# the membership change adds ZERO foreground recompiles over the
# fault-free baseline (compile_count stays flat; the new-P signature is
# served from the warm AOT cache).
ELASTIC_SOAK_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=12"
import tempfile
import jax, numpy as np
from repro.launch.mesh import make_mesh
from repro.launch.session import TrainSession
from repro.runtime.faults import FaultSchedule

STEPS = 14


def run(faults):
    mesh = make_mesh((3, 2, 2), ("pod", "data", "model"))
    sess = TrainSession.from_config(
        "paper-350m", strategy="acesync", mesh=mesh, steps=STEPS,
        seq_len=32, batch=6, ckpt_dir=tempfile.mkdtemp(), ckpt_every=0,
        fault_schedule=faults, blocking_replans=True)
    sess.run(STEPS, log_every=100)
    sess.finish()
    return sess


base = run(None)
base_compiles = base.loop.compile_count()
assert base.loop.membership_events == []

faults = FaultSchedule.preempt_and_rejoin(pod=2, kill_step=4,
                                          rejoin_step=8)
sess = run(faults)
loop = sess.loop
ev = loop.membership_events
assert [e["n_pods"] for e in ev] == [2, 3], ev
assert all(e["served_from_warm_cache"] for e in ev), ev
# the P-change added ZERO foreground recompiles over the baseline
assert loop.compile_count() == base_compiles, \
    (loop.compile_count(), base_compiles)
assert loop.warm_compile_count() >= 2
# mesh / ring hops / scheduler re-derived for the shrunken fleet
tr2 = loop._trainers[2]
assert tr2.n_pods == 2 and tr2.mesh.shape["pod"] == 2
assert tr2.scheduler.n_pods == 2
# batch re-balanced with membership (rows-per-slice constant), and back
assert loop.trainer.n_pods == 3
assert loop._pipeline.shape.global_batch == 6
assert jax.tree.leaves(sess.state["params"])[0].shape[0] == 3
assert all(np.isfinite(l) for l in sess.losses), sess.losses
assert len(loop.faults.peek()) == 0
# dead pod dropped out of the heartbeat feed while preempted
assert 2 in loop.monitor.alive_pods()
print("ELASTIC_SOAK_OK")
"""


@pytest.mark.slow
def test_elastic_membership_zero_foreground_recompiles():
    """P=3 -> P=2 -> P=3 under an injected preempt/rejoin: compile_count
    stays flat vs the fault-free baseline, membership swaps are served
    from the background-warmed AOT cache, ring/mesh re-derived."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    r = subprocess.run([sys.executable, "-c", ELASTIC_SOAK_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ELASTIC_SOAK_OK" in r.stdout
