"""Host spans and counters (``repro.obs``) and the train step's named
phases.

Off, a span is the one shared null context and nothing is recorded; on,
spans nest per thread and counters add up.  On the device side, the
compiled step's instructions carry the phase scopes in their ``op_name``:
``forward``, its transpose (the backward pass), ``optimizer`` and
``exchange`` (the codec kernels inside it)."""
import json
import os
import re
import subprocess
import sys
import threading

import jax
import pytest

from repro import obs
from repro.configs import SMOKE_ARCHS
from repro.configs.base import RunConfig, ShapeConfig
from repro.core.trainer import Trainer
from repro.data.pipeline import TokenPipeline
from repro.kernels import ops
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.session import TrainSession
from repro.launch.train import run_traced, trace_window
from repro.models.registry import build_model

SHAPE = ShapeConfig("obs", 32, 2, "train")
LOOP_SPANS = ("loop.elastic", "loop.poll", "loop.data", "loop.dispatch",
              "loop.flush", "loop.health")


@pytest.fixture
def clean_obs():
    obs.disable()
    obs.reset()
    yield obs
    obs.disable()
    obs.reset()


def test_span_off_is_the_shared_null_context(clean_obs):
    a, b = obs.span("a"), obs.span("b")
    assert a is b
    with a:
        obs.count("n")
    assert obs.export() == {"spans": [], "counters": {}}


def test_span_on_records_nesting_counters_export_and_reset(clean_obs):
    obs.enable()
    with obs.span("outer"):
        with obs.span("inner"):
            obs.count("n")
        obs.count("n", 2)
    with obs.span("after"):
        pass
    got = obs.export()
    by = {s["name"]: s for s in got["spans"]}
    assert by["inner"]["parent"] == "outer"
    assert by["outer"]["parent"] is None
    assert by["after"]["parent"] is None
    assert by["outer"]["start"] <= by["inner"]["start"] \
        <= by["inner"]["end"] <= by["outer"]["end"] <= by["after"]["start"]
    assert got["counters"] == {"n": 3}
    json.dumps(got)
    obs.reset()
    assert obs.export() == {"spans": [], "counters": {}}


def test_span_parents_are_per_thread(clean_obs):
    obs.enable()

    def other():
        with obs.span("thread"):
            pass

    with obs.span("main"):
        th = threading.Thread(target=other)
        th.start()
        th.join()
    by = {s["name"]: s for s in obs.export()["spans"]}
    assert by["thread"]["parent"] is None


@pytest.mark.parametrize("spec,want", [(None, (0, 6)), ("2:4", (2, 4)),
                                       ("3:", (3, 6)), (":9", (0, 6))])
def test_trace_window(spec, want):
    assert trace_window(spec, 6) == want


def test_trace_window_refuses_an_empty_range():
    with pytest.raises(ValueError):
        trace_window("4:4", 6)


def _phase(op_name: str) -> str:
    words = set(re.findall(r"[A-Za-z_]\w*", op_name))
    if "forward" in words:
        return "backward" if "transpose(" in op_name else "forward"
    for p in ("optimizer", "exchange"):
        if p in words:
            return p
    return "unscoped"


def _op_names(hlo: str):
    return re.findall(r'op_name="([^"]*)"', hlo)


def _stepped_trainer(strategy: str):
    cfg = SMOKE_ARCHS["paper-350m"]
    run = RunConfig(model=cfg, shape=SHAPE, total_steps=30, warmup_steps=2,
                    lr=1e-3)
    model = build_model(cfg, run)
    tr = Trainer(model, run, mesh=None, strategy=strategy)
    sched = tr.scheduler
    if strategy == "fullsync":
        plan = sched.full_plan()
    else:
        names = [l.name for l in sched.levels]
        idx = [names.index("INT8" if g % 2 else "FULL")
               for g in range(len(sched.sizes))]
        plan = sched.plan_from_levels(idx, sync_interval=1, adaptive=True)
    state = tr.init_state(jax.random.PRNGKey(0))
    state, _ = tr.step(state, next(TokenPipeline(model, SHAPE, seed=0)),
                       plan, "grad_sync")
    return tr, plan


@pytest.mark.parametrize("strategy", ["acesync", "fullsync"])
def test_step_hlo_has_every_phase(strategy, monkeypatch):
    # the sync path on the interpreted Pallas kernels, as on a chip, and
    # the location settings every entry point runs with
    monkeypatch.setattr(ops, "default_use_pallas", lambda: True)
    enable_compile_cache()
    tr, plan = _stepped_trainer(strategy)
    names = _op_names(tr.step_hlo_text(plan))
    phases = {_phase(n) for n in names}
    assert {"forward", "backward", "optimizer", "exchange"} <= phases
    assert any("transpose(jvp(forward))" in n for n in names)
    # instructions of a called computation (a while body) carry their
    # op_name relative to the caller's: only whole paths name a phase
    encodes = [n for n in names
               if n.startswith("jit(") and "quantize_int8_gather" in n]
    if strategy == "acesync":
        assert encodes
        assert all(_phase(n) == "exchange" and "encode_INT8" in n
                   for n in encodes)
    else:
        assert not encodes


def test_step_hlo_needs_a_step_first():
    cfg = SMOKE_ARCHS["paper-350m"]
    run = RunConfig(model=cfg, shape=SHAPE, total_steps=30, warmup_steps=2)
    tr = Trainer(build_model(cfg, run), run, mesh=None, strategy="fullsync")
    with pytest.raises(ValueError):
        tr.step_hlo_text(tr.scheduler.full_plan())


def _session(tmp_path):
    return TrainSession.from_config(
        "paper-350m", strategy="fullsync", smoke=True, seq_len=32, batch=2,
        steps=3, warmup_steps=1, ckpt_dir=str(tmp_path / "ck"))


def test_loop_records_its_spans_and_the_compile(clean_obs, tmp_path):
    obs.enable()
    sess = _session(tmp_path).run(3, log_every=0)
    got = obs.export()
    names = [s["name"] for s in got["spans"]]
    for name in LOOP_SPANS:
        assert names.count(name) >= 2, name
    assert "trainer.compile" in names
    assert got["counters"]["step.compiles"] == sess.loop.compile_count()
    # loop spans are leaves: only the compile nests, inside the dispatch
    for s in got["spans"]:
        if s["name"].startswith("loop."):
            assert s["parent"] is None
    assert {s["parent"] for s in got["spans"]
            if s["name"] == "trainer.compile"} == {"loop.dispatch"}
    assert all(h["dt"] >= 0 for h in sess.history)


def test_loop_records_nothing_while_off(clean_obs, tmp_path):
    _session(tmp_path).run(2, log_every=0)
    assert obs.export() == {"spans": [], "counters": {}}


def test_run_traced_writes_spans_hlo_and_a_trace(clean_obs, tmp_path):
    sess = _session(tmp_path)
    out = tmp_path / "trace"
    run_traced(sess, 3, str(out), "1:2")
    spans = json.loads((out / "spans.json").read_text())
    assert [s["name"] for s in spans["spans"]].count("loop.dispatch") == 3
    hlo = (out / "step_hlo.grad_sync.txt").read_text()
    assert "transpose(jvp(forward))" in hlo
    assert list(out.glob("plugins/profile/*/*.xplane.pb"))
    assert len(sess.history) == 3


_SCOPED_COMPILE = """
import os
import sys
import jax
import jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def f(x):
    if sys.argv[1] == "scoped":
        with jax.named_scope("forward"):
            return jnp.sin(x) * 2.0
    return jnp.sin(x) * 2.0


def other_caller(x):
    y = x
    return jax.jit(f).lower(y).compile()


compiled = (jax.jit(f).lower(jnp.ones(8)).compile() if sys.argv[2] == "a"
            else other_caller(jnp.ones(8)))
print('op_name="jit(f)/forward/' in compiled.as_text(),
      sum(n.startswith("jit_f-") for n in os.listdir(sys.argv[3])))
"""


def test_cached_executables_keep_their_own_scopes(tmp_path):
    """One program with and without the named scopes: the second compile
    does not load the first's executable from the persistent cache, so
    its metadata (what a profile names the ops by) is its own; the same
    program from another caller does load it."""
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               PYTHONPATH=os.pathsep.join(
                   [p for p in sys.path if p.endswith("src")]
                   + [os.environ.get("PYTHONPATH", "")]))
    script = tmp_path / "compile.py"
    script.write_text(_SCOPED_COMPILE)
    outs = [subprocess.run([sys.executable, str(script), *args, str(cache)],
                           env=env, capture_output=True, text=True,
                           timeout=300)
            for args in (("plain", "a"), ("scoped", "a"), ("scoped", "b"))]
    got = [o.stdout.split()[-2:] for o in outs]
    assert [g[0] for g in got] == ["False", "True", "True"], \
        outs[-1].stderr[-2000:]
    # a second entry for the scoped program, none for its other caller
    assert [g[1] for g in got] == ["1", "2", "2"]
