"""The configuration's family carries the model: the harness passes every
declared ModelConfig field through, refuses any other key and a family
without a module, and the readings a rehearsal compares are those the
harness read before the reference model moved into ``families/``."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import loader
import run
from conftest import BENCH, HERE, ROOT

PINNED = os.path.join(BENCH, "testdata", "rehearsal_readings.json")


def load_cfg(name="paper-350m"):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def child(args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu", CHIPBENCH_REHEARSAL="1")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("cell", sorted(
    json.load(open(PINNED))["cells"]))
def test_rehearsal_readings_equal_the_pinned(cell):
    """Program and reference read what they read before the move, to the
    last bit: the losses, gradient norms, first gradient and change."""
    pinned = json.load(open(PINNED))["cells"][cell]
    proc = child([os.path.join(HERE, "readings_child.py"), cell,
                  str(pinned["seed"])])
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    for side in ("program", "reference"):
        for key, want in pinned[side].items():
            assert got[side][key] == want, (side, key)


def test_declared_model_config_field_reaches_model_config():
    cfg = dict(load_cfg(), qk_norm=True, sliding_window=128,
               layer_pattern="local_global")
    mc = run.model_config(cfg)
    assert mc.qk_norm is True and mc.sliding_window == 128
    assert mc.layer_pattern == "local_global"
    assert (mc.name, mc.family, mc.n_layers, mc.d_ff) == (
        "paper-350m", "dense", 17, 4096)


def test_unknown_configuration_key_is_refused():
    with pytest.raises(ValueError, match="n_experts_typo"):
        run.model_config(dict(load_cfg(), n_experts_typo=8))


def test_harness_keys_are_not_model_fields():
    """What the program's ModelConfig takes from the file is every key
    but the harness's own."""
    from repro.configs.base import ModelConfig
    mc = run.model_config(load_cfg())
    for key in set(load_cfg()) - set(run.HARNESS_KEYS):
        assert getattr(mc, key) == load_cfg()[key], key
    assert not hasattr(ModelConfig, "batch_per_node")


def test_missing_family_module_names_the_file():
    with pytest.raises(FileNotFoundError,
                       match=re.escape(os.path.join("families",
                                                    "no-such.py"))):
        loader.family({"family": "no-such"})


def test_run_with_a_family_that_has_no_module_exits(tmp_path):
    """A configuration whose family has no module stops the run before it
    touches a device: non-zero, no result, the file it looked for named."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = tmp_path / "chipbench" / "configs" / "paper-350m.json"
    cfg = json.loads(path.read_text())
    path.write_text(json.dumps(dict(cfg, family="held-experts")))
    proc = child([str(tmp_path / "chipbench" / "run.py"), "--workload",
                  "p350m.full", "--seed", "1", "--seconds", "1"],
                 cwd=str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert os.path.join("families", "held-experts.py") in proc.stderr


def test_family_shapes_are_the_programs():
    from repro.models.registry import build_model
    import jax
    for c in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))[
            "configs"]:
        cfg = load_cfg(c["name"])
        specs = build_model(run.model_config(cfg)).param_specs()
        shapes = loader.family(cfg).param_shapes(cfg)
        assert jax.tree.map(lambda s: tuple(s.shape), specs) == \
            jax.tree.map(tuple, shapes,
                         is_leaf=lambda s: isinstance(s, tuple))


@pytest.mark.parametrize("module", ["run.py", "reference.py", "arith.py",
                                    "control.py"])
def test_no_model_equation_outside_the_families(module):
    """The model's leaves are named only in ``families/``."""
    with open(os.path.join(BENCH, module)) as f:
        text = f.read()
    for leaf in ("wq", "w_gate", "final_norm"):
        assert not re.search(rf"\b{leaf}\b", text), (module, leaf)
