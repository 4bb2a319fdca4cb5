"""Compile every Pallas kernel entry point for a described TPU v5e, at the
real bucket size (65 536-row tiles, gathers of 300 000 rows out of a
400 001-row buffer, k = 256).

Nothing runs: the chip's compiler refuses here what interpret mode
cannot see (untiled blocks, casts and reshapes Mosaic does not lower,
VMEM overflows).  The topology is described inside a fixture, so only
the worker that runs this file loads the TPU library; every compile
stays in this one file."""
import os
import re
import sys

import pytest

from repro.kernels.cases import REAL, kernel_cases

CASES = {c.name: c for c in kernel_cases(**REAL)}


@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    case = CASES[name]
    compiled = case.fn.lower(*case.specs(one_chip), interpret=False,
                             **dict(case.kw)).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def test_step_kernel_names_are_the_benchmarks(v5e_2x2, monkeypatch):
    """A four-pod acesync step compiled for the chip: every Pallas call
    keeps the name the benchmark's codec readers match (its jitted
    entry point's), each rung's encode and the peers' decode-accumulate
    show, and each call sits in the ``exchange`` phase."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import SMOKE_ARCHS
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.core.trainer import Trainer
    from repro.kernels import ops
    from repro.models.registry import build_model

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench"))
    import arith

    monkeypatch.setattr(ops, "default_use_pallas", lambda: True)
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    mesh = Mesh(np.array(v5e_2x2.devices).reshape(4, 1, 1),
                ("pod", "data", "model"))
    shape = ShapeConfig("names", 32, 8, "train")
    cfg = SMOKE_ARCHS["paper-350m"]
    run = RunConfig(model=cfg, shape=shape, total_steps=30, warmup_steps=2)
    model = build_model(cfg, run)
    tr = Trainer(model, run, mesh=mesh, strategy="acesync")
    rungs = sorted(set(arith.ENCODE_KERNELS.values())) + ["FULL"]
    names = [l.name for l in tr.scheduler.levels]
    plan = tr.scheduler.plan_from_levels(
        [names.index(rungs[g % len(rungs)])
         for g in range(len(tr.scheduler.sizes))],
        sync_interval=1, adaptive=True)
    ep = tr.exec_plan(plan)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tr.state_specs(), tr.state_shardings())
    fleet = NamedSharding(mesh, P("pod"))
    batch = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=fleet),
        model.input_specs(shape))
    hlo = tr.jit_step(ep).lower(state, batch,
                                tr.plan_arg_specs(ep)).compile().as_text()
    calls = [(ln.split(" = ", 1)[0].split()[-1].lstrip("%"),
              re.search(r'op_name="([^"]*)"', ln).group(1))
             for ln in hlo.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    encoded, decoded = set(), 0
    for name, op_name in calls:
        base = re.sub(r"\.\d+$", "", name)
        rung = [r for p, r in arith.ENCODE_KERNELS.items()
                if base.startswith(p)]
        assert rung or base.startswith(arith.DECODE_KERNELS), name
        encoded.update(rung)
        decoded += not rung
        assert "/exchange/" in op_name, (name, op_name)
    assert encoded == set(arith.ENCODE_KERNELS.values())
    assert decoded
