"""Compile every Pallas kernel entry point for a described TPU v5e, at the
real bucket size (65 536-row tiles, gathers of 300 000 rows out of a
400 001-row buffer, k = 256).

Nothing runs: the chip's compiler refuses here what interpret mode
cannot see (untiled blocks, casts and reshapes Mosaic does not lower,
VMEM overflows).  The topology is described inside a fixture, so only
the worker that runs this file loads the TPU library; every compile
stays in this one file."""
import os

import pytest

from repro.kernels.cases import REAL, kernel_cases

CASES = {c.name: c for c in kernel_cases(**REAL)}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    case = CASES[name]
    compiled = case.fn.lower(*case.specs(one_chip), interpret=False,
                             **dict(case.kw)).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
