"""Production mesh construction.

Single pod : (16, 16)    axes ("data", "model")   = 256 chips (v5e pod)
Multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips.

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax call).
"""
from __future__ import annotations

import jax


def _auto(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(shape, axes, devices=None):
    """Arbitrary mesh (tests / small simulations).

    ``devices``: explicit device list — the elastic-membership path builds
    a smaller mesh over the surviving subset of ``jax.devices()`` after a
    pod drops out (jax.make_mesh always spans the full inventory)."""
    shape, axes = tuple(shape), tuple(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))
    import numpy as np
    need = int(np.prod(shape))
    if len(devices) < need:
        raise ValueError(f"mesh {shape} needs {need} devices, "
                         f"got {len(devices)}")
    grid = np.asarray(devices[:need], dtype=object).reshape(shape)
    return jax.sharding.Mesh(grid, axes, axis_types=_auto(len(axes)))


# Hardware constants for the roofline analysis (TPU v5e)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (intra-pod)
DCN_BW = 6.25e9                   # bytes/s per pod-pair link (inter-pod,
                                  # 50 Gbit/s WAN-ish — the paper's regime)
