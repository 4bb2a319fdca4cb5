"""Sharding-constraint context.

Model code annotates activations with *logical* PartitionSpecs built from the
canonical axis names ("pod", "data", "model").  When a mesh is installed via
:func:`use_shard_ctx`, the constraints are applied after dropping any axis
the mesh does not have (e.g. single-pod meshes have no "pod" axis, smoke
tests have no mesh at all).  This lets the same model code run on a laptop
CPU and on a 512-chip multi-pod mesh.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

# canonical axes
BATCH_AXES = ("pod", "data")
MODEL_AXIS = "model"


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def current_exclude() -> tuple:
    return getattr(_state, "exclude", ())


@contextlib.contextmanager
def use_shard_ctx(mesh: Optional[Mesh], exclude: tuple = ()):
    """Install the ambient mesh for :func:`constrain`.

    ``exclude``: axis names that are MANUAL in the surrounding shard_map
    (e.g. ("pod",) inside the per-pod train step) — they are stripped from
    constraint specs because the arrays there are already per-pod local.
    """
    prev = getattr(_state, "mesh", None)
    prev_ex = getattr(_state, "exclude", ())
    _state.mesh = mesh
    _state.exclude = tuple(exclude)
    try:
        yield
    finally:
        _state.mesh = prev
        _state.exclude = prev_ex


def _norm_axis(ax, names) -> Optional[Union[str, tuple]]:
    """Drop axis names that the mesh doesn't have."""
    if ax is None:
        return None
    if isinstance(ax, str):
        return ax if ax in names else None
    kept = tuple(a for a in ax if a in names)
    if not kept:
        return None
    return kept if len(kept) > 1 else kept[0]


def norm_spec(spec: P, mesh: Mesh, exclude: tuple = ()) -> P:
    names = set(mesh.axis_names) - set(exclude)
    return P(*[_norm_axis(ax, names) for ax in spec])


def fit_spec(spec: P, shape, mesh: Mesh, exclude: tuple = ()) -> P:
    """norm_spec + drop axes whose size doesn't divide the array dim
    (e.g. batch=1 decode can't shard over data=16 — it becomes replicated)."""
    spec = norm_spec(spec, mesh, exclude)
    out = []
    for d, ax in enumerate(spec):
        if ax is None or d >= len(shape):
            out.append(None if d >= len(shape) else ax)
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        kept, prod = [], 1
        for a in axes:
            sz = mesh.shape[a]
            if shape[d] % (prod * sz) == 0:
                kept.append(a)
                prod *= sz
        out.append(tuple(kept) if len(kept) > 1
                   else (kept[0] if kept else None))
    return P(*out)


def constrain(x, spec: P):
    """with_sharding_constraint against the ambient mesh (no-op without
    one, and inside the fully-manual per-pod region)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    if set(current_exclude()) >= set(mesh.axis_names):
        return x  # fully-manual region: nothing left to constrain
    fitted = fit_spec(spec, x.shape, mesh, current_exclude())
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, fitted))


def batch_spec(*rest) -> P:
    """P(("pod","data"), *rest) — batch-sharded leading dim."""
    return P(BATCH_AXES, *rest)


def seq_spec(*rest) -> P:
    """P(("pod","data"), "model", *rest) — batch + sequence-parallel
    activations (Megatron-SP / Ulysses style): residual-stream tensors are
    sharded over "model" along the sequence dim so per-layer saved
    activations scale with the full chip count."""
    return P(BATCH_AXES, MODEL_AXIS, *rest)


def token_spec(*rest) -> P:
    """P(("pod","data","model"), *rest) — fully token-sharded flat (T, ...)
    tensors (MoE dispatch source layout)."""
    return P(BATCH_AXES + (MODEL_AXIS,), *rest)


def sharding_for(mesh: Mesh, spec: P, shape=None) -> NamedSharding:
    if shape is not None:
        return NamedSharding(mesh, fit_spec(spec, shape, mesh))
    return NamedSharding(mesh, norm_spec(spec, mesh))
