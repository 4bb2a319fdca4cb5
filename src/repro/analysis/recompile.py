"""Recompile-hazard audit: the plan must not widen the step cache.

The zero-retrace contract (pinned by tests/test_replan.py) hangs on the
:class:`~repro.core.planexec.ExecPlan` split: ``perms``/``omega``/``inv``
are pytree CHILDREN (device data — replans swap them without retracing) and
everything else is static aux hashed into ``static_key()``.  Three
drift modes silently break it:

  * a child leaf that is a Python scalar/list becomes a weak-typed trace
    constant — every new value is a new trace;
  * an aux field left out of ``static_key()`` makes two plans that lower
    differently share a cache entry (or, via pytree aux equality, still
    retrace while the documented key says they should not);
  * an unhashable aux field (list, np.ndarray) crashes or defeats the
    jit cache outright.

This pass checks a live ExecPlan instance against those modes, and
``audit_plan_pair`` asserts the documented cache identity: two plans
that differ only in device data must share a ``static_key``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np

from repro.core.planexec import ExecPlan

from repro.analysis.report import AuditReport

PASS = "recompile_hazard"

_CHILD_FIELDS = ("perms", "omega", "inv")


def _is_device_leaf(x) -> bool:
    return isinstance(x, (jax.Array, np.ndarray))


def audit_exec_plan(ep: ExecPlan, report: AuditReport,
                    where: str = "exec_plan") -> dict:
    """Static-key hygiene of one lowered plan."""
    report.ran(PASS)
    info: dict = {}

    # 1. the static key must be hashable (it IS the jit cache key)
    key = ep.static_key()
    try:
        hash(key)
        info["static_key_hashable"] = True
    except TypeError:
        info["static_key_hashable"] = False
        bad = []
        for i, part in enumerate(key):
            try:
                hash(part)
            except TypeError:
                bad.append(i)
        report.add(PASS, where,
                   "static_key() is unhashable — the compiled-step cache "
                   "cannot key on it",
                   details={"unhashable_positions": bad})

    # 2. children must be device data (arrays), never Python scalars or
    #    lists — those become per-value trace constants
    children = jax.tree.leaves(ep)
    n_bad_children = 0
    for leaf in children:
        if not _is_device_leaf(leaf):
            n_bad_children += 1
            report.add(PASS, where,
                       f"pytree child leaf of type {type(leaf).__name__} "
                       f"is a trace constant — every new value retraces",
                       details={"type": type(leaf).__name__,
                                "value": repr(leaf)[:80]})
    info["n_children"] = len(children)

    # 3. weak-typed children promote differently per call site: a weak
    #    omega forged from a Python float retraces against a strong one
    for name in _CHILD_FIELDS:
        val = getattr(ep, name, None)
        for leaf in jax.tree.leaves(val):
            if getattr(leaf, "weak_type", False):
                report.add(PASS, where,
                           f"child '{name}' carries a weak-typed array — "
                           f"dtype promotion differences will retrace",
                           details={"field": name,
                                    "dtype": str(leaf.dtype)})

    # 4. every aux (non-child) field must be folded into static_key():
    #    an unhashed field means two plans the cache treats as identical
    #    can lower different programs
    def _eq(a, b) -> bool:
        if isinstance(b, (jax.Array, np.ndarray)):
            return False
        try:
            return bool(a == b)
        except Exception:
            return False

    missing = []
    for f in dataclasses.fields(ep):
        if f.name in _CHILD_FIELDS:
            continue
        val = getattr(ep, f.name)
        if not any(_eq(val, part) for part in key):
            missing.append(f.name)
    if missing:
        report.add(PASS, where,
                   "plan field(s) missing from static_key() — the "
                   "compiled-step cache is wider than the documented "
                   "(bucket_sig, seg_sig) identity",
                   details={"missing_fields": missing})
    info["aux_fields_in_key"] = not missing

    # 5. aux fields must not hold device arrays (device data in a hash
    #    key pins buffers and compares by identity)
    for f in dataclasses.fields(ep):
        if f.name in _CHILD_FIELDS:
            continue
        for leaf in jax.tree.leaves(getattr(ep, f.name)):
            if isinstance(leaf, jax.Array):
                report.add(PASS, where,
                           f"aux field '{f.name}' holds a device array — "
                           f"static aux must be host data",
                           details={"field": f.name})
    return info


def audit_plan_pair(ep_a: ExecPlan, ep_b: ExecPlan, expect_same: bool,
                    report: AuditReport,
                    where: str = "exec_plan_pair") -> bool:
    """Assert the documented cache identity between two lowered plans:
    same (bucket/segment) signature -> same key (a replan that only moves
    device data must NOT retrace); different signature -> different key."""
    report.ran(PASS)
    same = ep_a.static_key() == ep_b.static_key()
    if same != expect_same:
        report.add(PASS, where,
                   ("plans that should share a compiled step have "
                    "different static keys — every replan would retrace"
                    if expect_same else
                    "plans with different schedules share a static key — "
                    "the cache would serve the wrong executable"),
                   details={"expect_same": expect_same, "same": same})
    return same == expect_same


def audit_trace_constants(fn_cache_size: int, n_distinct_plans: int,
                          report: AuditReport,
                          where: str = "step_cache") -> None:
    """Optional live check: stepping N same-signature plans through one
    jitted step must keep its trace cache at 1 entry."""
    report.ran(PASS)
    if fn_cache_size > 1:
        report.add(PASS, where,
                   f"compiled step retraced: {fn_cache_size} traces for "
                   f"{n_distinct_plans} same-signature plan(s)",
                   details={"cache_size": fn_cache_size})
