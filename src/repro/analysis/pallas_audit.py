"""Pallas kernel audit: BlockSpec tiling vs declared operand shapes.

Every registered kernel carries implicit contracts the interpreter does
not enforce: each BlockSpec tile must divide its operand exactly per
dimension, its last two dims must be multiples of the TPU's (8, 128)
tile or equal the operand's own dims (Mosaic refuses anything else), and
the index map must keep every block inside the array for every grid
point — an off-by-one index map reads out of bounds on hardware while
silently clamping in interpret mode, which is exactly the class of bug a
CPU CI cannot catch dynamically.

The audit intercepts ``pl.pallas_call`` (no kernel body ever runs),
records (grid, specs, operand shapes) for each call, and statically
checks divisibility, the TPU tiling rule and index-map bounds.
``audit_kernels`` drives every public kernel entry point in
``repro.kernels`` through the interceptor on representative shapes.

Scalar-prefetch index maps (the producer-fused gather path) are
evaluated with a zero ref: the data-dependent ``perm[i]`` block index is
checked at its lower bound only — the runtime range contract for perms
(indices < NB+1) is pinned by the kernel tests, not this pass.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.analysis.report import AuditReport

PASS = "pallas_blockspec"

MAX_GRID_POINTS = 4096      # index-map evaluation cap per call
TPU_TILE = (8, 128)         # (sublane, lane) tile of a 32-bit vreg


@dataclasses.dataclass
class PallasCallRecord:
    kernel_name: str
    grid: Tuple[int, ...]
    in_specs: List[Any]
    out_specs: List[Any]
    in_shapes: List[Tuple[int, ...]]
    out_shapes: List[Tuple[int, ...]]
    num_scalar_prefetch: int = 0


def _as_list(x) -> list:
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


class _ZeroRef:
    """Stands in for the scalar-prefetch ref when evaluating index maps
    statically: every lookup returns block index 0."""

    def __getitem__(self, _):
        return 0


@contextlib.contextmanager
def capture_pallas_calls():
    """Intercept ``pl.pallas_call``: record call geometry, return zeros
    of ``out_shape`` instead of executing.  Patch the module attribute —
    kernel modules resolve ``pl.pallas_call`` at call time."""
    records: List[PallasCallRecord] = []
    orig = pl.pallas_call

    def fake_pallas_call(kernel, *args, out_shape=None, grid=None,
                         in_specs=None, out_specs=None, grid_spec=None,
                         **kw):
        nsp = 0
        if grid_spec is not None:
            grid = getattr(grid_spec, "grid", grid)
            in_specs = getattr(grid_spec, "in_specs", in_specs)
            out_specs = getattr(grid_spec, "out_specs", out_specs)
            nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)

        def run(*operands):
            outs = _as_list(out_shape)
            records.append(PallasCallRecord(
                kernel_name=getattr(kernel, "__name__", repr(kernel)),
                grid=tuple(int(g) for g in _as_list(grid)),
                in_specs=_as_list(in_specs),
                out_specs=_as_list(out_specs),
                in_shapes=[tuple(x.shape) for x in operands[nsp:]],
                out_shapes=[tuple(o.shape) for o in outs],
                num_scalar_prefetch=nsp))
            zeros = [jnp.zeros(o.shape, o.dtype) for o in outs]
            if isinstance(out_shape, (list, tuple)):
                return type(out_shape)(zeros)
            return zeros[0]

        return run

    pl.pallas_call = fake_pallas_call
    try:
        yield records
    finally:
        pl.pallas_call = orig


def _spec_geometry(spec) -> Tuple[Optional[tuple], Optional[Callable]]:
    block = getattr(spec, "block_shape", None)
    index_map = getattr(spec, "index_map", None)
    if callable(block):         # defensively handle a swapped BlockSpec
        block, index_map = index_map, block
    return (tuple(block) if block is not None else None), index_map


def tiling_violations(block, shape) -> List[int]:
    """Dims among the last two whose block size is neither a multiple of
    :data:`TPU_TILE` nor the operand's full extent (a squeezed ``None``
    dim counts as 1)."""
    bad = []
    for d, tile in zip(range(len(block) - 1, -1, -1), TPU_TILE[::-1]):
        b = 1 if block[d] is None else int(block[d])
        if b % tile and b != int(shape[d]):
            bad.append(d)
    return sorted(bad)


def _grid_points(grid: Tuple[int, ...]):
    total = 1
    for g in grid:
        total *= max(int(g), 1)
    if total <= MAX_GRID_POINTS:
        idx = np.arange(total)
    else:                       # sample ends + stride (bounds live there)
        idx = np.unique(np.concatenate([
            np.arange(64), np.arange(total - 64, total),
            np.arange(0, total, max(total // MAX_GRID_POINTS, 1))]))
    for flat in idx.tolist():
        if not grid:
            yield ()
            continue
        yield tuple(int(c) for c in np.unravel_index(flat, grid))


def check_record(rec: PallasCallRecord, report: AuditReport,
                 where: Optional[str] = None) -> None:
    """Tile divisibility + index-map bounds for one captured call."""
    where = where or rec.kernel_name
    pairs = (list(zip(rec.in_specs, rec.in_shapes, ["in"] * 99))
             + list(zip(rec.out_specs, rec.out_shapes, ["out"] * 99)))
    for spec, shape, kind in pairs:
        block, index_map = _spec_geometry(spec)
        if block is None:       # whole-array spec: nothing to tile-check
            continue
        if len(block) != len(shape):
            report.add(PASS, where,
                       f"{kind} BlockSpec rank {len(block)} != operand "
                       f"rank {len(shape)}",
                       details={"block": list(block),
                                "shape": list(shape)})
            continue
        bad_dims = [d for d, (b, s) in enumerate(zip(block, shape))
                    if b is not None and int(s) % int(b) != 0]
        if bad_dims:
            report.add(PASS, where,
                       f"{kind} block {tuple(block)} does not divide "
                       f"operand shape {tuple(shape)}",
                       details={"block": list(block),
                                "shape": list(shape),
                                "bad_dims": bad_dims})
            continue
        bad_tiles = tiling_violations(block, shape)
        if bad_tiles:
            report.add(PASS, where,
                       f"{kind} block {tuple(block)} breaks the TPU "
                       f"{TPU_TILE} tiling rule on operand "
                       f"{tuple(shape)}: its last two dims must be tile "
                       f"multiples or the operand's own dims",
                       details={"block": [b for b in block],
                                "shape": list(shape),
                                "bad_dims": bad_tiles})
            continue
        if index_map is None:
            continue
        nblocks = [int(s) // (1 if b is None else int(b))
                   for b, s in zip(block, shape)]
        extra = ((_ZeroRef(),) if rec.num_scalar_prefetch else ())
        for point in _grid_points(rec.grid):
            try:
                out = index_map(*point, *extra)
            except Exception as e:
                report.add(PASS, where,
                           f"index map raised at grid point {point}: "
                           f"{type(e).__name__}: {e}")
                break
            out = out if isinstance(out, tuple) else (out,)
            if len(out) != len(block):
                report.add(PASS, where,
                           f"index map returns {len(out)} block indices "
                           f"for a rank-{len(block)} block")
                break
            idxs = []
            for i in out:       # tracers/ZeroRef lookups stay unchecked
                try:
                    idxs.append(int(i))
                except Exception:
                    idxs.append(None)
            oob = [d for d, (i, n) in enumerate(zip(idxs, nblocks))
                   if i is not None and not 0 <= i < max(n, 1)]
            if oob:
                report.add(PASS, where,
                           f"index map sends grid point {tuple(int(p) for p in point)} "
                           f"out of bounds: block index {tuple(out)} vs "
                           f"{nblocks} blocks",
                           details={"grid_point": [int(p) for p in point],
                                    "block_index": [i for i in idxs
                                                    if i is not None],
                                    "n_blocks": nblocks})
                break


def audit_records(records: Sequence[PallasCallRecord],
                  report: AuditReport) -> None:
    report.ran(PASS)
    for rec in records:
        check_record(rec, report)


# ---------------------------------------------------------------------------
# registered-kernel sweep
# ---------------------------------------------------------------------------


def _kernel_cases() -> Dict[str, Callable[[], None]]:
    """One callable per public kernel entry point (the
    :mod:`repro.kernels.cases` table) on small shapes: 4 tiles of rows,
    a gather of 8 rows out of 12, k = 104.  Each calls the RAW function
    (``__wrapped__`` under the jit decorator) so the interceptor sees
    the eager ``pl.pallas_call``."""
    from repro.kernels.cases import kernel_cases
    from repro.kernels.topk_compress import ROWS

    def case_fn(case):
        fn = getattr(case.fn, "__wrapped__", case.fn)
        zeros = [jnp.zeros(a.shape, a.dtype) for a in case.args]
        return lambda: fn(*zeros, interpret=True, **dict(case.kw))

    return {c.name: case_fn(c)
            for c in kernel_cases(rows=4 * ROWS, nb=11, k=104)}


def audit_kernels(report: AuditReport) -> dict:
    """Capture + check every registered kernel entry point."""
    report.ran(PASS)
    cases = _kernel_cases()
    checked, failed = [], []
    for name, case in cases.items():
        with capture_pallas_calls() as records:
            try:
                case()
            except Exception as e:
                failed.append(name)
                report.add(PASS, name,
                           f"kernel entry point failed under capture: "
                           f"{type(e).__name__}: {e}")
                continue
        if not records:
            report.add(PASS, name,
                       "no pallas_call captured — entry point bypassed "
                       "the kernel path", severity="warning")
            continue
        for rec in records:
            check_record(rec, report, where=name)
        checked.append(name)
    return {"kernels_checked": checked, "kernels_failed": failed}
