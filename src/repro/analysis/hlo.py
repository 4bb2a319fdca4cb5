"""Trip-count-aware HLO parser + cost model (library home).

Relocated from ``benchmarks/hlo_cost.py`` (which remains as a compat
shim): this is a library imported by tests, the dry-run harness and the
graph auditor, so it lives in the package.

XLA's ``compiled.cost_analysis()`` counts a ``while`` body ONCE, so any
scan-over-layers model under-reports FLOPs/bytes/collectives by the trip
count (verified empirically on this container).  This walker parses the
post-optimisation HLO text, recurses into fusions / while bodies / calls /
conditionals, multiplies while bodies by their ``known_trip_count``, and
classifies every collective by WHICH MESH AXES vary inside its replica
groups — giving per-axis wire bytes ("pod" = the paper's cloud-edge uplink).

Cost conventions (documented in EXPERIMENTS.md):
  * dot/convolution: 2 * out_elems * contraction_size FLOPs;
  * elementwise / reduce: 1 FLOP per output (resp. input) element;
  * bytes_accessed: operand + output bytes at fusion granularity (a fusion
    is one read of its inputs + one write of its outputs — the HBM-traffic
    proxy);
  * collective wire bytes per participant: all-reduce 2(G-1)/G * n,
    all-gather / reduce-scatter / all-to-all (G-1)/G * n_full,
    collective-permute n.

On top of the aggregate :class:`CostReport`, :func:`extract_collectives`
returns the flat per-op collective schedule (opcode, mesh-axis class,
bytes, ring direction) the collective-schema auditor diffs against the
:class:`~repro.core.planexec.ExecPlan` analytic schedule.
"""
from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16, "token": 0, "opaque": 0,
    "s4": 1, "u4": 1,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


# ---------------------------------------------------------------------------
# shape parsing
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Shape:
    dtype: str
    dims: Tuple[int, ...]

    @property
    def elems(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n

    @property
    def bytes(self) -> int:
        return self.elems * _DTYPE_BYTES.get(self.dtype, 4)


_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def parse_shapes(type_str: str) -> List[Shape]:
    """All array shapes inside a (possibly tuple) type string."""
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dt = m.group(1)
        if dt not in _DTYPE_BYTES:
            continue
        dims = tuple(int(x) for x in m.group(2).split(",") if x)
        out.append(Shape(dt, dims))
    return out


def shapes_bytes(shapes: Sequence[Shape]) -> int:
    return sum(s.bytes for s in shapes)


# ---------------------------------------------------------------------------
# HLO text parsing
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class HloOp:
    var: str
    shapes: List[Shape]
    opcode: str
    operands: List[str]
    raw: str


@dataclasses.dataclass
class HloComputation:
    name: str
    ops: List[HloOp]
    shape_of: Dict[str, List[Shape]]


_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->")
_PARAM_RE = re.compile(r"([\w.\-]+):\s*((?:\([^)]*\)|[a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?))")
_VAR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_OPCODE_RE = re.compile(r"\s*([\w\-]+)\(")


def _parse_op_line(line: str):
    """-> (var, type_str, opcode, rest_after_open_paren) or None.

    Handles tuple result types with nested parens and /*index=N*/ comments.
    """
    vm = _VAR_RE.match(line)
    if not vm:
        return None
    var = vm.group(1)
    i = vm.end()
    if i < len(line) and line[i] == "(":
        depth, j = 1, i + 1
        while j < len(line) and depth:
            if line[j] == "(":
                depth += 1
            elif line[j] == ")":
                depth -= 1
            j += 1
        type_str = line[i:j]
    else:
        tm = re.match(r"[a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?", line[i:])
        if not tm:
            return None
        j = i + tm.end()
        type_str = line[i:j]
    om = _OPCODE_RE.match(line[j:])
    if not om:
        return None
    return var, type_str, om.group(1), line[j + om.end():]


def parse_module(text: str) -> Tuple[Dict[str, HloComputation], Optional[str]]:
    comps: Dict[str, HloComputation] = {}
    entry: Optional[str] = None
    cur: Optional[HloComputation] = None
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped or stripped.startswith("//"):
            continue
        if stripped.startswith("HloModule"):
            continue
        # computation headers start at column 0 and end with "{"
        if not line.startswith(" ") and stripped.rstrip().endswith("{"):
            m = _COMP_RE.match(stripped)
            if m:
                cur = HloComputation(m.group(1), [], {})
                comps[cur.name] = cur
                if stripped.startswith("ENTRY"):
                    entry = cur.name
                # parameters: name: type pairs (header params carry no
                # nested tuples on this backend; regex pairing suffices)
                for pm in _PARAM_RE.finditer(m.group(2)):
                    cur.shape_of[pm.group(1)] = parse_shapes(pm.group(2))
                continue
        if stripped == "}":
            cur = None
            continue
        if cur is None:
            continue
        parsed = _parse_op_line(line)
        if not parsed:
            continue
        var, type_str, opcode, rest = parsed
        # operand references up to the closing paren of the operand list
        depth, i = 1, 0
        while i < len(rest) and depth > 0:
            if rest[i] == "(":
                depth += 1
            elif rest[i] == ")":
                depth -= 1
            i += 1
        operand_str = rest[:i - 1] if depth == 0 else rest
        operands = re.findall(r"%([\w.\-]+)", operand_str)
        op = HloOp(var, parse_shapes(type_str), opcode, operands, line)
        cur.ops.append(op)
        cur.shape_of[var] = op.shapes
    return comps, entry


# ---------------------------------------------------------------------------
# replica-group -> mesh-axis classification
# ---------------------------------------------------------------------------


def _parse_source_target_pairs(raw: str) -> Optional[List[List[int]]]:
    """collective-permute carries source_target_pairs, not replica_groups;
    each {src,dst} pair is classified like a 2-element group (the mesh
    axes that vary between the endpoints are the axes the transfer
    crosses — "pod" for the ring exchange's ppermutes)."""
    m = re.search(r"source_target_pairs=\{(\{[^=]*?\})\}", raw)
    if not m:
        return None
    pairs = []
    for g in re.findall(r"\{([\d,\s]*)\}", m.group(1)):
        pairs.append([int(x) for x in g.split(",") if x.strip()])
    return pairs or None


def _parse_replica_groups(raw: str) -> Optional[List[List[int]]]:
    """Handles explicit {{0,1},{2,3}} and iota [G,N]<=[dims]T(perm) forms."""
    m = re.search(r"replica_groups=\{(\{[^=]*?\})\}", raw)
    if m:
        groups = []
        for g in re.findall(r"\{([\d,\s]*)\}", m.group(1)):
            groups.append([int(x) for x in g.split(",") if x.strip()])
        return groups
    m = re.search(
        r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?",
        raw)
    if m:
        a, b = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        iota = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            iota = iota.transpose(perm)
        return iota.reshape(a, b).tolist()
    return None


def classify_axes(groups: Optional[List[List[int]]],
                  mesh_shape: Sequence[int],
                  axis_names: Sequence[str]) -> Tuple[str, int]:
    """-> (axis-class label like "pod" / "data" / "pod+data", group size)."""
    if not groups:
        return ("unknown", 1)
    g0 = groups[0]
    if len(g0) <= 1:
        return ("none", 1)
    coords = np.array(np.unravel_index(np.array(g0), mesh_shape)).T
    varying = [axis_names[i] for i in range(len(mesh_shape))
               if len(set(coords[:, i])) > 1]
    return ("+".join(varying) if varying else "none", len(g0))


def permute_direction(pairs: Optional[List[List[int]]],
                      mesh_shape: Sequence[int]) -> str:
    """Ring direction of a collective-permute's source-target pairs.

    Along the single varying mesh axis, a hop of +1 (mod size) is "fwd"
    and -1 is "bwd" (the two half-rings of the bidirectional exchange).
    Anything else — multi-axis hops, stride > 1, mixed deltas within one
    op — is "other" and flags a schedule the cost model never priced.
    On a 2-wide axis +1 == -1; that degenerate hop reports "fwd".
    """
    if not pairs:
        return "other"
    deltas = set()
    for pair in pairs:
        if len(pair) != 2:
            return "other"
        src, dst = pair
        sc = np.unravel_index(src, mesh_shape)
        dc = np.unravel_index(dst, mesh_shape)
        varying = [i for i in range(len(mesh_shape)) if sc[i] != dc[i]]
        if len(varying) != 1:
            return "other"
        ax = varying[0]
        size = int(mesh_shape[ax])
        d = (int(dc[ax]) - int(sc[ax])) % size
        if d == 1:
            deltas.add("fwd")
        elif d == size - 1:
            deltas.add("bwd")
        else:
            return "other"
    if len(deltas) != 1:
        return "other"
    return deltas.pop()


# ---------------------------------------------------------------------------
# cost walking
# ---------------------------------------------------------------------------


_TRIP_RE = re.compile(r'known_trip_count[\\"]*:\s*\{[\\"]*n[\\"]*:[\\"]*(\d+)')
_CALL_RE = re.compile(r"(?:calls|body|condition|to_apply|branch_computations)="
                      r"\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")


@dataclasses.dataclass
class CostReport:
    flops: float = 0.0
    bytes_accessed: float = 0.0
    transcendentals: float = 0.0
    collective_bytes: Dict[str, float] = dataclasses.field(
        default_factory=lambda: defaultdict(float))
    collective_count: Dict[str, int] = dataclasses.field(
        default_factory=lambda: defaultdict(int))
    op_flops: Dict[str, float] = dataclasses.field(
        default_factory=lambda: defaultdict(float))

    def add(self, other: "CostReport", mult: float = 1.0):
        self.flops += other.flops * mult
        self.bytes_accessed += other.bytes_accessed * mult
        self.transcendentals += other.transcendentals * mult
        for k, v in other.collective_bytes.items():
            self.collective_bytes[k] += v * mult
        for k, v in other.collective_count.items():
            self.collective_count[k] += int(v * mult)
        for k, v in other.op_flops.items():
            self.op_flops[k] += v * mult


_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "sign", "compare", "select", "and", "or", "xor", "not",
    "clamp", "floor", "ceil", "round-nearest-afz", "round-nearest-even",
    "convert", "bitcast-convert", "copy", "shift-left",
    "shift-right-logical", "shift-right-arithmetic", "remainder", "atan2",
    "power", "is-finite", "stochastic-convert",
}
_TRANSCENDENTAL = {"exponential", "log", "tanh", "rsqrt", "sqrt", "logistic",
                   "sine", "cosine", "expm1", "log1p", "erf", "cbrt"}
_ZERO_COST = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "optimization-barrier", "partition-id", "replica-id",
    "domain", "iota", "rng-get-and-update-state", "custom-call",
    "get-dimension-size",
}


class CostWalker:
    def __init__(self, comps: Dict[str, HloComputation],
                 mesh_shape: Sequence[int], axis_names: Sequence[str]):
        self.comps = comps
        self.mesh_shape = tuple(mesh_shape)
        self.axis_names = tuple(axis_names)
        self._cache: Dict[str, CostReport] = {}

    # -- per-op costs ----------------------------------------------------
    def _dot_flops(self, op: HloOp, comp: HloComputation) -> float:
        out_elems = sum(s.elems for s in op.shapes)
        m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.raw)
        lhs_shapes = comp.shape_of.get(op.operands[0]) if op.operands else None
        contraction = 1
        if m and lhs_shapes:
            lhs = lhs_shapes[0]
            for d in m.group(1).split(","):
                if d:
                    contraction *= lhs.dims[int(d)]
        return 2.0 * out_elems * contraction

    def _conv_flops(self, op: HloOp, comp: HloComputation) -> float:
        out_elems = sum(s.elems for s in op.shapes)
        rhs_shapes = comp.shape_of.get(op.operands[1]) \
            if len(op.operands) > 1 else None
        if not rhs_shapes:
            return 2.0 * out_elems
        kernel = rhs_shapes[0]
        fgc = 1
        m = re.search(r"feature_group_count=(\d+)", op.raw)
        if m:
            fgc = int(m.group(1))
        # kernel elems already include in/out channel dims; per output elem
        # the contraction is kernel_elems / out_channels
        m2 = re.search(r"dim_labels=\S*?->\S*", op.raw)
        out_ch = kernel.dims[-1] if kernel.dims else 1
        contraction = max(1, kernel.elems // max(out_ch, 1))
        return 2.0 * out_elems * contraction

    def _collective(self, op: HloOp, rep: CostReport, comp: HloComputation):
        rec = collective_record(op, comp, self.mesh_shape, self.axis_names)
        rep.collective_bytes[rec.axis] += rec.wire_bytes
        rep.collective_count[rec.axis] += 1

    # -- computation walk -------------------------------------------------
    def comp_cost(self, name: str) -> CostReport:
        if name in self._cache:
            return self._cache[name]
        comp = self.comps.get(name)
        rep = CostReport()
        if comp is None:
            return rep
        self._cache[name] = rep  # break cycles
        for op in comp.ops:
            self._op_cost(op, comp, rep)
        return rep

    def _op_cost(self, op: HloOp, comp: HloComputation, rep: CostReport):
        opc = op.opcode
        out_elems = sum(s.elems for s in op.shapes)
        out_bytes = shapes_bytes(op.shapes)
        in_bytes = sum(shapes_bytes(comp.shape_of.get(v, []))
                       for v in op.operands)

        if opc in _ZERO_COST:
            return
        # sliced-access ops touch only the slice, not the whole operand
        if opc in ("dynamic-slice", "slice"):
            rep.bytes_accessed += 2 * out_bytes
            return
        if opc == "dynamic-update-slice":
            upd = (shapes_bytes(comp.shape_of.get(op.operands[1], []))
                   if len(op.operands) > 1 else out_bytes)
            rep.bytes_accessed += 2 * upd
            return
        if opc == "gather":
            idx = (shapes_bytes(comp.shape_of.get(op.operands[1], []))
                   if len(op.operands) > 1 else 0)
            rep.bytes_accessed += 2 * out_bytes + idx
            return
        if opc == "scatter":
            upd = (shapes_bytes(comp.shape_of.get(op.operands[2], []))
                   if len(op.operands) > 2 else out_bytes)
            rep.bytes_accessed += 3 * upd
            return
        if opc == "fusion":
            m = _CALL_RE.search(op.raw)
            boundary = in_bytes + out_bytes
            if m:
                sub = self.comp_cost(m.group(1).split(",")[0].strip(" %"))
                # flops from inside; bytes: min(fusion boundary, internal
                # slice-aware traffic) — a fusion that only dynamic-slices a
                # big operand reads the slice, not the operand
                rep.flops += sub.flops
                rep.transcendentals += sub.transcendentals
                for k, v in sub.collective_bytes.items():
                    rep.collective_bytes[k] += v
                rep.op_flops["fusion"] += sub.flops
                rep.bytes_accessed += min(boundary,
                                          sub.bytes_accessed + out_bytes)
            else:
                rep.bytes_accessed += boundary
            return
        if opc == "while":
            m = _TRIP_RE.search(op.raw)
            trip = int(m.group(1)) if m else 1
            calls = dict(re.findall(r"(body|condition)=%?([\w.\-]+)", op.raw))
            body = self.comp_cost(calls.get("body", ""))
            cond = self.comp_cost(calls.get("condition", ""))
            rep.add(body, trip)
            rep.add(cond, trip)
            return
        if opc in ("call", "async-start", "async-done"):
            m = _CALL_RE.search(op.raw)
            if m:
                rep.add(self.comp_cost(m.group(1).split(",")[0].strip(" %")))
            return
        if opc == "conditional":
            m = re.search(r"branch_computations=\{([^}]*)\}", op.raw)
            branches = []
            if m:
                branches = [b.strip(" %") for b in m.group(1).split(",")]
            else:
                tm = re.findall(r"(?:true|false)_computation=%?([\w.\-]+)",
                                op.raw)
                branches = tm
            if branches:
                costs = [self.comp_cost(b) for b in branches]
                worst = max(costs, key=lambda c: c.flops)
                rep.add(worst)
            rep.bytes_accessed += in_bytes + out_bytes
            return
        if any(opc.startswith(c) for c in COLLECTIVES):
            if not opc.endswith("-done"):  # async pairs: count -start only
                self._collective(op, rep, comp)
            rep.bytes_accessed += in_bytes + out_bytes
            return
        # compute ops
        if opc == "dot":
            f = self._dot_flops(op, comp)
            rep.flops += f
            rep.op_flops["dot"] += f
        elif opc == "convolution":
            f = self._conv_flops(op, comp)
            rep.flops += f
            rep.op_flops["convolution"] += f
        elif opc in ("reduce", "reduce-window"):
            in_elems = sum(s.elems for v in op.operands
                           for s in comp.shape_of.get(v, []))
            rep.flops += in_elems
            rep.op_flops["reduce"] += in_elems
        elif opc in _TRANSCENDENTAL:
            rep.flops += out_elems
            rep.transcendentals += out_elems
            rep.op_flops["transcendental"] += out_elems
        elif opc in _ELEMENTWISE or opc in (
                "broadcast", "reshape", "transpose", "slice", "pad",
                "concatenate", "dynamic-slice", "dynamic-update-slice",
                "gather", "scatter", "select-and-scatter", "reverse",
                "sort", "rng", "rng-bit-generator", "map", "reduce-precision",
                "cholesky", "triangular-solve", "exponential-minus-one"):
            if opc in _ELEMENTWISE:
                rep.flops += out_elems
                rep.op_flops["elementwise"] += out_elems
            elif opc == "sort":
                in_elems = sum(s.elems for v in op.operands
                               for s in comp.shape_of.get(v, []))
                lg = math.log2(max(op.shapes[0].dims[-1], 2)) \
                    if op.shapes and op.shapes[0].dims else 1.0
                rep.flops += in_elems * lg
                rep.op_flops["sort"] += in_elems * lg
        rep.bytes_accessed += in_bytes + out_bytes


def analyze(hlo_text: str, mesh_shape: Sequence[int],
            axis_names: Sequence[str]) -> CostReport:
    comps, entry = parse_module(hlo_text)
    walker = CostWalker(comps, mesh_shape, axis_names)
    if entry is None:
        # fall back: largest computation
        entry = max(comps, key=lambda n: len(comps[n].ops)) if comps else ""
    return walker.comp_cost(entry)


# ---------------------------------------------------------------------------
# per-collective schedule extraction (the auditor's view)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CollectiveRecord:
    """One collective op on the executed path, with loop multiplicity."""
    opcode: str                 # normalised: "-start" stripped
    axis: str                   # mesh-axis class ("pod", "edge", "pod+edge")
    group_size: int
    payload_bytes: float        # operand (reduce-like) / output (gather-like)
    wire_bytes: float           # per-participant, CostReport conventions
    trip_mult: float            # product of enclosing while trip counts
    direction: str              # collective-permute: fwd / bwd / other; else ""
    source_target_pairs: Optional[List[List[int]]]
    computation: str
    raw: str
    #: bytes of each operand (an all-reduce that XLA's combiner merged
    #: from several reductions carries one operand per reduction)
    operand_bytes: Tuple[float, ...] = ()


def collective_record(op: HloOp, comp: HloComputation,
                      mesh_shape: Sequence[int],
                      axis_names: Sequence[str],
                      trip_mult: float = 1.0) -> CollectiveRecord:
    """Classify one collective op: axis, bytes, ring direction."""
    groups = _parse_replica_groups(op.raw)
    pairs = None
    if op.opcode.startswith("collective-permute"):
        pairs = _parse_source_target_pairs(op.raw)
        if groups is None:
            groups = pairs
    axis, gsize = classify_axes(groups, mesh_shape, axis_names)
    opc = op.opcode.replace("-start", "")
    each = tuple(float(shapes_bytes(comp.shape_of.get(v, [])))
                 for v in op.operands)
    operand_bytes = sum(each)
    out_bytes = shapes_bytes(op.shapes)
    if opc == "all-reduce":
        n = float(operand_bytes or out_bytes)
        wire = 2.0 * (gsize - 1) / max(gsize, 1) * n
    elif opc in ("all-gather", "all-to-all"):
        n = float(out_bytes)
        wire = (gsize - 1) / max(gsize, 1) * n
    elif opc == "reduce-scatter":
        n = float(operand_bytes or out_bytes)
        wire = (gsize - 1) / max(gsize, 1) * n
    else:  # collective-permute
        n = float(out_bytes)
        wire = n
    direction = ""
    if opc == "collective-permute":
        direction = permute_direction(pairs, mesh_shape)
    return CollectiveRecord(
        opcode=opc, axis=axis, group_size=gsize, payload_bytes=n,
        wire_bytes=wire, trip_mult=trip_mult, direction=direction,
        source_target_pairs=pairs, computation=comp.name, raw=op.raw,
        operand_bytes=each)


class _CollectiveCollector:
    """Walks the call graph like :class:`CostWalker` but keeps every
    collective as a separate record (the cost walker only aggregates)."""

    def __init__(self, comps: Dict[str, HloComputation],
                 mesh_shape: Sequence[int], axis_names: Sequence[str]):
        self.comps = comps
        self.mesh_shape = tuple(mesh_shape)
        self.axis_names = tuple(axis_names)
        self.records: List[CollectiveRecord] = []

    def walk(self, name: str, mult: float = 1.0,
             stack: frozenset = frozenset()):
        comp = self.comps.get(name)
        if comp is None or name in stack:
            return
        stack = stack | {name}
        for op in comp.ops:
            opc = op.opcode
            if opc == "fusion" or opc in ("call", "async-start",
                                          "async-done"):
                m = _CALL_RE.search(op.raw)
                if m:
                    self.walk(m.group(1).split(",")[0].strip(" %"),
                              mult, stack)
            elif opc == "while":
                tm = _TRIP_RE.search(op.raw)
                trip = int(tm.group(1)) if tm else 1
                calls = dict(re.findall(r"(body|condition)=%?([\w.\-]+)",
                                        op.raw))
                self.walk(calls.get("body", ""), mult * trip, stack)
                self.walk(calls.get("condition", ""), mult * trip, stack)
            elif opc == "conditional":
                m = re.search(r"branch_computations=\{([^}]*)\}", op.raw)
                branches = ([b.strip(" %") for b in m.group(1).split(",")]
                            if m else re.findall(
                                r"(?:true|false)_computation=%?([\w.\-]+)",
                                op.raw))
                for b in branches:
                    self.walk(b, mult, stack)
            elif any(opc.startswith(c) for c in COLLECTIVES):
                if not opc.endswith("-done"):  # async: count -start only
                    self.records.append(collective_record(
                        op, comp, self.mesh_shape, self.axis_names, mult))


def extract_collectives(hlo_text: str, mesh_shape: Sequence[int],
                        axis_names: Sequence[str]) -> List[CollectiveRecord]:
    """Every collective on the executed path of the entry computation,
    with while-loop trip multiplicity — the traced schedule the
    collective-schema auditor diffs against the analytic one."""
    comps, entry = parse_module(hlo_text)
    if entry is None:
        entry = max(comps, key=lambda n: len(comps[n].ops)) if comps else ""
    collector = _CollectiveCollector(comps, mesh_shape, axis_names)
    collector.walk(entry)
    return collector.records
