"""Device time per phase of the train step, from the window's profiler
trace.

The program names the phases of its step with ``jax.named_scope``:
``forward`` (its transpose, ``transpose(jvp(forward))``, is the backward
pass, recompute included), ``optimizer`` and ``exchange``, with the
exchange's sub-scopes (``importance``, ``pack``, ``encode_<RUNG>``,
``collective``, ``decode``, ``scatter``, ``unpack``).  They reach the
compiled HLO as each instruction's ``op_name``.  The trace's ``XLA Ops``
events name only the instruction, but the trace also carries, on its
``/host:metadata`` plane, the HLO proto of every module it ran, with that
metadata.  This module joins the two:

1. it reads each module's instructions and their ``op_name`` from the HLO
   proto (a small protobuf wire reader: no generated classes needed), an
   instruction of a called computation (a while body) taking its caller's
   path in front of its own;
2. it maps each instruction to a phase by :func:`phase_of`;
3. it keeps the ``XLA Ops`` events nested in the step modules'
   ``XLA Modules`` events (the step modules: the one with the most device
   time in the window and every other compiled from the same function,
   such as a pod cell's local and delta-exchange steps) and sums their
   self time per phase over the window, in the trace's clock
   (``tracereduce.clock_offset``), and apart from the phases the self
   time of their collective operations.

A program without the scopes reads no phase at all; the readers then
report nothing.
"""
from __future__ import annotations

import glob
import os
import re
import sys
import time
from collections import defaultdict

import tracereduce

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_ROOT = os.path.join(os.path.dirname(HERE), ".chipbench", "trace")
MODULES_LINE = "XLA Modules"
PHASES = ("forward", "backward", "optimizer", "exchange")
UNSCOPED = "unscoped"
#: the benchmark spans whose host times align the trace's clock: they end
#: before the profiler stops, so the trace holds the last of each
ALIGN_SPANS = ("data", "dispatch")

_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
_SUB = re.compile(r"(?:^|/)exchange/(importance|pack|unpack|collective|"
                  r"decode|scatter|encode_\w+)(?=/|$)")


def phase_of(op_name: str) -> str:
    """The phase of an instruction's ``op_name``, first match wins:
    ``transpose(`` with ``forward`` is backward, then forward, optimizer,
    exchange; anything else is unscoped."""
    words = set(_WORD.findall(op_name))
    if "forward" in words:
        return "backward" if "transpose(" in op_name else "forward"
    for phase in ("optimizer", "exchange"):
        if phase in words:
            return phase
    return UNSCOPED


def exchange_part(op_name: str) -> str:
    """The exchange sub-scope of an exchange op (``other`` outside one)."""
    m = _SUB.search(op_name)
    return m.group(1) if m else "other"


# ---------------------------------------------------------------------------
# protobuf wire format
# ---------------------------------------------------------------------------


def _varint(b, i: int):
    out = shift = 0
    while True:
        x = b[i]
        i += 1
        out |= (x & 0x7F) << shift
        if x < 0x80:
            return out, i
        shift += 7


def fields(b, lo: int = 0, hi: int = None):
    """(field number, value) of a message in ``b[lo:hi]``: an int for a
    varint, a (start, end) pair for a length-delimited field; fixed-width
    fields are skipped."""
    hi = len(b) if hi is None else hi
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield num, v
        elif wire == 2:
            n, i = _varint(b, i)
            yield num, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def _text(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def _ints(b, value):
    """A repeated int64 field's values, packed or not."""
    if isinstance(value, int):
        return [value]
    out, i = [], value[0]
    while i < value[1]:
        v, i = _varint(b, i)
        out.append(v)
    return out


# XSpace.planes = 1; XPlane.name = 2, event_metadata = 4 (map: key 1,
# value 2), stat_metadata = 5; XEventMetadata.name = 2, stats = 5;
# XStatMetadata.name = 2; XStat.metadata_id = 1, bytes_value = 6
def hlo_protos(raw: bytes) -> dict:
    """``{module name: HloProto bytes}`` from the ``/host:metadata`` plane
    of a serialised XSpace; the names are those of the ``XLA Modules``
    events (``jit_step(<program id>)``)."""
    b = memoryview(raw)
    out = {}
    for num, plane in fields(b):
        if num != 1:
            continue
        name, events, stat_names = None, [], {}
        for f, v in fields(b, *plane):
            if f == 2:
                name = _text(b, v)
            elif f == 4:
                events.append(v)
            elif f == 5:
                key = meta = None
                for ef, ev in fields(b, *v):
                    if ef == 1:
                        key = ev
                    elif ef == 2:
                        meta = ev
                if meta is not None:
                    stat_names[key] = next(
                        (_text(b, sv) for sf, sv in fields(b, *meta)
                         if sf == 2), None)
        if name != "/host:metadata":
            continue
        for entry in events:
            for ef, ev in fields(b, *entry):
                if ef != 2:
                    continue
                mod, proto = None, None
                for mf, mv in fields(b, *ev):
                    if mf == 2:
                        mod = _text(b, mv)
                    elif mf == 5:
                        stat = dict(fields(b, *mv))
                        if stat_names.get(stat.get(1)) == "Hlo Proto" \
                                and isinstance(stat.get(6), tuple):
                            proto = bytes(b[stat[6][0]:stat[6][1]])
                if mod is not None and proto is not None:
                    out[mod] = proto
    return out


# HloProto.hlo_module = 1; HloModuleProto.computations = 3,
# entry_computation_id = 6; HloComputationProto.instructions = 2, id = 5;
# HloInstructionProto.name = 1, metadata = 7 (OpMetadata.op_name = 2),
# id = 35, operand_ids = 36, called_computation_ids = 38
def instruction_op_names(hlo_proto: bytes) -> dict:
    """``{instruction name: op_name}`` of a module.

    An instruction of a called computation (a while body) carries its
    op_name relative to the caller's, so the caller's whole path is put in
    front of it.  An instruction with none takes the first op_name of the
    computations it calls (a fusion XLA built, such as a concatenation
    turned into dynamic-update-slices), else that of its first operand in
    a phase, else of its first named operand, else its caller's."""
    b = memoryview(hlo_proto)
    module = next((v for f, v in fields(b) if f == 1), None)
    if module is None:
        return {}
    comps, entry = {}, None
    for f, v in fields(b, *module):
        if f == 6:
            entry = v
        if f != 3:
            continue
        cid, instrs = None, []
        for cf, cv in fields(b, *v):
            if cf == 5:
                cid = cv
            elif cf == 2:
                iid, name, op_name, operands, called = None, None, "", [], []
                for inf, iv in fields(b, *cv):
                    if inf == 1:
                        name = _text(b, iv)
                    elif inf == 7:
                        op_name = next((_text(b, ov) for of, ov
                                        in fields(b, *iv) if of == 2), "")
                    elif inf == 35:
                        iid = iv
                    elif inf == 36:
                        operands += _ints(b, iv)
                    elif inf == 38:
                        called += _ints(b, iv)
                instrs.append((iid, name, op_name, operands, called))
        comps[cid] = instrs
    if entry not in comps:
        return {}

    def whole(op_name, prefix):
        if op_name.startswith("jit(") or not prefix:
            return op_name
        return prefix + "/" + op_name

    out = {}
    todo, seen = [(entry, "")], {entry}
    while todo:
        cid, prefix = todo.pop()
        resolved = {}
        for iid, name, op_name, operands, called in comps.get(cid, ()):
            if not op_name:
                op_name = next((o for c in called for _, _, o, _, _
                                in comps.get(c, ()) if o), "")
            if op_name:
                op_name = whole(op_name, prefix)
            else:
                named = [resolved[o] for o in operands if resolved.get(o)]
                op_name = next((o for o in named if phase_of(o) != UNSCOPED),
                               named[0] if named else prefix)
            out[name] = resolved[iid] = op_name
            for c in called:
                if c not in seen:
                    seen.add(c)
                    todo.append((c, op_name))
    return out


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------


def load(path: str) -> dict:
    """Module and op events per device plane, the benchmark's spans, and
    each module's ``{instruction: op_name}``, in the trace's clock."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    pd = ProfileData.from_serialized_xspace(raw)
    modules, ops, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            mods, evs = [], []
            for line in plane.lines:
                if line.name not in (MODULES_LINE, tracereduce.OPS_LINE):
                    continue
                into = mods if line.name == MODULES_LINE else evs
                for ev in line.events:
                    into.append((ev.name, ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9))
            modules[plane.name] = sorted(mods, key=lambda e: e[1])
            ops[plane.name] = sorted(evs, key=lambda e: (e[1], -e[2]))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(tracereduce.SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name[len(tracereduce.SPAN_PREFIX):],
                                      s, s + ev.duration_ns * 1e-9))
    hlo = {m: instruction_op_names(p) for m, p in hlo_protos(raw).items()}
    return {"modules": modules, "ops": ops, "spans": spans, "hlo": hlo}


def module_ops(mods, evs, module: str):
    """The op events nested in ``module``'s events (both sorted by
    start)."""
    out, j = [], 0
    for name, ms, me in mods:
        if name != module:
            continue
        while j < len(evs) and evs[j][1] < ms:
            j += 1
        k = j
        while k < len(evs) and evs[k][1] < me:
            if evs[k][2] <= me:
                out.append(evs[k])
            k += 1
        j = k
    return out


def step_module(modules: dict, lo: float, hi: float):
    """The module with the most device time in [lo, hi] over all planes."""
    total = defaultdict(float)
    for mods in modules.values():
        for name, s, e in mods:
            total[name] += max(0.0, min(e, hi) - max(s, lo))
    return max(total, key=total.get) if total else None


def module_base(module: str) -> str:
    """``jit_wrapped`` of ``jit_wrapped(12)``: the function a module was
    compiled from, without the program's id."""
    return module.split("(", 1)[0]


def step_modules(modules: dict, lo: float, hi: float):
    """The step module and every module compiled from the same function
    that ran in [lo, hi] (one per step kind), the step module first."""
    first = step_module(modules, lo, hi)
    if first is None:
        return []
    others = sorted({name for mods in modules.values()
                     for name, s, e in mods
                     if name != first and module_base(name)
                     == module_base(first) and e > lo and s < hi})
    return [first] + others


def is_collective(hlo: str) -> bool:
    """Whether an op moves data between chips: by its opcode, or by its
    name where the compiler wrapped the collective in another op."""
    name = tracereduce.short_name(hlo)
    return tracereduce.is_collective(hlo) or any(
        name.startswith(w) for w in tracereduce.COLLECTIVE_WORDS)


def phase_seconds(evs, op_names: dict, lo: float, hi: float):
    """Self seconds in [lo, hi] of the op events ``evs`` (sorted by start)
    per phase, per exchange sub-scope, and of the collective ops."""
    per, parts = defaultdict(float), defaultdict(float)
    coll = 0.0
    for hlo, s, e, own in tracereduce.self_times(evs):
        c = tracereduce.clip([(s, e)], lo, hi)
        if not c or e <= s:
            continue
        t = own * (c[0][1] - c[0][0]) / (e - s)
        op_name = op_names.get(tracereduce.short_name(hlo), "")
        phase = phase_of(op_name)
        per[phase] += t
        if phase == "exchange":
            parts[exchange_part(op_name)] += t
        if is_collective(hlo):
            coll += t
    return per, parts, coll


def reduce(tr: dict, lo: float, hi: float):
    """Per-phase and per-sub-scope self seconds of the step modules in the
    window [lo, hi] (trace clock), and the self seconds of their
    collective ops, averaged over the device planes, and the step
    module's name."""
    modules = step_modules(tr["modules"], lo, hi)
    if not modules:
        return None
    n = max(len(tr["ops"]), 1)
    per, parts = defaultdict(float), defaultdict(float)
    coll = 0.0
    for module in modules:
        names = tr["hlo"].get(module, {})
        for plane, evs in tr["ops"].items():
            p, q, c = phase_seconds(
                module_ops(tr["modules"].get(plane, []), evs, module),
                names, lo, hi)
            for k, v in p.items():
                per[k] += v / n
            for k, v in q.items():
                parts[k] += v / n
            coll += c / n
    return {"module": modules[0], "modules": modules, "phases": dict(per),
            "exchange": dict(parts), "collective": coll}


# ---------------------------------------------------------------------------
# what the readers call
# ---------------------------------------------------------------------------


def window_trace(t0: float):
    """The newest trace under ``.chipbench/trace``, if it was written
    after the window started (``t0``: ``perf_counter`` seconds)."""
    found = glob.glob(os.path.join(TRACE_ROOT, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    started = time.time() - (time.perf_counter() - t0)
    return path if os.path.getmtime(path) >= started else None


_CACHE: dict = {}


def reading(ctx: dict):
    """Device ms per host step of each phase of the step modules in the
    traced window (``exchange`` and ``collective``, the collective ops'
    self time, per exchanging step), or None where the trace or the
    program's scopes give nothing.  Read once per run; a
    trace it cannot read is logged and reads as nothing, never stopping
    the run."""
    key = (ctx["t0"], ctx["t_end"])
    if key not in _CACHE:
        try:
            _CACHE[key] = _read(ctx)
        except Exception as e:  # noqa: BLE001 - a metric never fails the run
            print(f"[trace] scopes: unreadable ({type(e).__name__}: {e})",
                  file=sys.stderr, flush=True)
            _CACHE[key] = None
    return _CACHE[key]


def _read(ctx: dict):
    path = window_trace(ctx["t0"])
    if path is None or not ctx["host_steps"]:
        return None
    tr = load(path)
    host = [(n, s, e) for n in ALIGN_SPANS
            for s, e in ctx["spans"].rec.get(n, ())]
    off = tracereduce.clock_offset(tr["spans"], host)
    red = reduce(tr, ctx["t0"] + off, ctx["t_end"] + off)
    if red is None or not any(red["phases"].get(p) for p in PHASES):
        return None
    steps, syncs = ctx["host_steps"], ctx["sync_steps"]
    ms = {p: 1e3 * red["phases"].get(p, 0.0) / steps
          for p in PHASES + (UNSCOPED,)}
    ms["exchange"] = (1e3 * red["phases"].get("exchange", 0.0) / syncs
                      if syncs else None)
    ms["collective"] = 1e3 * red["collective"] / syncs if syncs else None
    total = 1e3 * sum(red["phases"].values()) / steps
    parts = " ".join(f"{k}={1e3 * v / max(syncs, 1):.3f}"
                     for k, v in sorted(red["exchange"].items()))
    print(f"[trace] scopes modules={red['modules']} "
          f"step_self_ms={total:.3f} "
          + " ".join(f"{p}={ms[p]}" for p in PHASES + (UNSCOPED,))
          + f" | exchange ms per exchanging step: {parts}",
          file=sys.stderr, flush=True)
    return ms
