"""Reduce a profiler trace of the measured window to the numbers the
per-layer metrics read: device busy time (the union of the intervals in
which an operation ran), each operation's self time, collective time, the
Pallas kernel calls, and the idle gaps by what the host was doing.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event
per operation run, named by its whole HLO instruction
(``%quantize_int8_gather.3 = (s8[...], ...) custom-call(...), ...``).
Events nest: a ``while`` loop's event spans the ops of its body, so an
op's time here is its self time.  A Pallas kernel is a ``custom-call``
with ``custom_call_target="tpu_custom_call"``, named after the kernel's
function.  The benchmark's host spans are ``TraceAnnotation``s named
``chipbench.<span>`` on the host plane; they put the host's
``time.perf_counter`` and the trace's clock side by side.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
#: HLO opcodes of the operations that move data between chips
COLLECTIVE_WORDS = ("all-gather", "all-reduce", "collective-permute",
                    "all-to-all", "reduce-scatter", "send", "recv")
#: host events shorter than this do not label an idle gap
HOST_EVENT_MIN_S = 1e-4
#: the longest idle gaps that are labelled and reported
MAX_GAPS = 10


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(hlo: str) -> str:
    """``fusion.3`` of ``%fusion.3 = f32[...] fusion(...)``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")


def opcode(hlo: str) -> str:
    """``fusion`` of ``%fusion.3 = f32[8]{0} fusion(...)``: the first
    lower-case word after the result type that opens an operand list."""
    m = _OPCODE.search(" " + hlo.split(" = ", 1)[-1])
    return m.group(1) if m else ""


_LAYOUT = re.compile(r"\{[^{}]*\}")
_KIND = re.compile(r"\bkind=(k\w+)")


def describe(hlo: str) -> str:
    """``fusion.3 = (f32[8,1024], bf16[8]) fusion kLoop``: an
    instruction's name, result types without layouts, opcode and fusion
    kind, for a reader of the breakdown who has no HLO dump."""
    name, rest = (hlo.split(" = ", 1) + [""])[:2]
    op = opcode(hlo)
    types = rest.split(" " + op + "(", 1)[0] if op else rest
    kind = _KIND.search(rest)
    text = " ".join(x for x in (name.lstrip("%"), "=",
                                _LAYOUT.sub("", types).strip(), op,
                                kind.group(1) if kind else "") if x)
    return text[:200]


def is_collective(hlo: str) -> bool:
    """Whether the instruction's opcode moves data between chips."""
    op = opcode(hlo)
    return any(op.startswith(w) for w in COLLECTIVE_WORDS)


def load(path: str):
    """Device op events, benchmark spans and host events of one trace, in
    the trace's clock: ``{"devices": {plane: [(hlo, start_s, end_s)]},
    "spans": [(name, start_s, end_s)], "host": [(name, start_s, end_s)]}``.
    """
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans, host = {}, [], []
    names = {}          # one copy of each instruction's (long) text
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name = ev.name
                    evs.append((names.setdefault(name, name),
                                ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9))
            devices[plane.name] = sorted(evs, key=lambda e: (e[1], -e[2]))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                python = line.name.startswith(("python", "main"))
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = (ev.start_ns + ev.duration_ns) * 1e-9
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):], s, e))
                    elif python and e - s >= HOST_EVENT_MIN_S:
                        host.append((ev.name, s, e))
    return {"devices": devices, "spans": sorted(spans, key=lambda x: x[1]),
            "host": host}


def clock_offset(trace_spans, host_spans) -> float:
    """Seconds to add to a host ``perf_counter`` time to get the trace's
    clock: the median over spans matched by name and order."""
    by_name = defaultdict(list)
    for n, s, _ in trace_spans:
        by_name[n].append(s)
    host = defaultdict(list)
    for n, s, _ in sorted(host_spans, key=lambda x: x[1]):
        host[n].append(s)
    diffs = []
    for n, ts in by_name.items():
        hs = host.get(n, [])
        # the trace holds the last spans recorded: align from the end
        k = min(len(ts), len(hs))
        diffs += [t - h for t, h in zip(ts[-k:], hs[-k:])] if k else []
    if not diffs:
        raise ValueError("no benchmark span found in the trace")
    diffs.sort()
    return diffs[len(diffs) // 2]


def union(intervals: List[Tuple[float, float]]):
    """Merged, sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def self_times(evs):
    """[(hlo, start, end, self seconds)] of events sorted by start: each
    event's duration less the durations of the events directly inside
    it."""
    out = []
    stack = []          # indices into out of the open enclosing events
    for hlo, s, e in evs:
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        out.append([hlo, s, e, e - s])
        if stack:
            out[stack[-1]][3] -= e - s
        stack.append(len(out) - 1)
    return out


def label_gap(a: float, b: float, spans, host) -> str:
    """What the host was doing in the idle gap [a, b]: the benchmark span
    that covers most of it if that covers half, else the longest-covering
    event of a Python thread, else ``host``."""
    def best(events):
        name, over = None, 0.0
        for n, s, e in events:
            o = min(b, e) - max(a, s)
            if o > over:
                name, over = n, o
        return name, over
    name, over = best(spans)
    if name is not None and over >= 0.5 * (b - a):
        return name
    name, _ = best(host)
    return name if name is not None else "host"


def reduce_events(devices: Dict[str, list], trace_spans, lo: float,
                  hi: float, host=()) -> dict:
    """Numbers of the window [lo, hi] (trace clock), averaged over the
    device planes: busy seconds, per-op self seconds, collective seconds,
    the Pallas calls (``{name: {"hlo", "s", "n"}}``: self seconds and
    calls), and the longest idle gaps of the first plane labelled by what
    the host was doing."""
    n = max(len(devices), 1)
    busy = coll = 0.0
    per_op = defaultdict(float)
    desc = {}
    pallas = {}
    gaps = []
    first = sorted(devices)[0] if devices else None
    for plane, evs in sorted(devices.items()):
        iv = []
        for hlo, s, e, own in self_times(evs):
            c = clip([(s, e)], lo, hi)
            if not c:
                continue
            cs, ce = c[0]
            iv.append((cs, ce))
            t = own * (ce - cs) / (e - s) if e > s else 0.0
            name = short_name(hlo)
            per_op[name] += t / n
            desc.setdefault(name, hlo)
            if is_collective(hlo):
                coll += t / n
            if PALLAS_TARGET in hlo:
                k = pallas.setdefault(name, {"hlo": hlo, "s": 0.0, "n": 0.0})
                k["s"] += t / n
                k["n"] += 1.0 / n
        merged = union(iv)
        busy += sum(e - s for s, e in merged) / n
        if plane == first:
            edges = [lo] + [x for m in merged for x in m] + [hi]
            gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2])
                    if b > a]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:MAX_GAPS]
    labelled = [[label_gap(a, b, trace_spans, host), b - a]
                for a, b in longest]
    top = sorted(([describe(desc[k]), v] for k, v in per_op.items()),
                 key=lambda x: -x[1])
    return {"busy_s": busy, "window_s": hi - lo, "top_ops": top,
            "idle_gaps": labelled, "collective_s": coll, "pallas": pallas,
            "per_op": dict(per_op)}


def reduce_dir(trace_dir: str, t0: float, t_end: float, spans,
               traced: tuple) -> dict:
    """Reduce the trace in ``trace_dir`` over the host window
    [t0, t_end] (``perf_counter`` seconds), given the benchmark's
    ``Spans`` and the host times ``traced`` = (start, stop) between which
    the profiler recorded."""
    tr = load(find_xplane(trace_dir))
    rec = [(n, s, e) for n, v in spans.rec.items() for s, e in v
           if s >= traced[0] and e <= traced[1]]
    off = clock_offset(tr["spans"], rec)
    return reduce_events(tr["devices"], tr["spans"], t0 + off, t_end + off,
                         tr["host"])
