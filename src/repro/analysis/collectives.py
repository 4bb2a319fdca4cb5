"""Collective-schema audit: traced HLO schedule vs the ExecPlan's analytic.

Generalizes the hand-pinned assertions of ``tests/test_collectives.py`` /
``tests/test_hierarchy.py`` to ANY (strategy, codec, mesh, segments,
ring/bidir/hier) combination: the compiled step's collectives are
extracted with :func:`repro.analysis.hlo.extract_collectives` and diffed
against what :func:`repro.core.planexec.exec_wire_bytes` /
``exec_intra_bytes`` priced for the same :class:`ExecPlan`.

Invariants checked (all per device, the paper's accounting):
  * slow-tier traced bytes == analytic, up to the FULL-rung psum
    promotion slack (XLA promotes a bf16 all-reduce to f32 on CPU —
    exactly one extra copy of the FULL portion, since the analytic
    convention 2(P-1)/P * 2n already equals the bf16 wire volume);
  * fast-tier (intra-cluster) traced bytes == analytic, same slack rule
    for INTRA_FULL rungs;
  * ppermute count == sum over ringing rungs of K * (ring_width - 1);
  * every ppermute is a unit-stride ring hop (fwd/bwd half-rings only);
  * no sync-sized collective leaks onto a non-fleet mesh axis tuple that
    includes the pod axis unexpectedly.

Sub-threshold all-reduces (metric pmeans of scalar loss/gnorm/divergence)
are excluded: they are host telemetry, not the sync schedule.  Where
XLA's all-reduce combiner merged such pmeans into a sync all-reduce, only
that collective's sync-sized operands are counted.
"""
from __future__ import annotations

from typing import Iterator, Sequence, Tuple

from repro.core import planexec
from repro.core.compression import Level

from repro.analysis.hlo import CollectiveRecord, extract_collectives
from repro.analysis.report import AuditReport

PASS = "collective_schema"

# all-reduces below this payload are metric pmeans (f32 scalars), not sync
# traffic: the smallest real sync all-reduce is a 1-block FULL rung
# (1024 entries * 2B bf16 = 2 KiB).
METRIC_BYTES = 512.0


def _rungs(ep: planexec.ExecPlan
           ) -> Iterator[Tuple[Level, int, int, int]]:
    """Yield (level, sig_blocks, ring_chunks, hier_mode) per executed
    (segment, rung) piece — segmented plans execute seg_sig, not sig."""
    if ep.segmented:
        for ssig, sch, shier in zip(ep.seg_sig, ep.seg_chunks, ep.seg_hier):
            for r, s in enumerate(ssig):
                k = sch[r] if r < len(sch) else 0
                h = shier[r] if r < len(shier) else 0
                yield ep.levels[r], s, k, h
    else:
        for r, s in enumerate(ep.sig):
            k = ep.chunks[r] if r < len(ep.chunks) else 0
            h = ep.hier[r] if r < len(ep.hier) else 0
            yield ep.levels[r], s, k, h


def expected_schedule(ep: planexec.ExecPlan, n_pods: int,
                      n_edge: int = 1) -> dict:
    """The analytic schedule the compiled step must realise."""
    n_edge = max(int(n_edge), 1)
    n_cross = max(n_pods // n_edge, 1)
    permutes = 0
    ring_widths = set()
    full_slack = 0.0
    intra_full_slack = 0.0
    for level, s, k, h in _rungs(ep):
        if not s:
            continue
        ring_p = n_cross if h else n_pods
        if k:
            permutes += k * (ring_p - 1)
            ring_widths.add(ring_p)
        if level.is_full:
            full_slack += float(level.wire_bytes(s * ep.block, ring_p,
                                                 ep.block))
        if h == planexec.INTRA_FULL:
            from repro.codecs import build_codec
            intra_full_slack += float(build_codec("full").wire_bytes(
                s * ep.block, n_edge, ep.block))
    return {
        "slow_bytes": float(planexec.exec_wire_bytes(ep, n_pods, n_cross)),
        "intra_bytes": float(planexec.exec_intra_bytes(ep, n_edge)),
        "full_slack": full_slack,
        "intra_full_slack": intra_full_slack,
        "permutes": permutes,
        "ring_widths": sorted(ring_widths),
        "bidir": bool(ep.bidir),
        "n_pods": int(n_pods),
        "n_edge": int(n_edge),
        "n_cross": int(n_cross),
    }


def _is_metric(rec: CollectiveRecord) -> bool:
    return (rec.opcode == "all-reduce"
            and rec.payload_bytes < METRIC_BYTES)


def _sync_wire(rec: CollectiveRecord) -> float:
    """Per-device wire bytes of ``rec``'s sync traffic: a combined
    all-reduce's metric-sized operands are left out."""
    if rec.opcode != "all-reduce" or len(rec.operand_bytes) < 2:
        return rec.wire_bytes
    sync_n = sum(b for b in rec.operand_bytes if b >= METRIC_BYTES)
    return rec.wire_bytes * sync_n / rec.payload_bytes


def audit_collectives(hlo_text: str, ep: planexec.ExecPlan,
                      mesh_shape: Sequence[int],
                      axis_names: Sequence[str], n_pods: int,
                      n_edge: int, report: AuditReport,
                      where: str = "step") -> dict:
    """Diff the compiled step's collectives against ``ep``'s analytic
    schedule; append violations to ``report``.  Returns the traced
    summary (recorded into ``report.info`` by the driver)."""
    report.ran(PASS)
    want = expected_schedule(ep, n_pods, n_edge)
    records = extract_collectives(hlo_text, mesh_shape, axis_names)
    sync = [r for r in records if not _is_metric(r)]

    # tier classification: the slow tier is anything crossing the pod
    # axis — "pod" alone (cross-cluster ring / flat pod fleet) or the
    # combined "pod+edge" fleet gather of flat rungs on a hier mesh; the
    # fast tier is the intra-cluster "edge" exchange.
    slow = [r for r in sync if "pod" in r.axis.split("+")]
    fast = [r for r in sync if r.axis == "edge"]
    # pure data/model-axis collectives are legitimate auto-SPMD compute
    # (tensor-parallel psums); but the pod axis is shard_map-manual, so a
    # collective mixing it with a NON-fleet axis was never scheduled.
    mixed = [r for r in slow
             if set(r.axis.split("+")) - {"pod", "edge"}]

    traced_slow = sum(_sync_wire(r) * r.trip_mult for r in slow)
    traced_fast = sum(_sync_wire(r) * r.trip_mult for r in fast)

    def _within(traced: float, analytic: float, slack: float) -> bool:
        return analytic - 0.5 <= traced <= analytic + slack + 0.5

    if not _within(traced_slow, want["slow_bytes"], want["full_slack"]):
        report.add(PASS, where,
                   "slow-tier traced wire bytes diverge from the "
                   "ExecPlan analytic schedule",
                   details={"traced": traced_slow,
                            "analytic": want["slow_bytes"],
                            "full_promotion_slack": want["full_slack"]})
    if not _within(traced_fast, want["intra_bytes"],
                   want["intra_full_slack"]):
        report.add(PASS, where,
                   "fast-tier traced wire bytes diverge from the "
                   "ExecPlan analytic schedule",
                   details={"traced": traced_fast,
                            "analytic": want["intra_bytes"],
                            "full_promotion_slack":
                                want["intra_full_slack"]})

    permutes = [r for r in slow if r.opcode == "collective-permute"]
    n_permutes = int(round(sum(r.trip_mult for r in permutes)))
    if n_permutes != want["permutes"]:
        report.add(PASS, where,
                   "ppermute count diverges from the ring schedule "
                   "K * (P - 1) per ringing rung",
                   details={"traced": n_permutes,
                            "expected": want["permutes"],
                            "ring_widths": want["ring_widths"]})

    bad_dir = [r for r in permutes if r.direction == "other"]
    for r in bad_dir:
        report.add(PASS, where,
                   "collective-permute is not a unit-stride ring hop",
                   details={"source_target_pairs": r.source_target_pairs,
                            "axis": r.axis})
    directions = {r.direction for r in permutes} - {"other"}
    expect_both = (want["bidir"] and want["permutes"] > 0
                   and all(w >= 3 for w in want["ring_widths"]))
    if expect_both and directions == {"fwd"}:
        report.add(PASS, where,
                   "bidirectional ring requested but only forward-"
                   "half-ring ppermutes were traced", severity="warning",
                   details={"directions": sorted(directions)})

    for r in mixed:
        report.add(PASS, where,
                   f"sync-sized collective mixes the pod axis with a "
                   f"non-fleet axis '{r.axis}'",
                   details={"opcode": r.opcode, "axis": r.axis,
                            "wire_bytes": r.wire_bytes})

    traced = {
        "slow_bytes": traced_slow,
        "fast_bytes": traced_fast,
        "permutes": n_permutes,
        "directions": sorted(directions),
        "n_sync_collectives": len(sync),
        "n_metric_collectives": len(records) - len(sync),
    }
    return {"expected": want, "traced": traced}
