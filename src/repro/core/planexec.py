"""Plan-as-data: the executable, retrace-free form of a SyncPlan.

The scheduler's :class:`~repro.core.scheduler.SyncPlan` is a host-side
policy object (one ladder-rung index per parameter group).  Baking it into
the jitted train step as a static argument meant every replan risked a
fresh XLA compile — up to L^G variants for G groups over an L-rung ladder.
:class:`ExecPlan` is the same plan lowered to *data*:

  * every parameter group is laid out block-aligned in one static flat
    (NB, block) buffer (``leaf_layout``), computed once per (model, mesh);
  * per rung, a gather permutation ``perm_r: int32[S_r]`` of block indices
    repacks the member groups into one contiguous per-rung buffer, and
    one inverse permutation ``inv: int32[NB]`` maps every layout row back
    to the row of *bucket space* (the rung buffers end to end) that holds
    it, so the exchange's outputs return to the leaves by gather.  Perms and inverse are ordinary device arrays —
    replans swap them without retracing;
  * only the tuple of padded per-rung block counts — the **bucket-shape
    signature** — plus the per-rung **chunk grid** of the ring exchange is
    static.  Rung sizes are rounded up to a small geometric ladder of size
    classes (:func:`pad_block_class`; the growth is scheduled per rung by
    :func:`rung_growth` — big rungs take finer classes, tiny rungs coarser
    ones), so assignments that shuffle groups between rungs without
    crossing a class boundary hit the warm jit cache.  The padding is real
    zeros on the wire and is priced explicitly by
    ``repro.codecs.plan_wire_bytes``.

The jit cache is therefore keyed on ``(levels, sig, chunks, block)`` — a
handful of variants per run — instead of the full per-group assignment.

Chunk grid (the ring exchange)
------------------------------
Rungs whose bucket is big enough to be DCN-bound run a chunked,
double-buffered ring pipeline (``Codec.ef_sync_ring``): the bucket is
split into K chunks exchanged with ``jax.lax.ppermute`` so the transfer
of chunk *i* hides the decode-accumulate of chunk *i-1*.
:func:`ring_chunk_count` picks K per rung from the roofline constants in
``repro.launch.mesh`` (DCN 6.25 GB/s vs HBM 819 GB/s); K is rounded to a
power-of-two class and the padded rung size to a K multiple, so the grid
is a deterministic function of the (already class-rounded) signature —
replans that keep the signature keep the chunk grid, and the step stays
retrace-free.  ``chunks[r] == 0`` means the one-shot ``all_gather`` path
(small buckets, psum codecs, single pod).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import BLOCK, Level
from repro.launch.mesh import DCN_BW, HBM_BW, ICI_BW

#: default geometric growth of the padded-size ladder.  2.0 gives pure
#: power-of-two classes (fewest signatures, up to 2x wire padding); the
#: default 1.125 bounds the padding overhead at 12.5% while still
#: absorbing replan-to-replan bucket jitter.  Smaller growth -> less
#: padding on the wire but more distinct bucket signatures (more
#: compiles); 1.0 disables padding entirely (exact sizes — right for
#: strategies whose plan never changes).  Tunable per run via
#: ``ACESyncConfig.bucket_pad_growth``; the *effective* growth is
#: scheduled per rung by :func:`rung_growth`.
PAD_GROWTH = 1.125

#: per-hop launch overhead of a pod-axis ``ppermute`` (DCN round-trip
#: setup; WAN-ish link per the paper's regime).  The ring pipeline pays
#: K*(P-1) of these to hide the decode, so rungs whose total DCN time is
#: not >> this latency stay on the one-shot path.
RING_HOP_LATENCY_S = 10e-6

#: never split a rung into more chunks than this: each extra chunk adds a
#: ppermute launch and shrinks the per-transfer payload toward the
#: latency floor.
RING_MAX_CHUNKS = 16

#: target per-chunk DCN transfer time.  Big enough to amortise
#: RING_HOP_LATENCY_S (~50x), small enough that the first chunk lands
#: quickly and the decode pipeline fills.
RING_TARGET_CHUNK_S = 500e-6


def n_blocks(n: int, block: int = BLOCK) -> int:
    return (int(n) + block - 1) // block


def pad_block_class(nb: int, growth: float = PAD_GROWTH) -> int:
    """Smallest size class >= ``nb`` blocks on a geometric ladder
    (1, 2, 4, 8, ... at the default growth of 2).  0 stays 0: an unused
    rung is absent from the trace entirely."""
    if nb <= 0:
        return 0
    if not growth or growth <= 1.0:
        return int(nb)
    c = 1
    while c < nb:
        c = max(c + 1, int(math.ceil(c * growth)))
    return c


#: floor of the scheduled pad growth: huge rungs never pad more than
#: ~3.1% — but also never get finer classes than this, so replan-to-
#: replan jitter still lands in class (collapsing to near-exact sizes
#: would reintroduce the per-replan retraces the class ladder exists to
#: prevent).
MIN_RUNG_GROWTH = 1.03125

#: rung size (blocks) where the growth schedule starts decaying from the
#: base.  Below this the padding is a few KB — not worth narrower (=
#: jitter-fragile) classes; above it the decay keeps the ABSOLUTE class
#: width at (base-1)*32 blocks (~4 at the 1.125 default) until the
#: MIN_RUNG_GROWTH floor takes over and the width grows as ~3.1% of nb.
RUNG_GROWTH_KNEE = 32


def rung_growth(nb: int, base: Optional[float]) -> Optional[float]:
    """Per-rung pad-growth schedule (ROADMAP knob).

    The flat default charged every rung the same relative padding; but
    the overhead that matters is byte-weighted, so big rungs want *finer*
    classes (12.5% of a multi-MB bucket is real DCN time) while tiny
    rungs want *coarser* ones (their absolute padding is a few KB and
    fewer classes means fewer compiled variants).  Scheduled, monotone in
    nb, and careful to keep classes wide enough in ABSOLUTE blocks that
    steady-state replan jitter never crosses a class boundary:

      * nb <= 4 blocks: power-of-two classes (at most 1-2 pad blocks);
      * nb <= RUNG_GROWTH_KNEE: the configured base growth (full flat
        absorption; padding bytes are negligible here);
      * larger: the excess over 1.0 decays as KNEE/nb — constant
        ~(base-1)*KNEE-block class width — floored at
        :data:`MIN_RUNG_GROWTH`, so a 4096-block rung pads <= ~3.1%
        while its classes stay >= ~128 blocks wide.

    ``BENCH_step_time.json`` records the resulting classes and the
    byte-weighted ``padding_overhead_frac`` per run.
    """
    if not base or base <= 1.0:
        return base
    if nb <= 4:
        return max(base, 2.0)
    if nb <= RUNG_GROWTH_KNEE:
        return base
    # floored at MIN_RUNG_GROWTH (or at base itself when the user asked
    # for something even finer than the floor)
    return max(1.0 + (base - 1.0) * (RUNG_GROWTH_KNEE / nb),
               min(base, MIN_RUNG_GROWTH))


def scheduled_block_class(nb: int, base: Optional[float]) -> int:
    """Smallest size class >= ``nb`` on the SINGLE scheduled ladder.

    Unlike evaluating :func:`pad_block_class` with a per-``nb`` growth
    (which would give every queried size its own ladder — a class "map"
    that is neither monotone nor a partition, so two replans one block
    apart could each be their own class and retrace), the ladder here is
    built once with the step growth evaluated at the LADDER VALUE:
    ``c -> max(c + 1, ceil(c * rung_growth(c, base)))``.  The resulting
    class function is a true monotone partition of the block counts —
    idempotent, with class widths that follow the schedule (coarse below
    the knee, ~(base-1)*KNEE blocks just above it, ~3.1% of the rung in
    the floor regime)."""
    if nb <= 0:
        return 0
    if not base or base <= 1.0:
        return int(nb)
    c = 1
    while c < nb:
        g = rung_growth(c, base)
        c = max(c + 1, int(math.ceil(c * g)))
    return c


def bucket_signature(level_idx: Sequence[int], sizes: Sequence[int],
                     n_levels: int, block: int = BLOCK,
                     growth: Optional[float] = None) -> Tuple[int, ...]:
    """Padded per-rung block counts — the static jit-cache key of the
    exchange.  ``growth=None`` gives exact (unpadded) bucket sizes; a
    float is the *base* growth of the scheduled class ladder
    (:func:`scheduled_block_class`)."""
    per = [0] * n_levels
    for li, n in zip(level_idx, sizes):
        per[int(li)] += n_blocks(n, block)
    if growth:
        per = [scheduled_block_class(nb, growth) for nb in per]
    return tuple(per)


def ring_hops(n_pods: int, bidir: bool = True) -> int:
    """Sequential hops on the ring's critical path: the bidirectional
    ring splits the P-1 receives over two independent half-rings (forward
    ⌈(P-1)/2⌉, backward ⌊(P-1)/2⌋), so full-duplex DCN links finish in
    ⌈(P-1)/2⌉ sequential hop times — up to 2x effective bandwidth at the
    same total ppermute count and wire bytes."""
    if n_pods <= 1:
        return 0
    return (n_pods // 2) if bidir else (n_pods - 1)


def ring_chunk_count(level: Level, nb: int, n_pods: int,
                     block: int = BLOCK,
                     ring: Optional[int] = None,
                     bidir: bool = True) -> int:
    """Chunk count K for one rung (0 = one-shot ``all_gather`` fallback).

    Roofline heuristic over the ``launch.mesh`` constants: the ring
    pipeline hides the per-chunk decode (HBM-bound, ~819 GB/s) behind the
    DCN transfer of the next chunk (6.25 GB/s — >100x slower per byte, so
    the decode always fits under the wire once the bucket is big enough),
    at the cost of K*(P-1) ppermute launches.  A rung rings when its
    per-hop DCN time dominates the hop latency; K targets
    ~RING_TARGET_CHUNK_S of wire time per chunk-hop, clamped to
    [2, RING_MAX_CHUNKS] and rounded to a power-of-two class so the grid
    — like the signature it derives from — is stable across replans.
    ``bidir`` shortens the critical path to :func:`ring_hops` sequential
    hops, which only moves the latency thresholds (per-hop wire time is
    P-independent).

    ``ring``: None = the heuristic; 0 (or negative) = force one-shot;
    K > 0 = force K chunks on every ring-capable rung (tests, benches).

    Cross-pod determinism: the ring is bit-deterministic on ANY pod
    count — P = 2 trivially (two-term sums commute), P >= 3 through the
    codecs' order-insensitive accumulation (fixed-point partial sums /
    integer vote counts, canonical-order buffering for top-k; see
    ``Codec.ef_sync_ring``), so the auto heuristic rings every mesh and
    forced rings share the same deterministic fold.
    """
    codec = level.codec
    if (n_pods <= 1 or nb <= 0
            or not getattr(codec, "supports_ring", False)):
        return 0
    if ring is not None:
        return 0 if ring <= 0 else min(int(ring), nb)
    payload = codec.payload_bytes(nb * block, block)
    hops = ring_hops(n_pods, bidir)
    hop_t = payload / DCN_BW             # per-hop wire time (full payload)
    # decode reads the payload + reads/writes the f32 accumulator per
    # received peer — all P-1 of them, whichever direction they arrive by
    decode_t = (payload + 8.0 * nb * block) * (n_pods - 1) / HBM_BW
    # not worth pipelining: the decode we could hide is smaller than the
    # launch overhead of even a 2-chunk ring
    if decode_t < 2 * hops * RING_HOP_LATENCY_S:
        return 0
    if hop_t < 8 * RING_HOP_LATENCY_S:
        return 0  # latency-bound already; chunking only adds hops
    k = int(round(hop_t / RING_TARGET_CHUNK_S))
    k = max(2, min(RING_MAX_CHUNKS, nb, k))
    k = 1 << (k - 1).bit_length()        # power-of-two chunk class
    return min(k, RING_MAX_CHUNKS, nb)


def ring_override(ring_chunks: int) -> Optional[int]:
    """Translate ``ACESyncConfig.ring_chunks`` (0 = auto, -1 = never,
    K = force K) into the ``ring`` argument of :func:`ring_chunk_count` /
    :func:`exec_grid` / ``sync_tree`` (None = auto, <= 0 = force
    one-shot, K = force K).  The ONE place the two sentinel conventions
    meet — pass config values through here, never raw."""
    return None if ring_chunks == 0 else int(ring_chunks)


# ---------------------------------------------------------------------------
# two-tier (hierarchical) exchange: per-rung tier choice
# ---------------------------------------------------------------------------

#: hier grid entries: 0 = flat (single-tier) exchange; 1 = two-tier with a
#: full-precision (bf16 psum) intra-cluster stage; 2 = two-tier with an
#: INT8 gather+fold intra-cluster stage.
INTRA_FULL = 1
INTRA_INT8 = 2


def hier_override(hier_mode_cfg: int) -> Optional[int]:
    """Translate ``ACESyncConfig.hier_mode`` (0 = roofline auto, -1 =
    never two-tier, 1/2 = force full/INT8 intra stage) into the ``hier``
    argument of :func:`hier_rung_mode` / :func:`exec_grid` (None = auto,
    <= 0 = force flat, 1/2 = force)."""
    return None if hier_mode_cfg == 0 else int(hier_mode_cfg)


def hier_rung_mode(level: Level, nb: int, n_cross: int, n_edge: int,
                   block: int = BLOCK, hier: Optional[int] = None) -> int:
    """Tier choice for one rung on a (n_cross clusters) x (n_edge members)
    fleet: 0 = flat, :data:`INTRA_FULL` / :data:`INTRA_INT8` = two-tier.

    A hier-capable rung (``codec.supports_hier`` — dense formats whose
    cluster aggregate re-encodes losslessly enough without a second error-
    feedback stage) ALWAYS goes two-tier on a hierarchical fleet: its
    cross-tier volume drops from (C*E - 1) to (C - 1) payloads per device
    regardless of rung size.  The roofline only picks the INTRA stage —
    full-precision (bf16 psum on the fast links, lossless tier-1) while
    its ICI time hides under the DCN transfer of the cross tier, INT8
    gather+fold once the edge group is wide enough that a dense bf16
    intra stage would dominate the wall clock.  Like the ring chunk grid,
    the choice is a deterministic function of (signature, mesh constants)
    — replans that keep the signature keep the tier grid, and the step
    stays retrace-free.

    ``hier``: None = the heuristic; <= 0 = force flat; 1/2 = force the
    full/INT8 intra stage on every hier-capable rung (tests, benches).
    """
    codec = level.codec
    if (n_edge <= 1 or n_cross <= 1 or nb <= 0
            or not getattr(codec, "supports_hier", False)):
        return 0
    if hier is not None:
        if hier <= 0:
            return 0
        return INTRA_INT8 if hier >= 2 else INTRA_FULL
    from repro.codecs import build_codec
    n = nb * block
    cross_t = (n_cross - 1) * codec.payload_bytes(n, block) / DCN_BW
    intra_full_t = build_codec("full").wire_bytes(n, n_edge, block) / ICI_BW
    return INTRA_FULL if intra_full_t <= cross_t else INTRA_INT8


def exec_grid(level_idx: Sequence[int], sizes: Sequence[int],
              levels: Sequence[Level], n_pods: int, block: int = BLOCK,
              growth: Optional[float] = None,
              ring: Optional[int] = None, bidir: bool = True,
              n_edge: int = 1, hier: Optional[int] = None
              ) -> Tuple[Tuple[int, ...], Tuple[int, ...],
                         Tuple[int, ...]]:
    """(sig, chunks, hier) of the executed exchange: the class-padded
    signature with each ringing rung rounded up to a chunk multiple, plus
    the per-rung tier grid (:func:`hier_rung_mode`).  The ONE place the
    executed static shape is decided — the Scheduler's plan pricing and
    ``build_exec_plan`` both call it, so analytic bytes match the traced
    collectives, chunk padding and tier split included.

    ``n_pods`` is the FLEET size (clusters x edge members); ``n_edge`` > 1
    makes it a hierarchical fleet of ``n_pods // n_edge`` clusters.  Two-
    tier rungs ring over the CROSS axis (cluster count); flat rungs on a
    hierarchical fleet gather over the combined (pod, edge) axis in one
    shot — ``ppermute`` cannot span a tuple axis, so they never ring."""
    sig = list(bucket_signature(level_idx, sizes, len(levels), block,
                                growth))
    n_edge = max(int(n_edge), 1)
    n_cross = max(n_pods // n_edge, 1)
    chunks, hgrid = [], []
    for r, nb in enumerate(sig):
        h = hier_rung_mode(levels[r], nb, n_cross, n_edge, block, hier)
        if h:
            k = ring_chunk_count(levels[r], nb, n_cross, block, ring,
                                 bidir)
        elif n_edge > 1:
            k = 0
        else:
            k = ring_chunk_count(levels[r], nb, n_pods, block, ring,
                                 bidir)
        if k > 1 and nb % k:
            sig[r] = nb = ((nb + k - 1) // k) * k
        chunks.append(k)
        hgrid.append(h)
    return tuple(sig), tuple(chunks), tuple(hgrid)


def sig_wire_bytes(sig: Sequence[int], levels: Sequence[Level],
                   n_pods: int, block: int = BLOCK,
                   hier: Optional[Sequence[int]] = None,
                   n_cross: Optional[int] = None) -> int:
    """Per-device wire bytes of an executed exchange with bucket signature
    ``sig`` over the bandwidth-constrained (cross) tier — what the slow-
    tier collectives actually move, padding included.  The ring path moves
    exactly the all_gather receive volume (K chunks x (P-1) hops x chunk
    payload), so chunking never changes the per-rung pricing — only the
    chunk-multiple rounding in :func:`exec_grid` (already folded into
    ``sig``) does.  With a ``hier`` tier grid, two-tier rungs cross the
    slow tier once per CLUSTER (``n_cross`` peers) instead of once per
    fleet member — the headline wire-byte cut of the hierarchy."""
    total = 0
    for r, S in enumerate(sig):
        if not S:
            continue
        pods = n_pods
        if hier and r < len(hier) and hier[r] and n_cross:
            pods = n_cross
        total += levels[r].wire_bytes(S * block, pods, block)
    return int(total)


def sig_intra_bytes(sig: Sequence[int], levels: Sequence[Level],
                    n_edge: int, block: int = BLOCK,
                    hier: Optional[Sequence[int]] = None) -> int:
    """Fast-tier (intra-cluster) per-device wire bytes of a hierarchical
    exchange: the tier-1 aggregation volume of each two-tier rung, priced
    by the intra codec the tier grid selected (bf16 psum or INT8 gather).
    Flat rungs move nothing on the fast tier (their single collective is
    priced by :func:`sig_wire_bytes` at the fleet count)."""
    if not hier or n_edge <= 1:
        return 0
    from repro.codecs import build_codec
    total = 0
    for r, S in enumerate(sig):
        if not S or not (r < len(hier) and hier[r]):
            continue
        name = "full" if hier[r] == INTRA_FULL else "int8"
        total += build_codec(name).wire_bytes(S * block, n_edge, block)
    return int(total)


# ---------------------------------------------------------------------------
# leaf layout: computed once per (model, mesh), threaded through replans
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LeafLayout:
    """Where each leaf lands in the static flat (NB, block) buffer.

    Depends only on (leaf local sizes, block) — never on the plan — so the
    Trainer builds it ONCE at construction and every replan's
    :func:`build_exec_plan` reuses it instead of re-deriving block counts
    and start offsets from the full pytree (the host-side replan overhead
    the PR-4 satellite removes)."""
    sizes: Tuple[int, ...]
    block: int
    nbs: Tuple[int, ...]
    starts: Tuple[int, ...]          # block offset of each leaf
    total_blocks: int


def leaf_layout(sizes: Sequence[int], block: int = BLOCK) -> LeafLayout:
    nbs = tuple(n_blocks(n, block) for n in sizes)
    starts, off = [], 0
    for nb in nbs:
        starts.append(off)
        off += nb
    return LeafLayout(sizes=tuple(int(n) for n in sizes), block=block,
                      nbs=nbs, starts=tuple(starts), total_blocks=off)


# ---------------------------------------------------------------------------
# backward segmentation: the static per-rung layer -> segment schedule
# ---------------------------------------------------------------------------


def config_segments(cfg) -> int:
    """Backward segment count a config asks for: 1 (barriered) unless
    ``overlap_backward`` is on, else ``backward_segments`` (0 = defer to
    :func:`auto_segments` once the layout is known).  The single source of
    truth shared by the Trainer (lowering) and the Scheduler (plan
    signatures) — they must agree or replans would mispredict the
    compiled-step cache key."""
    if not getattr(cfg, "overlap_backward", False):
        return 1
    return int(getattr(cfg, "backward_segments", 0))


def auto_segments(layout: LeafLayout) -> int:
    """Default backward segment count (``backward_segments = 0``): two
    segments on any multi-leaf model.  Two is the sweet spot on the
    roofline — the deep half's encode+collective issues while the shallow
    half's backward still runs (most of the latency win of finer splits),
    while per-piece class padding and collective launch overhead stay at
    one extra piece per rung."""
    return 2 if len(layout.sizes) > 1 else 1


def segment_leaf_bounds(layout: LeafLayout, segments: int
                        ) -> Tuple[int, ...]:
    """Leaf-index boundaries splitting the layout into ``segments``
    contiguous leaf ranges balanced by block count — the static backward
    schedule.  Depends only on the layout (never the plan), so every
    replan shares the same segmentation and the per-(segment, rung) piece
    sizes stay a function of the bucket signature alone (retrace-free).

    Returns ``segments + 1`` monotonically increasing bounds with
    ``bounds[0] == 0`` and ``bounds[-1] == n_leaves`` (fewer when there
    are not enough leaves to populate every segment).  Leaf order is tree
    order: backward produces the DEEP (late) leaves' gradients first, so
    the streaming path walks segments in reverse."""
    n = len(layout.sizes)
    segments = max(1, min(int(segments), max(n, 1)))
    if segments <= 1 or n <= 1:
        return (0, n)
    total = max(layout.total_blocks, 1)
    bounds = [0]
    cum = 0
    for i, nb in enumerate(layout.nbs):
        cum += nb
        # cut after leaf i once this segment holds its block-count share
        if (len(bounds) < segments
                and cum * segments >= len(bounds) * total
                and i + 1 < n):
            bounds.append(i + 1)
    bounds.append(n)
    return tuple(bounds)


def seg_grids(level_idx: Sequence[int], layout: LeafLayout,
              levels: Sequence[Level], n_pods: int,
              growth: Optional[float], ring: Optional[int], bidir: bool,
              n_edge: int = 1, hier: Optional[int] = None,
              segments: int = 0):
    """The static per-(segment, rung) executed grids of a backward-
    segmented plan: ``(bounds, seg_nb, seg_sig, seg_chunks, seg_hier)``.

    ``bounds`` of length 2 means the plan stays flat (single segment).
    Each segment's grid is :func:`exec_grid` over its own leaf range, so
    every piece is class-padded / chunk-gridded exactly like a flat rung
    and small replan jitter lands in class.  NOTE the per-segment grids
    depend on which rung each leaf is assigned to — the segmented
    signature (``seg_sig``), not the flat ``sig``, is the compiled-step
    identity of a segmented plan, and a replan that moves leaves across a
    segment boundary is a NEW signature (handled by the background
    warm-compile path, never a foreground retrace).  Shared by
    :func:`build_exec_plan` and ``Scheduler._finalize`` so the plan the
    scheduler prices and the plan the trainer lowers agree."""
    if segments == 0:
        segments = auto_segments(layout)
    bounds = segment_leaf_bounds(layout, segments)
    if len(bounds) <= 2:
        return bounds, (), (), (), ()
    nbs, starts = layout.nbs, layout.starts
    seg_nb, seg_sig, seg_chunks, seg_hier = [], [], [], []
    for s in range(len(bounds) - 1):
        lo, hi = bounds[s], bounds[s + 1]
        base = starts[lo]
        end = starts[hi - 1] + nbs[hi - 1] if hi > lo else base
        seg_nb.append(end - base)
        ssig, sch, shg = exec_grid(
            tuple(level_idx[lo:hi]), layout.sizes[lo:hi], levels,
            n_pods, layout.block, growth, ring, bidir, n_edge=n_edge,
            hier=hier)
        seg_sig.append(ssig)
        seg_chunks.append(sch)
        seg_hier.append(shg)
    return (bounds, tuple(seg_nb), tuple(seg_sig), tuple(seg_chunks),
            tuple(seg_hier))


@dataclass(frozen=True)
class ExecPlan:
    """A SyncPlan lowered to device data + a static bucket signature.

    Registered as a pytree: ``perms``, ``omega`` and ``inv`` are children
    (traced, swapped per replan), everything else is aux data (hashed into
    the jit cache key).  ``total_blocks`` is the NB of the *local* leaf
    layout the perms index into (one zero pad block lives at index NB).
    ``inv`` holds one ``int32[NB]`` per leaf range (one when unsegmented,
    one per segment otherwise): ``inv[i]`` is the bucket-space row of
    layout row ``i`` — bucket space being the range's rung buckets laid
    end to end in rung order, each its padded ``S_r`` rows.  Its shape
    depends only on the layout, never on the plan.
    ``chunks`` is the static per-rung chunk grid of the ring exchange
    (0 = one-shot; see :func:`ring_chunk_count`); ``bidir`` selects the
    bidirectional half-ring circulation for ringing rungs (static: it
    changes the lowered ppermute pattern).

    Backward-segmented plans (``build_exec_plan(segments > 1)``)
    additionally carry the static segment schedule: ``seg_leaves`` are
    the leaf-index bounds (:func:`segment_leaf_bounds`), ``seg_nb`` the
    per-segment block counts of the local layout, and ``seg_sig`` /
    ``seg_chunks`` / ``seg_hier`` the per-(segment, rung) executed grids
    — each piece class-padded exactly like a flat rung, so replan jitter
    still lands in class.  ``perms`` then nests per segment (leaf order;
    the streaming path walks them in reverse), each segment's perm
    indices LOCAL to its own (seg_nb + 1, block) buffer — the point of
    the whole scheme: a segment's gather depends only on that segment's
    leaves, so its encode+collective carries no data dependence on the
    rest of the backward pass."""
    levels: Tuple[Level, ...]
    sig: Tuple[int, ...]              # padded block count per rung
    block: int
    total_blocks: int
    perms: tuple                      # int32[S_r] per rung with sig[r] > 0
    omega: jax.Array                  # f32[n_fleet] aggregation weights
    inv: tuple = ()                   # int32[NB] per leaf range
    chunks: Tuple[int, ...] = ()      # ring chunk count per rung
    bidir: bool = True                # both DCN directions at once
    hier: Tuple[int, ...] = ()        # per-rung tier grid (0/1/2)
    seg_leaves: Tuple[int, ...] = ()  # leaf-index bounds (segmented only)
    seg_nb: Tuple[int, ...] = ()      # blocks per segment (local layout)
    seg_sig: Tuple[Tuple[int, ...], ...] = ()
    seg_chunks: Tuple[Tuple[int, ...], ...] = ()
    seg_hier: Tuple[Tuple[int, ...], ...] = ()

    @property
    def segmented(self) -> bool:
        return len(self.seg_sig) > 1

    def static_key(self) -> tuple:
        return (self.levels, self.sig, self.chunks, self.bidir,
                self.hier, self.block, self.total_blocks,
                self.seg_leaves, self.seg_nb, self.seg_sig,
                self.seg_chunks, self.seg_hier)

    def with_omega(self, omega) -> "ExecPlan":
        return replace(self, omega=jnp.asarray(omega, jnp.float32))


jax.tree_util.register_pytree_node(
    ExecPlan,
    lambda ep: ((ep.perms, ep.omega, ep.inv),
                (ep.levels, ep.sig, ep.block, ep.total_blocks, ep.chunks,
                 ep.bidir, ep.hier, ep.seg_leaves, ep.seg_nb, ep.seg_sig,
                 ep.seg_chunks, ep.seg_hier)),
    lambda aux, ch: ExecPlan(levels=aux[0], sig=aux[1], block=aux[2],
                             total_blocks=aux[3], chunks=aux[4],
                             bidir=aux[5], hier=aux[6], seg_leaves=aux[7],
                             seg_nb=aux[8], seg_sig=aux[9],
                             seg_chunks=aux[10], seg_hier=aux[11],
                             perms=tuple(ch[0]), omega=ch[1],
                             inv=tuple(ch[2])),
)


def _rung_perms(level_idx, nbs, starts, sig, base: int, nb: int,
                lo: int, hi: int, L: int):
    """Gather perms and their inverse for leaves [lo, hi) spanning ``nb``
    layout rows from block ``base``: one int32[sig[r]] per rung with a
    non-empty bucket, indices relative to ``base``, pad entries pointing
    at the zero row ``nb``; and ``inv: int32[nb]``, each layout row's row
    of bucket space (the rung buckets end to end).

    Raises unless every layout row lands in exactly one rung bucket: the
    return path gathers each leaf through ``inv`` and has no other source
    for a row no rung wrote, so bucket space needs no zero row."""
    member = [[] for _ in range(L)]
    for i in range(lo, hi):
        if nbs[i]:
            member[level_idx[i]].append(
                np.arange(starts[i] - base, starts[i] - base + nbs[i],
                          dtype=np.int32))
    perms, rows = [], []
    inv = np.zeros((nb,), np.int32)
    off = 0
    for r in range(L):
        S = sig[r]
        if not S:
            continue
        idx = (np.concatenate(member[r]) if member[r]
               else np.zeros((0,), np.int32))
        rows.append(idx)
        inv[idx] = off + np.arange(idx.shape[0])
        # pad entries gather the zero block at index ``nb``
        p = np.full((S,), nb, np.int32)
        p[: idx.shape[0]] = idx
        perms.append(jnp.asarray(p))
        off += S
    rows = np.concatenate(rows) if rows else np.zeros((0,), np.int32)
    if not np.array_equal(np.sort(rows), np.arange(nb)):
        raise ValueError("every layout row must lie in exactly one rung "
                         "bucket")
    return tuple(perms), jnp.asarray(inv)


def build_exec_plan(plan, sizes: Optional[Sequence[int]] = None, *,
                    block: int = BLOCK, growth: Optional[float] = None,
                    omega=None, n_pods: int = 1,
                    ring: Optional[int] = None, bidir: bool = True,
                    n_edge: int = 1, hier: Optional[int] = None,
                    layout: Optional[LeafLayout] = None,
                    segments: int = 1) -> ExecPlan:
    """Lower a :class:`SyncPlan` to an :class:`ExecPlan`.

    ``sizes`` are the per-group element counts of the layout the exchange
    actually runs on — the LOCAL shard sizes when the sync executes inside
    a data/model-manual region (see ``core.sync.local_group_sizes``) —
    or pass a prebuilt ``layout`` (:func:`leaf_layout`) to skip the
    per-replan recomputation.  ``growth``: padded-class ladder for
    adaptive plans (``None`` = exact sizes, right for plans that never
    change).  ``n_pods``/``ring`` feed the chunk-grid heuristic (a 1-pod
    build never rings).  The perms and their inverse are numpy-built
    (O(total_blocks), trivial next to a train step) and uploaded once per
    distinct assignment.

    ``segments > 1`` builds the backward-interleaved plan: leaves split
    into contiguous ranges (:func:`segment_leaf_bounds`), each range
    packing its OWN block buffer with segment-local perms, so a
    segment's encode+exchange depends only on that range's gradients and
    issues while the rest of the backward still runs (``core/sync.py``
    streaming path).  Every per-(segment, rung) piece is class-padded and
    chunk/tier-gridded exactly like a flat rung (:func:`exec_grid` per
    segment), so the schedule stays a function of the bucket signature
    only — retrace-free across replans.  Blockwise codec math makes the
    piece split numerics-neutral: segmented == barriered bit-identical.
    """
    if layout is None:
        if sizes is None:
            raise ValueError("need sizes or a prebuilt layout")
        layout = leaf_layout(sizes, block)
    else:
        block = layout.block
    level_idx = tuple(int(i) for i in plan.level_idx)
    if len(level_idx) != len(layout.sizes):
        raise ValueError(f"plan has {len(level_idx)} groups, layout has "
                         f"{len(layout.sizes)}")
    L = len(plan.levels)
    nbs, starts = layout.nbs, layout.starts
    NB = layout.total_blocks
    sig, chunks, hgrid = exec_grid(level_idx, layout.sizes, plan.levels,
                                   n_pods, block, growth, ring, bidir,
                                   n_edge=n_edge, hier=hier)
    om = plan.omega if omega is None else omega
    kw = dict(levels=tuple(plan.levels), sig=sig, block=block,
              total_blocks=NB, chunks=chunks, bidir=bidir, hier=hgrid,
              omega=jnp.asarray(om, jnp.float32))
    bounds, seg_nb, seg_sig, seg_chunks, seg_hier = seg_grids(
        level_idx, layout, plan.levels, n_pods, growth, ring, bidir,
        n_edge=n_edge, hier=hier, segments=segments)
    if len(bounds) > 2:
        seg_perms, seg_inv = zip(*(
            _rung_perms(level_idx, nbs, starts, seg_sig[s],
                        starts[bounds[s]], seg_nb[s], bounds[s],
                        bounds[s + 1], L)
            for s in range(len(bounds) - 1)))
        return ExecPlan(perms=seg_perms, inv=seg_inv, seg_leaves=bounds,
                        seg_nb=seg_nb, seg_sig=seg_sig,
                        seg_chunks=seg_chunks, seg_hier=seg_hier, **kw)
    perms, inv = _rung_perms(level_idx, nbs, starts, sig, 0, NB, 0,
                             len(level_idx), L)
    return ExecPlan(perms=perms, inv=(inv,), **kw)


def exec_wire_bytes(ep: ExecPlan, n_pods: int,
                    n_cross: Optional[int] = None) -> int:
    """Analytic per-device slow-tier wire bytes of the exchange ``ep``
    actually executes — per-(segment, rung) pieces for segmented plans,
    the flat rung grid otherwise.  This is what the traced collectives
    move, piece class padding included (the segmented counterpart of
    :func:`sig_wire_bytes` over ``SyncPlan.bucket_sig``)."""
    if ep.segmented:
        return sum(sig_wire_bytes(s, ep.levels, n_pods, ep.block, hier=h,
                                  n_cross=n_cross)
                   for s, h in zip(ep.seg_sig, ep.seg_hier))
    return sig_wire_bytes(ep.sig, ep.levels, n_pods, ep.block,
                          hier=ep.hier, n_cross=n_cross)


def exec_intra_bytes(ep: ExecPlan, n_edge: int) -> int:
    """Fast-tier counterpart of :func:`exec_wire_bytes` (zero for flat
    fleets)."""
    if ep.segmented:
        return sum(sig_intra_bytes(s, ep.levels, n_edge, ep.block, hier=h)
                   for s, h in zip(ep.seg_sig, ep.seg_hier))
    return sig_intra_bytes(ep.sig, ep.levels, n_edge, ep.block,
                           hier=ep.hier)
