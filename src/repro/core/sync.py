"""Hierarchical cloud-edge synchronisation (paper eqs. 7-8) on the pod axis.

Execution context: on a pod mesh these functions run INSIDE the trainer's
per-pod shard_map, which is manual over every mesh axis, so each device
compresses its pod's whole tree and exchanges payloads only with its
pod-peers over the (slow, DCN) "pod" axis.  On a single-pod mesh
compression runs in a shard_map manual over "data"/"model", so every
device compresses exactly its local shard — no resharding:

    g_ef   = g + gamma * e                          (eq 7, error feedback)
    payload= codec.ef_encode(g_ef_local)             (codec from the plan)
    agg    = codec.pod_exchange(payloads, omega)     (eq 8, one collective)
    e'     = g_ef - decompress(own payload)

Since the plan-as-data refactor the exchange is **retrace-free**: every
leaf is laid out block-aligned in ONE static flat (NB, block) buffer, and
per ladder rung a gather permutation (``repro.core.planexec.ExecPlan`` —
ordinary device data) repacks the member leaves into one contiguous
per-rung buffer.  Each rung runs its codec's fused EF + compress +
exchange round on that buffer — ONE pod collective (all_gather/psum) for
small buckets, or the plan's K-chunk ``ppermute`` ring for DCN-bound ones
(``Codec.ef_sync_ring``: the transfer of chunk *i* hides the
decode-accumulate of chunk *i-1*; exactly the same bytes on the wire) —
and the aggregate/residual are scattered back through the same
permutation.  Only the tuple of padded per-rung block counts — the
bucket-shape signature — plus the per-rung chunk grid is static, so a
replan that keeps the signature swaps permutations without recompiling
(tests/test_replan.py pins this; tests/test_collectives.py keeps pinning
the collectives-per-rung and analytic==traced byte contracts, now with
the per-leaf block padding priced explicitly).

The trainer-level counterpart is rung-ordered apply (``apply_fn``): the
optimizer consumes each rung's aggregate the moment it lands, so the
apply of rung r overlaps the exchange of rung r+1 instead of barriering
on the whole tree.

Wire formats are pluggable :class:`repro.codecs.base.Codec` objects (FULL
bf16-psum, dense INT8 / packed INT4, block top-k, 1-bit sign with majority
vote, SKIP); plans refer to them through the thin ``Level`` view.

Without a mesh (unit tests) the same math runs on the single local array
with n_pods = 1.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.codecs import EDGE_AXIS, POD_AXIS, plan_wire_bytes
from repro.core import compression as C
from repro.core.planexec import ExecPlan, build_exec_plan, n_blocks
from repro.kernels.decode import FIXED_POINT_BITS
from repro.core.scheduler import SyncPlan
from repro.kernels import ops
from repro.models.shardctx import norm_spec


# ---------------------------------------------------------------------------
# Parameter groups
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class GroupMeta:
    name: str
    size: int
    depth: float          # relative depth in the network, [0, 1]
    kind: str             # embed | attn | mlp | other


_KIND_PATTERNS = (
    ("embed", "embed"),
    ("attn", "attn"), ("wq", "attn"), ("wk", "attn"), ("wv", "attn"),
    ("wo", "attn"), ("mix", "attn"),
    ("ffn", "mlp"), ("w_gate", "mlp"), ("w_up", "mlp"), ("w_down", "mlp"),
    ("router", "mlp"),
)


def _kind_of(path: str) -> str:
    for pat, kind in _KIND_PATTERNS:
        if pat in path:
            return kind
    return "other"


def group_metas(param_specs) -> List[GroupMeta]:
    """Flatten the param pytree into ordered per-leaf groups."""
    leaves = jax.tree_util.tree_flatten_with_path(param_specs)[0]
    out = []
    total = max(len(leaves) - 1, 1)
    for i, (path, leaf) in enumerate(leaves):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        size = 1
        for d in leaf.shape:
            size *= d
        out.append(GroupMeta(name=name, size=int(size), depth=i / total,
                             kind=_kind_of(name)))
    return out


def group_sizes(param_specs) -> List[int]:
    return [g.size for g in group_metas(param_specs)]


# ---------------------------------------------------------------------------
# local layout: where each leaf lands in the static flat block buffer
# ---------------------------------------------------------------------------


def _pod_info(mesh) -> int:
    """FLEET size: every device one flat exchange spans — the pod axis
    times the (optional) fast intra-cluster edge axis."""
    if mesh is None or POD_AXIS not in mesh.axis_names:
        return 1
    n = mesh.shape[POD_AXIS]
    if EDGE_AXIS in mesh.axis_names:
        n *= mesh.shape[EDGE_AXIS]
    return n


def fleet_axes(mesh) -> Tuple[str, ...]:
    """The mesh axes one flat fleet collective spans: ``("pod",)`` on a
    flat mesh, ``("pod", "edge")`` on a hierarchical one, ``()`` without
    a pod axis.  ``pmean``/``psum`` over the tuple reduce across the
    whole fleet; the tuple-axis ``all_gather`` order is pod-major,
    matching the ``pod * n_edge + edge`` fleet slot indexing."""
    if mesh is None or POD_AXIS not in mesh.axis_names:
        return ()
    if EDGE_AXIS in mesh.axis_names:
        return (POD_AXIS, EDGE_AXIS)
    return (POD_AXIS,)


def _tier_info(mesh) -> Tuple[int, int]:
    """(n_cross, n_edge) of the two-tier topology: cluster count on the
    slow pod axis x members per cluster on the fast edge axis.  A flat
    mesh is (n_pods, 1)."""
    if mesh is None or POD_AXIS not in mesh.axis_names:
        return 1, 1
    n_edge = mesh.shape[EDGE_AXIS] if EDGE_AXIS in mesh.axis_names else 1
    return mesh.shape[POD_AXIS], n_edge


def _uses_nested(mesh, inside_manual: bool) -> bool:
    """Whether sync_tree will wrap the exchange in a data/model shard_map
    (leaves become local shards there).  Inside the trainer's fully-manual
    per-pod region every axis is already manual and leaves are whole."""
    return mesh is not None and not inside_manual


def _local_shape(shape, spec, mesh) -> Tuple[int, ...]:
    """Per-device shard shape of a leaf under the nested data/model-manual
    region (the pod axis is manual outside and does not divide here)."""
    spec = norm_spec(spec if spec is not None else P(), mesh)
    out = list(shape)
    for d, ax in enumerate(spec):
        if ax is None or d >= len(out):
            continue
        for a in ((ax,) if isinstance(ax, str) else tuple(ax)):
            if a not in (POD_AXIS, EDGE_AXIS):
                out[d] //= mesh.shape[a]
    return tuple(out)


def local_group_sizes(param_specs, shardings, mesh,
                      inside_manual: Optional[bool] = None) -> List[int]:
    """Per-group element counts of the layout the exchange actually runs
    on: the local shard sizes when a nested data/model shard_map applies,
    the global sizes otherwise.  This is what ``planexec.build_exec_plan``
    must be fed so host-built gather perms match the traced layout."""
    leaves, treedef = jax.tree_util.tree_flatten(param_specs)
    s_leaves = treedef.flatten_up_to(shardings) if shardings is not None \
        else [None] * len(leaves)
    if inside_manual is None:
        inside_manual = mesh is not None and POD_AXIS in mesh.axis_names
    if not _uses_nested(mesh, inside_manual):
        return [int(math.prod(l.shape)) for l in leaves]
    return [int(math.prod(_local_shape(l.shape, s, mesh)))
            for l, s in zip(leaves, s_leaves)]


# ---------------------------------------------------------------------------
# static-shape repack + per-rung exchange (the retrace-free hot path)
# ---------------------------------------------------------------------------


def _leaf_blocks(leaves, block: int) -> jax.Array:
    """Concatenate leaves into the static (NB, block) layout: each leaf
    flattened, zero-padded to a block multiple, block-aligned.  The layout
    depends only on (leaf shapes, block) — never on the plan."""
    parts = [C.pad_to_blocks(l.reshape(-1).astype(jnp.float32), block)
             for l in leaves]
    if not parts:
        return jnp.zeros((0, block), jnp.float32)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _rung_exchange(codec, fb, eb, perm, omega, omega_own, *, chunks,
                   bidir, gamma, n_pods, block, use_pallas, fixed_bits,
                   hier=0, n_cross=1, n_edge=1, omega_intra=None):
    """One rung's gather + EF + compress + exchange round: the two-tier
    path when the plan's tier grid says so (``hier > 0`` — intra-cluster
    aggregation over the fast edge axis feeding one payload per cluster
    over the pod axis, ``Codec.ef_sync_hier``), the chunked ring
    pipeline when the chunk grid says so (``chunks > 0``; see
    ``planexec.ring_chunk_count``), the one-shot path otherwise.

    The rung bucket is ``fb[perm]`` of the packed (NB+1, block)
    grad/error buffers.  The one-shot path hands the buffers + perm to
    ``Codec.ef_sync_gather``, so producer-fused codecs run the gather
    INSIDE the encode kernel — the encode reads each row straight out of
    the buffer the backward wrote, nothing rematerialises the bucket in
    between (the segment-streaming win: the collective's operand cone is
    exactly this range's rows).  The ring and two-tier paths chunk /
    re-encode whole-bucket payloads, so they materialise the gather up
    front as before.  All paths accumulate deterministically
    (fixed-point / integer / canonical-order — the codec's choice)
    whenever >= 3 peers exchange, so per-device aggregates are
    bit-identical on any mesh and ring <-> one-shot <-> two-tier replans
    never move the numerics."""
    if hier and n_edge > 1:
        return codec.ef_sync_hier(
            fb[perm].reshape(-1), eb[perm].reshape(-1), omega_intra,
            omega_own, gamma=gamma, n_cross=n_cross, n_edge=n_edge,
            intra_mode=hier, n_chunks=chunks, block=block,
            cross_axis=POD_AXIS, intra_axis=EDGE_AXIS,
            use_pallas=use_pallas, bidir=bidir, fixed_bits=fixed_bits)
    axis = (POD_AXIS, EDGE_AXIS) if n_edge > 1 else POD_AXIS
    if chunks and n_pods > 1:
        return codec.ef_sync_ring(
            fb[perm].reshape(-1), eb[perm].reshape(-1), omega, omega_own,
            gamma=gamma, n_pods=n_pods, n_chunks=chunks, block=block,
            axis=axis, use_pallas=use_pallas, bidir=bidir,
            fixed_bits=fixed_bits)
    return codec.ef_sync_gather(
        fb, eb, perm, omega, omega_own, gamma=gamma, n_pods=n_pods,
        block=block, axis=axis, use_pallas=use_pallas,
        fixed_bits=fixed_bits)


def _range_sync(gs, es, aux, perms, sig, chunks, hgrid, NB, *, levels,
                block, omega, omega_own, omega_intra, scalars, bidir,
                gamma, n_pods, n_cross, n_edge, use_pallas, fixed_bits,
                apply_fn):
    """One leaf range's pack + per-rung exchange + scatter + unpack.

    The whole tree is one range on the barriered path; the backward-
    streaming path calls this once per segment — crucially the packed
    buffers here are built ONLY from this range's leaves, so the rung
    collectives below carry no data dependence on any other segment's
    gradients and XLA's scheduler issues them while the rest of the
    backward still runs.  Returns ``(aggs | aux_outs, errs)`` as leaf
    tuples for the range.

    Named scopes (inside the caller's ``exchange``): ``pack``, one
    ``encode_<RUNG>`` per rung, ``collective``, ``decode``, ``scatter``
    and ``unpack``."""
    with jax.named_scope("pack"):
        fb = _leaf_blocks(gs, block)
        eb = _leaf_blocks(es, block)
        assert fb.shape[0] == NB, \
            f"leaf layout has {fb.shape[0]} blocks, plan was built for {NB}"
        zrow = jnp.zeros((1, block), jnp.float32)
        fb = jnp.concatenate([fb, zrow])
        eb = jnp.concatenate([eb, zrow])
        abufs = [jnp.concatenate([_leaf_blocks(a, block), zrow])
                 for a in aux]
        agg = None if apply_fn is not None \
            else jnp.zeros((NB + 1, block), jnp.float32)
        err = jnp.zeros((NB + 1, block), jnp.float32)
    # Encode pass: every payload-gather rung (one-shot multi-pod path)
    # stops at its packed uint8 wire buffer; the wires are concatenated
    # into ONE all_gather per range instead of one per rung — same bytes,
    # same per-rung fold (slicing a gathered concatenation is
    # bit-identical to gathering the piece), but the sync round's
    # collective latency stops scaling with the rung count, on the CPU
    # sim and the DCN alike.  Ring / two-tier / single-pod rungs keep
    # their own exchange paths.
    axis = (POD_AXIS, EDGE_AXIS) if n_edge > 1 else POD_AXIS
    staged, wire_parts, woff = [], [], 0
    pi = 0
    for r, S in enumerate(sig):
        if not S:
            continue
        perm = perms[pi]
        pi += 1
        codec = levels[r].codec
        chunks_r = chunks[r] if chunks else 0
        hier_r = hgrid[r] if hgrid else 0
        # a rung's whole round where it has its own exchange path (one
        # pod, ring, two-tier): decode and collective included
        with jax.named_scope("encode_" + levels[r].name):
            if (n_pods > 1 and codec.supports_ring
                    and not (hier_r and n_edge > 1)
                    and not (chunks_r and n_pods > 1)):
                wire, meta, new_e = codec.ef_encode_wire(
                    fb, eb, perm, gamma=gamma, block=block,
                    use_pallas=use_pallas)
                staged.append((S, perm, codec, (meta, woff, wire.shape[0],
                                                new_e)))
                wire_parts.append(wire)
                woff += wire.shape[0]
            else:
                b_out = _rung_exchange(
                    codec, fb, eb, perm, omega,
                    omega_own, chunks=chunks_r,
                    bidir=bidir, gamma=gamma, n_pods=n_pods, block=block,
                    use_pallas=use_pallas, fixed_bits=fixed_bits,
                    hier=hier_r, n_cross=n_cross,
                    n_edge=n_edge, omega_intra=omega_intra)
                staged.append((S, perm, None, b_out))
    gathered = None
    if wire_parts:
        with jax.named_scope("collective"):
            coal = wire_parts[0] if len(wire_parts) == 1 \
                else jnp.concatenate(wire_parts)
            gathered = jax.lax.all_gather(coal, axis)
    # Decode + scatter pass, in rung order (the perms are disjoint).
    for S, perm, codec, payload in staged:
        if codec is None:
            b_agg, b_err = payload
        else:
            meta, o, nbytes, b_err = payload
            with jax.named_scope("decode"):
                b_agg = codec.wire_decode_fold(
                    gathered[:, o:o + nbytes], meta, omega, n=S * block,
                    block=block, use_pallas=use_pallas,
                    deterministic=n_pods >= 3, fixed_bits=fixed_bits)
        # apply_fn names its own scope (the trainer's ``optimizer``)
        with jax.named_scope("scatter"):
            err = err.at[perm].set(b_err.reshape(S, block))
            if apply_fn is None:
                agg = agg.at[perm].set(b_agg.reshape(S, block))
            else:
                rows = apply_fn(b_agg.reshape(S, block),
                                tuple(ab[perm] for ab in abufs), scalars)
                abufs = [ab.at[perm].set(nr)
                         for ab, nr in zip(abufs, rows)]

    def unpack(flat_buf, like):
        outs, boff = [], 0
        for leaf in like:
            n = math.prod(leaf.shape)
            o = boff * block
            outs.append(flat_buf[o:o + n].reshape(leaf.shape)
                        .astype(leaf.dtype))
            boff += n_blocks(n, block)
        return tuple(outs)

    with jax.named_scope("unpack"):
        errs = unpack(err[:NB].reshape(-1), es)
        if apply_fn is None:
            return unpack(agg[:NB].reshape(-1), gs), errs
        outs = tuple(unpack(ab[:NB].reshape(-1), a)
                     for ab, a in zip(abufs, aux))
    return outs, errs


def _repack_sync_local(gs, es, perms, omega, omega_own, omega_intra, aux,
                       scalars, *, ep: ExecPlan, gamma, n_pods, n_cross,
                       n_edge, use_pallas, fixed_bits, apply_fn=None):
    """Fully local per-device sync of the whole tree through the plan's
    gather/scatter repacking.

    ``gs`` / ``es``: tuples of local shard arrays (grads and EF residuals)
    in leaf order.  They are packed into the static block layout, each
    rung's bucket is gathered through its permutation (device data — the
    only thing a replan changes), pushed through the codec's fused EF +
    compress + exchange round (ring-chunked where the plan says so),
    and scattered back.  Pad blocks gather the zero row at index NB and
    scatter into it, so they never touch real data.

    Rung-ordered apply: with ``apply_fn`` set, ``aux`` is a tuple of
    leaf-tuples (e.g. params / m / v) packed into the same block layout,
    and ``apply_fn(agg_rows, aux_rows, scalars)`` (all ``(S, block)``
    f32) consumes each rung's aggregate AS SOON AS that rung's exchange
    lands — the optimizer math for rung r carries no data dependence on
    rung r+1's collective, so XLA overlaps the apply with the next rung's
    DCN transfer instead of barriering on the whole tree.  Returns
    ``(aux_out_tuples, errs)`` instead of ``(aggs, errs)``.

    Backward-interleaved streaming: a segmented plan
    (``ep.segmented`` — see ``planexec.build_exec_plan(segments > 1)``)
    runs one :func:`_range_sync` per leaf segment, walked in REVERSE leaf
    order (backward produces the deep leaves' gradients first).  Each
    segment packs its OWN buffers from only its leaves, so a segment's
    encode+collective is issued by XLA's scheduler as soon as that leaf
    range's gradients materialise in the backward pass — the exchange of
    the deep half hides behind the backward (and the apply) of the
    shallow half.  Blockwise codec math makes the piece split exact:
    segmented == barriered bit-identical (tests/test_multipod.py soaks
    this on the P = 2 and P = 3 meshes)."""
    kw = dict(levels=ep.levels, block=ep.block, omega=omega,
              omega_own=omega_own, omega_intra=omega_intra,
              scalars=scalars, bidir=ep.bidir, gamma=gamma,
              n_pods=n_pods, n_cross=n_cross, n_edge=n_edge,
              use_pallas=use_pallas, fixed_bits=fixed_bits,
              apply_fn=apply_fn)
    if not ep.segmented:
        return _range_sync(gs, es, aux, perms, ep.sig, ep.chunks,
                           ep.hier, ep.total_blocks, **kw)
    S = len(ep.seg_sig)
    outs: list = [None] * S
    errs: list = [None] * S
    for s in reversed(range(S)):
        lo, hi = ep.seg_leaves[s], ep.seg_leaves[s + 1]
        outs[s], errs[s] = _range_sync(
            gs[lo:hi], es[lo:hi], tuple(a[lo:hi] for a in aux),
            perms[s], ep.seg_sig[s], ep.seg_chunks[s], ep.seg_hier[s],
            ep.seg_nb[s], **kw)
    err_leaves = tuple(e for seg in errs for e in seg)
    if apply_fn is None:
        return tuple(g for seg in outs for g in seg), err_leaves
    # per-aux leaf tuples reassembled across segments, leaf order
    n_aux = len(aux)
    aux_outs = tuple(tuple(o for seg in outs for o in seg[a])
                     for a in range(n_aux))
    return aux_outs, err_leaves


# ---------------------------------------------------------------------------
# tree-level API
# ---------------------------------------------------------------------------


def _auto_axes(mesh):
    return tuple(a for a in mesh.axis_names
                 if a not in (POD_AXIS, EDGE_AXIS))


def sync_tree(tree, errors, plan: Union[SyncPlan, ExecPlan], *, mesh,
              shardings, gamma: float, block: int = C.BLOCK,
              inside_manual: bool = None, use_pallas: bool = None,
              ring: Optional[int] = None, bidir: bool = True,
              fixed_bits: int = FIXED_POINT_BITS, apply_fn=None,
              apply_aux=(), apply_scalars=()):
    """Compress + hierarchically aggregate a gradient (or delta) pytree.

    Must be called inside the outer per-pod shard_map when the mesh has a
    pod axis.  ``shardings``: pytree of PartitionSpec matching ``tree`` (the
    data/model sharding of each leaf).  Returns (agg_tree, new_errors).

    ``plan`` may be an :class:`~repro.core.planexec.ExecPlan` — the
    retrace-free form whose gather perms and omega are traced device data
    (the trainer's hot path) — or a host :class:`SyncPlan`, which is
    lowered at trace time with exact (unpadded) bucket sizes, perms baked
    as constants.  Both run the same static-shape exchange: per rung with
    a non-empty bucket either ONE pod collective (the one-shot path) or
    the plan's K-chunk ``ppermute`` ring (big DCN-bound buckets; same
    bytes on the wire — tests/test_collectives.py counts both in the
    lowered HLO).  ``ring`` / ``bidir`` tune the chunk heuristic and the
    ring direction for the SyncPlan lowering path (None = roofline auto,
    0 = force one-shot, K = force K chunks; ExecPlans already carry
    their chunk grid and direction).  ``fixed_bits`` sets the
    deterministic fixed-point accumulation width used whenever >= 3 pods
    exchange (``ACESyncConfig.accum_bits``).

    Rung-ordered apply: with ``apply_fn`` given, ``apply_aux`` is a tuple
    of pytrees shaped like ``tree`` (e.g. params / m / v) and the sync
    consumes each rung's aggregate in place of returning it —
    ``apply_fn(agg_rows, aux_rows, apply_scalars)`` maps the rung bucket's
    ``(S, block)`` f32 rows to updated aux rows, and the return value is
    ``(tuple_of_new_aux_trees, new_errors)``.  This is how the trainer
    overlaps the optimizer with the exchange: rung r's update depends
    only on rung r's collective, not on a whole-tree barrier.

    ``inside_manual``: whether we are already inside a shard_map manual
    over every mesh axis (then the exchange runs on the whole local
    leaves); default: pod axis present.  ``use_pallas``: route the EF +
    compress inner loop through the fused Pallas kernels; default
    :func:`repro.kernels.ops.default_use_pallas` (kernels on accelerators,
    pure-jnp oracles on CPU, ``REPRO_FORCE_INTERPRET=1`` to force the
    kernel path under the interpreter).
    """
    if inside_manual is None:
        inside_manual = mesh is not None and POD_AXIS in mesh.axis_names
    if use_pallas is None:
        use_pallas = ops.default_use_pallas()
    n_pods = _pod_info(mesh)
    n_cross, n_edge = _tier_info(mesh)

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    e_leaves = treedef.flatten_up_to(errors)
    s_leaves = treedef.flatten_up_to(shardings) if shardings is not None \
        else [None] * len(leaves)
    nested = _uses_nested(mesh, inside_manual)

    if isinstance(plan, SyncPlan):
        assert len(leaves) == len(plan.level_idx), \
            (len(leaves), len(plan.level_idx))
        if nested:
            lsz = [math.prod(_local_shape(l.shape, s, mesh))
                   for l, s in zip(leaves, s_leaves)]
        else:
            lsz = [math.prod(l.shape) for l in leaves]
        ep = build_exec_plan(plan, lsz, block=block, growth=None,
                             n_pods=n_pods, ring=ring, bidir=bidir,
                             n_edge=n_edge)
    else:
        ep = plan

    omega = ep.omega
    if n_pods == 1 and omega.shape[0] == 1:
        omega = jnp.ones((1,), jnp.float32)  # single pod: identity weight
    # own device's aggregation weight and its cluster's (E,) omega slice,
    # computed at the per-pod level (axis_index may not re-bind "pod"/
    # "edge" inside the nested fully-manual shard_map).  Fleet indexing is
    # pod-major — slot = pod * n_edge + edge — matching the tuple-axis
    # all_gather order flat rungs fold in.
    if n_edge > 1:
        pod_i = jax.lax.axis_index(POD_AXIS)
        fleet_i = pod_i * n_edge + jax.lax.axis_index(EDGE_AXIS)
        omega_own = omega[fleet_i]
        omega_intra = omega.reshape(n_cross, n_edge)[pod_i]
    elif n_pods > 1:
        omega_own = omega[jax.lax.axis_index(POD_AXIS)]
        omega_intra = omega[:1]          # no fast tier: unused
    else:
        omega_own = omega[0]
        omega_intra = omega[:1]

    fn = functools.partial(_repack_sync_local, ep=ep, gamma=gamma,
                           n_pods=n_pods, n_cross=n_cross, n_edge=n_edge,
                           use_pallas=use_pallas, fixed_bits=fixed_bits,
                           apply_fn=apply_fn)
    gs, es = tuple(leaves), tuple(e_leaves)
    aux = tuple(tuple(treedef.flatten_up_to(a)) for a in apply_aux)
    scalars = tuple(apply_scalars)
    if nested:
        aspecs = []
        for s in s_leaves:
            aspec = norm_spec(s if s is not None else P(), mesh)
            # drop the pod/edge axes from specs (manual outside already)
            aspecs.append(P(*[None if ax in (POD_AXIS, EDGE_AXIS) else ax
                              for ax in aspec]))
        aspecs = tuple(aspecs)
        # mirror the perm structure (flat per-rung, or nested per-segment
        # for backward-streaming plans): every perm rides replicated
        pspecs = jax.tree.map(lambda _: P(None), ep.perms)
        aux_specs = tuple(aspecs for _ in aux)
        scalar_specs = tuple(P() for _ in scalars)
        out_main = (tuple(aspecs for _ in aux) if apply_fn is not None
                    else aspecs)
        inner = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(aspecs, aspecs, pspecs, P(None), P(), P(None),
                      aux_specs, scalar_specs),
            out_specs=(out_main, aspecs),
            axis_names=set(_auto_axes(mesh)), check_vma=False)
        aggs, news = inner(gs, es, ep.perms, omega, omega_own,
                           omega_intra, aux, scalars)
    else:
        # no mesh, or the fully-manual per-pod region (leaves whole and
        # replicated over data/model there): device-local math, pod
        # collectives bound by the enclosing manual region
        aggs, news = fn(gs, es, ep.perms, omega, omega_own, omega_intra,
                        aux, scalars)
    news_tree = jax.tree_util.tree_unflatten(treedef, list(news))
    if apply_fn is not None:
        out_trees = tuple(jax.tree_util.tree_unflatten(treedef, list(a))
                          for a in aggs)
        return out_trees, news_tree
    return jax.tree_util.tree_unflatten(treedef, list(aggs)), news_tree


def grad_group_stats(tree):
    """Per-group scalars feeding the importance estimator: (mean|g|, var,
    norm) each (G,).

    One fused pass per leaf: the three reductions (sum|g|, sum g^2, sum g)
    share a single read of the leaf and XLA fuses them into one HBM
    traversal; the derived statistics come from the stacked (G, 3) table
    in one vectorised epilogue.  This runs every grad step — the old
    per-leaf mean/var/norm chain launched three independent reductions per
    leaf."""
    leaves = jax.tree_util.tree_leaves(tree)
    rows, ns = [], []
    for g in leaves:
        g32 = g.astype(jnp.float32).reshape(-1)
        rows.append(jnp.stack([jnp.sum(jnp.abs(g32)),
                               jnp.sum(g32 * g32),
                               jnp.sum(g32)]))
        ns.append(max(g32.shape[0], 1))
    table = jnp.stack(rows)                       # (G, 3), stacked once
    n = jnp.asarray(ns, jnp.float32)
    mean_abs = table[:, 0] / n
    mean = table[:, 2] / n
    var = jnp.maximum(table[:, 1] / n - mean * mean, 0.0)
    nrm = jnp.sqrt(table[:, 1])
    return mean_abs, var, nrm


def wire_bytes_of_plan(plan: SyncPlan, sizes: Sequence[int],
                       n_pods: int, block: int = C.BLOCK) -> int:
    """Analytic on-the-wire bytes per device per sync for a plan, priced
    exactly the way :func:`sync_tree` transmits it (block-aligned leaves
    repacked into one per-rung buffer and one collective, per-leaf block
    padding included) — the number Table 1 reports and
    tests/test_collectives.py pins to the traced HLO."""
    return plan_wire_bytes(plan, sizes, n_pods, block)
