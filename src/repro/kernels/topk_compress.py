"""Pallas TPU kernel: fused error-feedback + block-local top-k selection.

The gradient-compression hot loop (paper eqs. 6-7) touches every gradient
byte several times when written naively:

    read g, read e  -> ef = g + gamma*e          (1 pass)
    top-k select over ef                          (1-2 passes)
    write masked ef, write residual               (1 pass each)

This kernel fuses all of it into ONE HBM pass per block: each grid step
loads a (rows, 1024) tile into VMEM, computes the error-feedback
accumulator, finds the per-row top-k threshold with a fixed 16-step
bisection on |ef| (VPU-friendly: no sort, no data-dependent control flow),
and writes the selected-dense tile and the residual tile.

Selection contract (shared with ref.py, bit-exact): keep entries with
|ef| >= t where t is the bisection threshold for "approximately k per row";
ties around the threshold may admit slightly more/fewer than k — the wire
format carries a count, so correctness does not depend on exact k (DGC
makes the same trade).

Block geometry: tiles are (ROWS, LANES) = (8, 1024) f32 = 32 KiB in VMEM —
8 sublanes x 128-lane multiples, MXU/VPU aligned.  The producer-fused
gather kernels fetch one (1, LANES) row per grid step through a 3-D view
(see :func:`gather_ef_call`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8          # sublane tile height (rows of independent 1024-blocks)
LANES = 1024      # block width (multiple of 128 lanes)
BISECT_ITERS = 16


# ---------------------------------------------------------------------------
# producer-fused gather plumbing (shared by every gather+encode kernel)
# ---------------------------------------------------------------------------


#: most gather rows one pallas_call takes: its perm rides in scalar
#: memory (1 MiB of SMEM on a v5e), which holds ~256K int32 indices
MAX_GATHER_ROWS = 131072


def gather_ef_call(body, fb, eb, perm, out_defs, *, name: str,
                   interpret: bool = False):
    """Run a per-row encode ``body`` directly on gathered bucket rows.

    ``fb`` / ``eb``: the packed (NB+1, LANES) grad / error-feedback
    buffers (zero row last); ``perm``: (S,) int32 block indices.
    ``body(g, e) -> tuple`` maps (1, LANES) f32 row tiles to the per-row
    encode outputs; ``out_defs`` lists each output's ``(width, dtype)``
    (outputs are (S, width)).  ``name`` names the kernel: its caller's
    name, which the trace shows for the call.

    The gather never materialises in HBM: the perm rides in
    scalar-prefetch memory and the input index map reads block
    ``perm[i]`` per grid step, so Pallas's pipeline does the gather while
    fetching the row.  Every operand is viewed 3-D, (rows, 1, width),
    with (None, 1, width) blocks: a (1, width) block of a 2-D array
    breaks the TPU's (8, 128) tiling rule, while as the last two dims of
    a 3-D view it equals the array's own dims.  The views are free
    reshapes.

    A perm longer than :data:`MAX_GATHER_ROWS` (more than scalar memory
    holds) runs as several calls over consecutive perm chunks; each call
    writes its rows in place into the shared outputs (aliased through),
    so no chunk is copied again.
    """
    S = perm.shape[0]
    nbp1, lanes = fb.shape
    perm = perm.astype(jnp.int32)
    fb3, eb3 = fb.reshape(nbp1, 1, lanes), eb.reshape(nbp1, 1, lanes)
    out_shape = [jax.ShapeDtypeStruct((S, 1, w), dt) for w, dt in out_defs]
    row_spec = pl.BlockSpec((None, 1, lanes), lambda i, p: (p[i], 0, 0))

    def call(p_chunk, start, outs):
        def kernel(p_ref, g_ref, e_ref, *refs):
            for ref, o in zip(refs[len(outs):],
                              body(g_ref[...], e_ref[...])):
                ref[...] = o.astype(ref.dtype)

        out_specs = [pl.BlockSpec((None, 1, w),
                                  lambda i, p: (i + start, 0, 0))
                     for w, _ in out_defs]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(p_chunk.shape[0],),
            in_specs=[row_spec, row_spec]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(outs),
            out_specs=out_specs)
        return pl.pallas_call(
            kernel, name=name, grid_spec=grid_spec, out_shape=out_shape,
            input_output_aliases={3 + j: j for j in range(len(outs))},
            interpret=interpret,
        )(p_chunk, fb3, eb3, *outs)

    if S <= MAX_GATHER_ROWS:
        outs = call(perm, 0, [])
    else:
        outs = [jnp.zeros(o.shape, o.dtype) for o in out_shape]
        for start in range(0, S, MAX_GATHER_ROWS):
            outs = call(perm[start:start + MAX_GATHER_ROWS], start, outs)
    return tuple(o.reshape(S, w) for o, (w, _) in zip(outs, out_defs))


def _select_body(ef, k):
    """Shared selection math (kernel + oracle): per-row bisection threshold.

    ef: (rows, LANES) f32. Returns (mask f32, threshold (rows, 1))."""
    mag = jnp.abs(ef)
    hi = jnp.max(mag, axis=-1, keepdims=True)
    lo = jnp.zeros_like(hi)
    kf = jnp.float32(k)
    for _ in range(BISECT_ITERS):
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((mag >= mid).astype(jnp.float32), axis=-1,
                      keepdims=True)
        # too many selected -> raise threshold; too few -> lower it
        take_hi = cnt > kf
        lo = jnp.where(take_hi, mid, lo)
        hi = jnp.where(take_hi, hi, mid)
    thr = 0.5 * (lo + hi)
    mask = (mag >= thr).astype(ef.dtype)
    return mask, thr


def _kernel(g_ref, e_ref, sel_ref, res_ref, *, gamma: float, k: int):
    g = g_ref[...].astype(jnp.float32)
    e = e_ref[...].astype(jnp.float32)
    ef = g + gamma * e
    mask, _ = _select_body(ef, k)
    sel = ef * mask
    sel_ref[...] = sel.astype(sel_ref.dtype)
    res_ref[...] = (ef - sel).astype(res_ref.dtype)


@functools.partial(jax.jit, static_argnames=("gamma", "k", "interpret"))
def ef_topk_select(g, e, *, gamma: float, k: int, interpret: bool = False):
    """g, e: (n_rows, LANES) f32 — n_rows % ROWS == 0.
    Returns (selected_dense, residual), both (n_rows, LANES) f32."""
    n_rows, lanes = g.shape
    assert lanes == LANES and n_rows % ROWS == 0, (g.shape,)
    grid = (n_rows // ROWS,)
    spec = pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, gamma=gamma, k=k),
        name="ef_topk_select",
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((n_rows, LANES), jnp.float32)] * 2,
        interpret=interpret,
    )(g, e)
    return out[0], out[1]


@functools.partial(jax.jit, static_argnames=("gamma", "k", "interpret"))
def ef_topk_gather(fb, eb, perm, *, gamma: float, k: int,
                   interpret: bool = False):
    """Producer-fused gather + EF + top-k selection: reads the rung's
    rows straight out of the (NB+1, LANES) buffers through ``perm``.
    Returns (selected_dense, residual), both (S, LANES) f32 — bit-exact
    to :func:`ef_topk_select` on the gathered rows."""

    def body(g, e):
        ef = g.astype(jnp.float32) + gamma * e.astype(jnp.float32)
        mask, _ = _select_body(ef, k)
        sel = ef * mask
        return sel, ef - sel

    out_defs = [(LANES, jnp.float32), (LANES, jnp.float32)]
    return gather_ef_call(body, fb, eb, perm, out_defs,
                          name="ef_topk_gather", interpret=interpret)
