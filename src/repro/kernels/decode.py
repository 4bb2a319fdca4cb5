"""Pallas TPU kernels: fused decode-accumulate for the ring exchange.

The chunked ring pipeline (``Codec.ef_sync_ring``) folds ONE peer's
payload chunk into the running aggregate per hop:

    acc += weight * decode(payload_chunk)

Done naively that is two HBM passes (materialise the dense decode, then
FMA).  These kernels fuse dequantisation + weighted accumulate into one
VMEM pass per (8, 1024) tile — the decode compute the ring hides behind
the DCN transfer of the next chunk:

  * int8:  acc += w * (q * scale)          (dequant-add)
  * int4:  unpack two nibbles per byte, then dequant-add
  * sign:  majority-vote partial counts: vote += w * (+-1 signs unpacked
           from the bit-packed wire), mag += w * scale
  * topk:  scatter-add the k (value, index) pairs per block into the
           dense accumulator (one lane compare per kept entry)

``weight`` is a TRACED scalar (the omega entry of the sending pod — plan
data, swapped per replan), so it rides as a (1, 1) operand instead of a
baked constant.  The arithmetic association matches the jnp oracle path
(``acc + w * (q * scale)``) bit for bit on identical inputs.

Deterministic (fixed-point) variants
------------------------------------
For P >= 3 pods the ring folds peers in per-pod arrival order, so the
float accumulate above would let per-pod aggregates differ at ulp level
(fp addition is not associative).  The ``*_fp`` kernels instead quantise
each weighted term to int32 fixed point and accumulate in INTEGER
arithmetic — exact, commutative and associative, so every pod reaches
bit-identical sums in any fold order:

    acc_i32 += round(w * decode(chunk) * 2^bits)       (int32 add)

``fixed_point`` / ``FIXED_POINT_BITS`` define the shared quantiser (used
by the kernels, the oracle refs AND the codecs' one-shot fold, so ring
and all_gather paths stay bit-identical).  With the default 16
fractional bits the representable aggregate range is ±2^15 at 2^-16
absolute resolution; per-term saturation (and, past it, int32 wraparound)
is itself deterministic — accuracy degrades, determinism never does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.quantize import unpack_nibbles
from repro.kernels.topk_compress import LANES, ROWS

#: fractional bits of the deterministic fixed-point accumulator
#: (``ACESyncConfig.accum_bits`` overrides per run).
FIXED_POINT_BITS = 16

#: largest f32 magnitude that casts to int32 without overflow (2^31 - 128,
#: the nearest representable float below 2^31).
_INT32_SAT = 2147483520.0


def fixed_point(x, bits: int = FIXED_POINT_BITS):
    """f32 -> int32 fixed point: round-to-nearest-even at ``bits``
    fractional bits, saturating at the int32 range.  Pure jnp, so it runs
    inside kernel bodies, the oracle refs and the codec fold alike —
    every path quantises a term to exactly the same integer."""
    s = jnp.round(x * jnp.float32(2.0 ** bits))
    return jnp.clip(s, -_INT32_SAT, _INT32_SAT).astype(jnp.int32)


def from_fixed_point(acc, bits: int = FIXED_POINT_BITS):
    """int32 fixed point -> f32 (exact: int32 -> f64-free scale by a
    power of two)."""
    return acc.astype(jnp.float32) * jnp.float32(2.0 ** -bits)


_spec = pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))
_sspec = pl.BlockSpec((ROWS, 1), lambda i: (i, 0))
_wspec = pl.BlockSpec((1, 1), lambda i: (0, 0))


def unpack_signs(packed):
    """(rows, C // 8) uint8 bit-packed -> (rows, C) f32 {-1, +1} signs.
    Same bit layout as ``repro.codecs.base.unpack_bits`` (bit i of byte b
    = column 8b+i); plain jnp, so it runs inside the kernel body and in
    the oracle ref alike.  The shifts run in int32: Mosaic has no
    uint8 -> f32 cast."""
    p = packed.astype(jnp.int32)
    bits = ((p[:, :, None] >> jnp.arange(8, dtype=jnp.int32)) &
            1).astype(jnp.float32)
    return bits.reshape(packed.shape[0], packed.shape[1] * 8) * 2.0 - 1.0


def _int8_kernel(acc_ref, q_ref, s_ref, w_ref, out_ref):
    w = w_ref[0, 0]
    q = q_ref[...].astype(jnp.float32)
    out_ref[...] = acc_ref[...] + w * (q * s_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequant_accum_int8_fused(acc, q, s, w, *, interpret: bool = False):
    """acc (rows, LANES) f32, q int8, s (rows, 1) f32, w (1, 1) f32
    -> acc + w * (q * s) in one pass."""
    n_rows, lanes = acc.shape
    assert lanes == LANES and n_rows % ROWS == 0, (acc.shape,)
    return pl.pallas_call(
        _int8_kernel,
        name="dequant_accum_int8_fused",
        grid=(n_rows // ROWS,),
        in_specs=[_spec, _spec, _sspec, _wspec],
        out_specs=_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, LANES), jnp.float32),
        interpret=interpret,
    )(acc, q, s, w)


def _int4_kernel(acc_ref, p_ref, s_ref, w_ref, out_ref):
    w = w_ref[0, 0]
    q = unpack_nibbles(p_ref[...])
    out_ref[...] = acc_ref[...] + w * (q * s_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequant_accum_int4_fused(acc, p, s, w, *, interpret: bool = False):
    """acc (rows, LANES) f32, p (rows, LANES // 2) uint8 packed nibbles,
    s (rows, 1) f32, w (1, 1) f32 -> acc + w * dequant(p, s)."""
    n_rows, lanes = acc.shape
    assert lanes == LANES and n_rows % ROWS == 0, (acc.shape,)
    pspec = pl.BlockSpec((ROWS, LANES // 2), lambda i: (i, 0))
    return pl.pallas_call(
        _int4_kernel,
        name="dequant_accum_int4_fused",
        grid=(n_rows // ROWS,),
        in_specs=[_spec, pspec, _sspec, _wspec],
        out_specs=_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, LANES), jnp.float32),
        interpret=interpret,
    )(acc, p, s, w)


def _sign_kernel(vote_ref, mag_ref, p_ref, s_ref, w_ref, vout_ref,
                 mout_ref):
    w = w_ref[0, 0]
    vout_ref[...] = vote_ref[...] + w * unpack_signs(p_ref[...])
    mout_ref[...] = mag_ref[...] + w * s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def sign_vote_accum_fused(vote, mag, p, s, w, *, interpret: bool = False):
    """Majority-vote partials: vote (rows, LANES) f32 += w * signs
    (unpacked from p (rows, LANES // 8) uint8), mag (rows, 1) f32
    += w * s."""
    n_rows, lanes = vote.shape
    assert lanes == LANES and n_rows % ROWS == 0, (vote.shape,)
    pspec = pl.BlockSpec((ROWS, LANES // 8), lambda i: (i, 0))
    return pl.pallas_call(
        _sign_kernel,
        name="sign_vote_accum_fused",
        grid=(n_rows // ROWS,),
        in_specs=[_spec, _sspec, pspec, _sspec, _wspec],
        out_specs=[_spec, _sspec],
        out_shape=[jax.ShapeDtypeStruct((n_rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((n_rows, 1), jnp.float32)],
        interpret=interpret,
    )(vote, mag, p, s, w)


def _topk_kernel(acc_ref, q_ref, i_ref, s_ref, w_ref, out_ref, *, k: int):
    w = w_ref[0, 0]
    wv = w * (q_ref[...].astype(jnp.float32) * s_ref[...])   # (ROWS, k)
    # indices < LANES are exact in f32, whose lane reductions Mosaic has
    idx = i_ref[...].astype(jnp.int32).astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, (ROWS, k), 1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 1
                                     ).astype(jnp.float32)

    def body(j, acc):
        # entry j of every row, picked by a masked lane sum (Mosaic has
        # no per-j dynamic lane slice); the sum adds exact zeros only
        pick = col == j
        lane = jnp.sum(jnp.where(pick, idx, 0.0), axis=1, keepdims=True)
        val = jnp.sum(jnp.where(pick, wv, 0.0), axis=1, keepdims=True)
        # only the hit lane changes, as in the oracle's scatter-add
        return jnp.where(lanes == lane, acc + val, acc)

    out_ref[...] = jax.lax.fori_loop(0, k, body, acc_ref[...])


def _int8_fp_kernel(acc_ref, q_ref, s_ref, w_ref, out_ref, *, bits: int):
    w = w_ref[0, 0]
    q = q_ref[...].astype(jnp.float32)
    out_ref[...] = acc_ref[...] + fixed_point(w * (q * s_ref[...]), bits)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def dequant_accum_int8_fp_fused(acc, q, s, w, *, bits: int,
                                interpret: bool = False):
    """Deterministic int8 decode-accumulate: acc (rows, LANES) int32
    += fixed_point(w * (q * s)) — exact integer partial sums, fold-order
    insensitive."""
    n_rows, lanes = acc.shape
    assert lanes == LANES and n_rows % ROWS == 0, (acc.shape,)
    return pl.pallas_call(
        functools.partial(_int8_fp_kernel, bits=bits),
        name="dequant_accum_int8_fp_fused",
        grid=(n_rows // ROWS,),
        in_specs=[_spec, _spec, _sspec, _wspec],
        out_specs=_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, LANES), jnp.int32),
        interpret=interpret,
    )(acc, q, s, w)


def _int4_fp_kernel(acc_ref, p_ref, s_ref, w_ref, out_ref, *, bits: int):
    w = w_ref[0, 0]
    q = unpack_nibbles(p_ref[...])
    out_ref[...] = acc_ref[...] + fixed_point(w * (q * s_ref[...]), bits)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def dequant_accum_int4_fp_fused(acc, p, s, w, *, bits: int,
                                interpret: bool = False):
    """Deterministic int4 decode-accumulate on the int32 fixed-point
    accumulator (packed-nibble unpack fused in)."""
    n_rows, lanes = acc.shape
    assert lanes == LANES and n_rows % ROWS == 0, (acc.shape,)
    pspec = pl.BlockSpec((ROWS, LANES // 2), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_int4_fp_kernel, bits=bits),
        name="dequant_accum_int4_fp_fused",
        grid=(n_rows // ROWS,),
        in_specs=[_spec, pspec, _sspec, _wspec],
        out_specs=_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, LANES), jnp.int32),
        interpret=interpret,
    )(acc, p, s, w)


def _sign_fp_kernel(vote_ref, mag_ref, p_ref, s_ref, w_ref, vout_ref,
                    mout_ref, *, bits: int):
    w = w_ref[0, 0]
    wq = fixed_point(w, bits)               # omega quantised once per hop
    signs = unpack_signs(p_ref[...]).astype(jnp.int32)    # exact ±1
    vout_ref[...] = vote_ref[...] + wq * signs
    mout_ref[...] = mag_ref[...] + fixed_point(w * s_ref[...], bits)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def sign_vote_accum_fp_fused(vote, mag, p, s, w, *, bits: int,
                             interpret: bool = False):
    """Deterministic majority-vote partials: integer vote counts
    (vote int32 += fixed_point(w) * ±1) and fixed-point magnitude
    (mag int32 += fixed_point(w * s)) — both exact and commutative."""
    n_rows, lanes = vote.shape
    assert lanes == LANES and n_rows % ROWS == 0, (vote.shape,)
    pspec = pl.BlockSpec((ROWS, LANES // 8), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_sign_fp_kernel, bits=bits),
        name="sign_vote_accum_fp_fused",
        grid=(n_rows // ROWS,),
        in_specs=[_spec, _sspec, pspec, _sspec, _wspec],
        out_specs=[_spec, _sspec],
        out_shape=[jax.ShapeDtypeStruct((n_rows, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((n_rows, 1), jnp.int32)],
        interpret=interpret,
    )(vote, mag, p, s, w)


@functools.partial(jax.jit, static_argnames=("interpret",))
def topk_scatter_accum_fused(acc, q, idx, s, w, *, interpret: bool = False):
    """acc (rows, LANES) f32 += w * scatter(q * s at idx): the top-k
    rung's decode-accumulate.  Indices are distinct per block (top_k), so
    the one-hot accumulation never double-counts a lane."""
    n_rows, lanes = acc.shape
    k = q.shape[1]
    assert lanes == LANES and n_rows % ROWS == 0, (acc.shape,)
    kspec = pl.BlockSpec((ROWS, k), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        name="topk_scatter_accum_fused",
        grid=(n_rows // ROWS,),
        in_specs=[_spec, kspec, kspec, _sspec, _wspec],
        out_specs=_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, LANES), jnp.float32),
        interpret=interpret,
    )(acc, q, idx, s, w)
