"""Public jit'd wrappers for the compression kernels.

On TPU these dispatch to the compiled Pallas kernels; on CPU (unit-test
environments) they run the same kernel bodies under ``interpret=True``.
``use_pallas=False`` falls back to the pure-jnp oracle — the path the CPU
dry-run lowers, keeping kernel code out of the roofline HLO while the
math stays identical.

Backend dispatch is decided ONCE per process (the sync hot loop calls
these per bucket per step).  Two cached predicates:

  * :func:`interpret_mode` — should ``pallas_call`` interpret?  True on
    CPU, False on accelerators.
  * :func:`default_use_pallas` — should the sync path route through the
    kernels at all?  True on accelerators; False on CPU, where the
    interpreted kernels would only slow the oracle math down, unless
    ``REPRO_FORCE_INTERPRET=1`` opts CPU CI into the interpreted kernel
    path.

``REPRO_FORCE_INTERPRET`` is a CPU-only switch: set on any other backend
it raises, so nothing can put the kernels into interpret mode on a chip.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.topk_compress import (ef_topk_gather, ef_topk_select,
                                         LANES, ROWS)
from repro.kernels.decode import (dequant_accum_int4_fp_fused,
                                  dequant_accum_int4_fused,
                                  dequant_accum_int8_fp_fused,
                                  dequant_accum_int8_fused,
                                  sign_vote_accum_fp_fused,
                                  sign_vote_accum_fused,
                                  topk_scatter_accum_fused)
from repro.kernels.quantize import (quantize_int8_fused, dequantize_int8,
                                    ef_int4_fused, ef_int4_gather,
                                    quantize_int8_gather)
from repro.kernels.sign import ef_sign_fused, ef_sign_gather

FORCE_INTERPRET_ENV = "REPRO_FORCE_INTERPRET"


def _force_interpret() -> bool:
    """``REPRO_FORCE_INTERPRET`` as a bool; an error off the CPU."""
    v = os.environ.get(FORCE_INTERPRET_ENV, "").strip().lower()
    forced = v not in ("", "0", "false", "no")
    if forced and jax.default_backend() != "cpu":
        raise RuntimeError(
            f"{FORCE_INTERPRET_ENV}={v} is a CPU-only switch; the "
            f"{jax.default_backend()} backend runs the compiled kernels")
    return forced


@functools.lru_cache(maxsize=None)
def interpret_mode() -> bool:
    """Whether pallas_call should run interpreted (cached per process):
    exactly when the backend is the CPU."""
    _force_interpret()
    return jax.default_backend() == "cpu"


@functools.lru_cache(maxsize=None)
def default_use_pallas() -> bool:
    """Default ``use_pallas`` for the sync hot path (cached per process):
    compiled kernels on accelerators, oracle math on CPU unless
    ``REPRO_FORCE_INTERPRET=1``."""
    return _force_interpret() or jax.default_backend() != "cpu"


def pad_rows(flat: jax.Array):
    """(n,) -> (rows, LANES) padded to a ROWS multiple."""
    n = flat.shape[0]
    per = ROWS * LANES
    nb = (n + per - 1) // per
    pad = nb * per - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(nb * ROWS, LANES), n


def ef_topk(g_flat, e_flat, *, gamma: float, k: int, use_pallas: bool = True):
    """Fused error-feedback + block top-k on flat arrays.
    Returns (selected_dense (n,), residual (n,))."""
    g2, n = pad_rows(g_flat.astype(jnp.float32))
    e2, _ = pad_rows(e_flat.astype(jnp.float32))
    if use_pallas:
        sel, res = ef_topk_select(g2, e2, gamma=gamma, k=k,
                                  interpret=interpret_mode())
    else:
        sel, res = ref.ef_topk_select_ref(g2, e2, gamma=gamma, k=k)
    return sel.reshape(-1)[:n], res.reshape(-1)[:n]


def quantize_int8(x_flat, *, use_pallas: bool = True):
    """Returns (q (rows, LANES) int8, scales (rows,1) f32, residual (n,),
    n)."""
    x2, n = pad_rows(x_flat.astype(jnp.float32))
    if use_pallas:
        q, s, r = quantize_int8_fused(x2, interpret=interpret_mode())
    else:
        q, s, r = ref.quantize_int8_ref(x2)
    return q, s, r.reshape(-1)[:n], n


def dequant_int8(q, scales, n, *, use_pallas: bool = True):
    if use_pallas:
        out = dequantize_int8(q, scales, interpret=interpret_mode())
    else:
        out = ref.dequantize_int8_ref(q, scales)
    return out.reshape(-1)[:n]


def ef_int4(g_flat, e_flat, *, gamma: float, use_pallas: bool = True):
    """Fused error-feedback + packed-int4 quantisation on flat arrays.
    Returns (packed uint8 (rows, LANES//2), scales (rows, 1) f32,
    residual (n,), n)."""
    g2, n = pad_rows(g_flat.astype(jnp.float32))
    e2, _ = pad_rows(e_flat.astype(jnp.float32))
    if use_pallas:
        p, s, r = ef_int4_fused(g2, e2, gamma=gamma,
                                interpret=interpret_mode())
    else:
        p, s, r = ref.ef_int4_ref(g2, e2, gamma=gamma)
    return p, s, r.reshape(-1)[:n], n


def _pad_rows2(a, rows, fill=0):
    """Pad dim 0 of ``a`` up to ``rows`` (kernel tiles want ROWS
    multiples; the pad rows carry zero payload and are sliced off)."""
    if a.shape[0] == rows:
        return a
    pad = [(0, rows - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad, constant_values=fill)


def _w2(w):
    return jnp.asarray(w, jnp.float32).reshape(1, 1)


def decode_accum_int8(acc, q, s, w, *, use_pallas: bool = True,
                      fixed_bits=None):
    """acc (nb, LANES) f32 += w * (q * s) fused — the int8 rung's ring
    decode-accumulate.  ``s``: (nb,) f32 per-block scales.
    ``fixed_bits`` set -> the deterministic variant on the int32
    fixed-point accumulator (see kernels/decode.py)."""
    nb = acc.shape[0]
    rows = ((nb + ROWS - 1) // ROWS) * ROWS
    args = (_pad_rows2(acc, rows), _pad_rows2(q, rows),
            _pad_rows2(s.reshape(-1, 1), rows), _w2(w))
    if fixed_bits is not None:
        if use_pallas:
            out = dequant_accum_int8_fp_fused(*args, bits=int(fixed_bits),
                                              interpret=interpret_mode())
        else:
            out = ref.dequant_accum_int8_fp_ref(*args, int(fixed_bits))
    elif use_pallas:
        out = dequant_accum_int8_fused(*args, interpret=interpret_mode())
    else:
        out = ref.dequant_accum_int8_ref(*args)
    return out[:nb]


def decode_accum_int4(acc, p, s, w, *, use_pallas: bool = True,
                      fixed_bits=None):
    """acc (nb, LANES) f32 += w * dequant(p packed nibbles, s) fused.
    ``fixed_bits`` set -> deterministic int32 fixed-point accumulate."""
    nb = acc.shape[0]
    rows = ((nb + ROWS - 1) // ROWS) * ROWS
    args = (_pad_rows2(acc, rows), _pad_rows2(p, rows),
            _pad_rows2(s.reshape(-1, 1), rows), _w2(w))
    if fixed_bits is not None:
        if use_pallas:
            out = dequant_accum_int4_fp_fused(*args, bits=int(fixed_bits),
                                              interpret=interpret_mode())
        else:
            out = ref.dequant_accum_int4_fp_ref(*args, int(fixed_bits))
    elif use_pallas:
        out = dequant_accum_int4_fused(*args, interpret=interpret_mode())
    else:
        out = ref.dequant_accum_int4_ref(*args)
    return out[:nb]


def sign_vote_accum(vote, mag, p, s, w, *, use_pallas: bool = True,
                    fixed_bits=None):
    """Majority-vote partials: vote (nb, LANES) += w * unpacked signs,
    mag (nb,) += w * s, fused.  ``fixed_bits`` set -> integer vote counts
    + fixed-point magnitude (deterministic, fold-order insensitive)."""
    nb = vote.shape[0]
    rows = ((nb + ROWS - 1) // ROWS) * ROWS
    args = (_pad_rows2(vote, rows), _pad_rows2(mag.reshape(-1, 1), rows),
            _pad_rows2(p, rows), _pad_rows2(s.reshape(-1, 1), rows),
            _w2(w))
    if fixed_bits is not None:
        if use_pallas:
            v, m = sign_vote_accum_fp_fused(*args, bits=int(fixed_bits),
                                            interpret=interpret_mode())
        else:
            v, m = ref.sign_vote_accum_fp_ref(*args, int(fixed_bits))
    elif use_pallas:
        v, m = sign_vote_accum_fused(*args, interpret=interpret_mode())
    else:
        v, m = ref.sign_vote_accum_ref(*args)
    return v[:nb], m[:nb].reshape(-1)


def topk_scatter_accum(acc, q, idx, s, w, *, use_pallas: bool = True):
    """acc (nb, LANES) += w * scatter(q * s at idx) fused — the top-k
    rung's ring decode-accumulate."""
    nb = acc.shape[0]
    rows = ((nb + ROWS - 1) // ROWS) * ROWS
    args = (_pad_rows2(acc, rows), _pad_rows2(q, rows),
            _pad_rows2(idx, rows), _pad_rows2(s.reshape(-1, 1), rows),
            _w2(w))
    if use_pallas:
        out = topk_scatter_accum_fused(*args, interpret=interpret_mode())
    else:
        out = ref.topk_scatter_accum_ref(args[0], args[1], args[2],
                                         args[3], args[4])
    return out[:nb]


def ef_sign(g_flat, e_flat, *, gamma: float, use_pallas: bool = True):
    """Fused error-feedback + 1-bit sign compression on flat arrays.
    Returns (sign int8 (rows, LANES), scales (rows, 1) f32, residual (n,),
    n)."""
    g2, n = pad_rows(g_flat.astype(jnp.float32))
    e2, _ = pad_rows(e_flat.astype(jnp.float32))
    if use_pallas:
        sg, s, r = ef_sign_fused(g2, e2, gamma=gamma,
                                 interpret=interpret_mode())
    else:
        sg, s, r = ref.ef_sign_ref(g2, e2, gamma=gamma)
    return sg, s, r.reshape(-1)[:n], n


# ---------------------------------------------------------------------------
# producer-fused gather + encode (the backward-streaming sync hot path)
# ---------------------------------------------------------------------------
# These read a rung's rows straight out of the packed (NB+1, LANES)
# grad / error buffers through the plan's gather perm — the gathered
# bucket never materialises between the backward pass and the encode.


def gather_ef_int8(fb, eb, perm, *, gamma: float, use_pallas: bool = True):
    """Fused gather + EF + int8 encode of one rung's rows.
    Returns (q (S, LANES) int8, scales (S, 1) f32, residual (S*LANES,))."""
    if use_pallas:
        q, s, r = quantize_int8_gather(fb, eb, perm, gamma=gamma,
                                       interpret=interpret_mode())
    else:
        q, s, r = ref.quantize_int8_gather_ref(fb, eb, perm, gamma=gamma)
    return q, s, r.reshape(-1)


def gather_ef_int4(fb, eb, perm, *, gamma: float, use_pallas: bool = True):
    """Fused gather + EF + packed-int4 encode of one rung's rows.
    Returns (packed (S, LANES//2) uint8, scales (S, 1) f32,
    residual (S*LANES,))."""
    if use_pallas:
        p, s, r = ef_int4_gather(fb, eb, perm, gamma=gamma,
                                 interpret=interpret_mode())
    else:
        p, s, r = ref.ef_int4_gather_ref(fb, eb, perm, gamma=gamma)
    return p, s, r.reshape(-1)


def gather_ef_sign(fb, eb, perm, *, gamma: float, use_pallas: bool = True):
    """Fused gather + EF + 1-bit sign encode of one rung's rows.
    Returns (sign (S, LANES) int8, scales (S, 1) f32,
    residual (S*LANES,))."""
    if use_pallas:
        sg, s, r = ef_sign_gather(fb, eb, perm, gamma=gamma,
                                  interpret=interpret_mode())
    else:
        sg, s, r = ref.ef_sign_gather_ref(fb, eb, perm, gamma=gamma)
    return sg, s, r.reshape(-1)


def gather_ef_topk(fb, eb, perm, *, gamma: float, k: int,
                   use_pallas: bool = True):
    """Fused gather + EF + block top-k selection of one rung's rows.
    Returns (selected_dense (S, LANES) f32, residual (S*LANES,))."""
    if use_pallas:
        sel, res = ef_topk_gather(fb, eb, perm, gamma=gamma, k=k,
                                  interpret=interpret_mode())
    else:
        sel, res = ref.ef_topk_gather_ref(fb, eb, perm, gamma=gamma, k=k)
    return sel, res.reshape(-1)
