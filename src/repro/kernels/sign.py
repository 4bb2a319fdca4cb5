"""Pallas TPU kernel: fused error-feedback + 1-bit sign compression.

The sign-with-majority-vote rung (signSGD / "When Less is More") transmits
one bit per entry plus a per-1024-block magnitude ``scale = mean(|ef|)``.
This kernel fuses the HBM-heavy part into one VMEM pass per (8, 1024)
tile:

    ef       = g + gamma * e
    sign     = +1 where ef >= 0 else -1      (int8, one per entry)
    scale    = mean(|ef|) per 1024-block
    residual = ef - sign * scale             (next error-feedback buffer)

The 8-entries-per-byte bit packing happens OUTSIDE the kernel (jnp, in
repro/codecs/builtin.py): it runs on the 8x-smaller int8 sign tensor, so
it is not HBM-bound, and keeping sub-byte shuffles out of Mosaic keeps the
kernel portable across TPU generations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.topk_compress import LANES, ROWS, gather_ef_call


def lane_mean(x):
    """Mean over the last axis, (rows, C) -> (rows, 1), summed by halving
    with static slices: the same adds in the same order on every backend.
    A reduce leaves the order to the compiler, and Mosaic and XLA pick
    different ones, so their means differ in the last bit.  Widths that
    are not a power of two finish with a plain sum."""
    w = x.shape[-1]
    n = w
    while w > 1 and w % 2 == 0:
        w //= 2
        x = x[:, :w] + x[:, w:]
    return jnp.sum(x, axis=-1, keepdims=True) / n


def _sign_body(x):
    """Shared math (kernel + oracle). x: (rows, LANES) f32."""
    scale = lane_mean(jnp.abs(x))
    sign = jnp.where(x >= 0, 1.0, -1.0)
    return sign, scale


def _kernel(g_ref, e_ref, sign_ref, s_ref, r_ref, *, gamma: float):
    g = g_ref[...].astype(jnp.float32)
    e = e_ref[...].astype(jnp.float32)
    ef = g + gamma * e
    sign, scale = _sign_body(ef)
    sign_ref[...] = sign.astype(jnp.int8)
    s_ref[...] = scale.astype(jnp.float32)
    r_ref[...] = (ef - sign * scale).astype(r_ref.dtype)


@functools.partial(jax.jit, static_argnames=("gamma", "interpret"))
def ef_sign_fused(g, e, *, gamma: float, interpret: bool = False):
    """g, e: (n_rows, LANES) f32 — n_rows % ROWS == 0.
    Returns (sign int8 (n_rows, LANES), scales (n_rows, 1) f32,
    residual f32)."""
    n_rows, lanes = g.shape
    assert lanes == LANES and n_rows % ROWS == 0, (g.shape,)
    grid = (n_rows // ROWS,)
    spec = pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))
    sspec = pl.BlockSpec((ROWS, 1), lambda i: (i, 0))
    sign, s, r = pl.pallas_call(
        functools.partial(_kernel, gamma=gamma),
        name="ef_sign_fused",
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, sspec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((n_rows, LANES), jnp.int8),
            jax.ShapeDtypeStruct((n_rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_rows, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(g, e)
    return sign, s, r


@functools.partial(jax.jit, static_argnames=("gamma", "interpret"))
def ef_sign_gather(fb, eb, perm, *, gamma: float,
                   interpret: bool = False):
    """Producer-fused gather + EF + 1-bit sign compression through
    ``perm``.  Returns (sign (S, LANES) int8, scales (S, 1) f32,
    residual (S, LANES) f32), per-row bit-exact to
    :func:`ef_sign_fused`."""

    def body(g, e):
        ef = g.astype(jnp.float32) + gamma * e.astype(jnp.float32)
        sign, scale = _sign_body(ef)
        return sign, scale, ef - sign * scale

    out_defs = [(LANES, jnp.int8), (1, jnp.float32), (LANES, jnp.float32)]
    return gather_ef_call(body, fb, eb, perm, out_defs,
                          name="ef_sign_gather", interpret=interpret)
