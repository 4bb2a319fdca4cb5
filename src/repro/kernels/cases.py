"""The table of Pallas kernel entry points: for each of the 16 kernels,
its argument shapes at a chosen bucket size, its static keywords and its
``ref.py`` oracle.

One table serves every check that must cover all kernels: the chip smoke
test runs each case on the device against its oracle, the TPU compile
test compiles each case for a described v5e, the kernel tests run each
case interpreted on the CPU, and the graph auditor captures each case's
BlockSpecs.  A kernel added here is covered by all four.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import decode, quantize, ref, sign, topk_compress
from repro.kernels.topk_compress import LANES

#: the real bucket sizes the chip checks use: 65 536-row tiles, gathers
#: of 300 000 rows (a paper-350m rung's size; more than one call's scalar
#: memory holds) out of a 400 000-block (NB+1, LANES) buffer, and the
#: largest ladder top-k (TOPK25: 256 of 1024 kept)
REAL = dict(rows=65536, nb=400000, k=256, gather_rows=300000)


class Arg(NamedTuple):
    """One kernel operand: how to draw it, and its shape and dtype."""
    role: str            # f32 | scale | weight | q8 | u8 | acc_i | perm | idx
    shape: Tuple[int, ...]
    dtype: object
    hi: int = 0          # perm: exclusive upper bound of the row indices


@dataclasses.dataclass(frozen=True)
class KernelCase:
    name: str
    fn: Callable                      # jitted kernel entry point
    oracle: Callable                  # same positional args and keywords
    args: Tuple[Arg, ...]
    kw: Tuple[Tuple[str, object], ...] = ()

    def specs(self, sharding=None) -> tuple:
        """ShapeDtypeStructs of the operands (for AOT compiles)."""
        return tuple(jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=sharding)
                     for a in self.args)

    def inputs(self, seed: int = 0) -> tuple:
        """Operands drawn on the default device from ``seed``."""
        keys = jax.random.split(jax.random.PRNGKey(seed), len(self.args))
        return tuple(_draw(k, a) for k, a in zip(keys, self.args))

    def kernel(self, *args, interpret: bool = False):
        return self.fn(*args, interpret=interpret, **dict(self.kw))

    def reference(self, *args):
        return self.oracle(*args, **dict(self.kw))


def _draw(key, a: Arg):
    if a.role == "f32":
        return jax.random.normal(key, a.shape, jnp.float32)
    if a.role == "scale":        # per-block scales: positive, gradient-sized
        return jnp.abs(jax.random.normal(key, a.shape, jnp.float32)) * 1e-2
    if a.role == "weight":       # an omega entry
        return jax.random.uniform(key, a.shape, jnp.float32, 0.1, 1.0)
    if a.role == "q8":
        return jax.random.randint(key, a.shape, -127, 128, jnp.int32
                                  ).astype(jnp.int8)
    if a.role == "u8":
        return jax.random.randint(key, a.shape, 0, 256, jnp.int32
                                  ).astype(jnp.uint8)
    if a.role == "acc_i":        # fixed-point partial sums
        return jax.random.randint(key, a.shape, -(1 << 20), 1 << 20,
                                  jnp.int32)
    if a.role == "perm":
        return jax.random.randint(key, a.shape, 0, a.hi, jnp.int32)
    if a.role == "idx":          # k distinct lanes per row, as top_k emits
        rows, k = a.shape
        order = jnp.argsort(jax.random.uniform(key, (rows, LANES)), axis=1)
        return order[:, :k].astype(a.dtype)
    raise ValueError(f"unknown operand role {a.role!r}")


def kernel_cases(rows: int, nb: int, k: int,
                 gather_rows: Optional[int] = None) -> List[KernelCase]:
    """Every kernel entry point on ``rows``-row tiles (a multiple of
    ``ROWS``), gathers of ``gather_rows`` (default ``rows``) rows out of
    an (nb+1, LANES) buffer, and ``k`` kept entries per top-k block."""
    f32, i32, i8, u8 = jnp.float32, jnp.int32, jnp.int8, jnp.uint8
    L = LANES
    x = Arg("f32", (rows, L), f32)
    s = Arg("scale", (rows, 1), f32)
    w = Arg("weight", (1, 1), f32)
    q8 = Arg("q8", (rows, L), i8)
    p4 = Arg("u8", (rows, L // 2), u8)
    p1 = Arg("u8", (rows, L // 8), u8)
    acc_i = Arg("acc_i", (rows, L), i32)
    mag_i = Arg("acc_i", (rows, 1), i32)
    buf = Arg("f32", (nb + 1, L), f32)
    perm = Arg("perm", (gather_rows or rows,), i32, hi=nb + 1)
    gather = (buf, buf, perm)

    def fp(oracle):
        return lambda *a, bits: oracle(*a, bits)

    return [
        KernelCase("quantize_int8_fused", quantize.quantize_int8_fused,
                   ref.quantize_int8_ref, (x,)),
        KernelCase("dequantize_int8", quantize.dequantize_int8,
                   ref.dequantize_int8_ref, (q8, s)),
        KernelCase("ef_int4_fused", quantize.ef_int4_fused,
                   ref.ef_int4_ref, (x, x), (("gamma", 0.7),)),
        KernelCase("quantize_int8_gather", quantize.quantize_int8_gather,
                   ref.quantize_int8_gather_ref, gather,
                   (("gamma", 0.9),)),
        KernelCase("ef_int4_gather", quantize.ef_int4_gather,
                   ref.ef_int4_gather_ref, gather, (("gamma", 0.7),)),
        KernelCase("ef_sign_fused", sign.ef_sign_fused, ref.ef_sign_ref,
                   (x, x), (("gamma", 0.6),)),
        KernelCase("ef_sign_gather", sign.ef_sign_gather,
                   ref.ef_sign_gather_ref, gather, (("gamma", 0.6),)),
        KernelCase("ef_topk_select", topk_compress.ef_topk_select,
                   ref.ef_topk_select_ref, (x, x),
                   (("gamma", 1.0), ("k", k))),
        KernelCase("ef_topk_gather", topk_compress.ef_topk_gather,
                   ref.ef_topk_gather_ref, gather,
                   (("gamma", 1.0), ("k", k))),
        KernelCase("dequant_accum_int8_fused",
                   decode.dequant_accum_int8_fused,
                   ref.dequant_accum_int8_ref, (x, q8, s, w)),
        KernelCase("dequant_accum_int4_fused",
                   decode.dequant_accum_int4_fused,
                   ref.dequant_accum_int4_ref, (x, p4, s, w)),
        KernelCase("sign_vote_accum_fused", decode.sign_vote_accum_fused,
                   ref.sign_vote_accum_ref,
                   (x, Arg("f32", (rows, 1), f32), p1, s, w)),
        KernelCase("topk_scatter_accum_fused",
                   decode.topk_scatter_accum_fused,
                   ref.topk_scatter_accum_ref,
                   (x, Arg("q8", (rows, k), i8),
                    Arg("idx", (rows, k), jnp.uint16), s, w)),
        KernelCase("dequant_accum_int8_fp_fused",
                   decode.dequant_accum_int8_fp_fused,
                   fp(ref.dequant_accum_int8_fp_ref), (acc_i, q8, s, w),
                   (("bits", decode.FIXED_POINT_BITS),)),
        KernelCase("dequant_accum_int4_fp_fused",
                   decode.dequant_accum_int4_fp_fused,
                   fp(ref.dequant_accum_int4_fp_ref), (acc_i, p4, s, w),
                   (("bits", decode.FIXED_POINT_BITS),)),
        KernelCase("sign_vote_accum_fp_fused",
                   decode.sign_vote_accum_fp_fused,
                   fp(ref.sign_vote_accum_fp_ref),
                   (acc_i, mag_i, p1, s, w),
                   (("bits", decode.FIXED_POINT_BITS),)),
    ]


def parity(case: KernelCase, inputs, *, interpret: bool) -> dict:
    """Run ``case``'s kernel and its jitted oracle on ``inputs``; count
    the output elements that differ and their largest absolute gap (both
    reduced on the device, so no bucket crosses to the host)."""
    got = jax.tree.leaves(case.kernel(*inputs, interpret=interpret))
    want = jax.tree.leaves(jax.jit(case.reference)(*inputs))
    assert len(got) == len(want), (case.name, len(got), len(want))
    mismatches, max_diff = 0, 0.0
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, \
            (case.name, a.shape, b.shape, a.dtype, b.dtype)
        mismatches += int(jnp.sum(a != b))
        gap = jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))
        max_diff = max(max_diff, float(jnp.max(gap)))
    return {"mismatches": mismatches, "max_abs_diff": max_diff}
