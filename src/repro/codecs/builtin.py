"""The built-in wire-format codecs.

The first four migrate the seed's closed compression ladder (FULL / INT8 /
TOPK / SKIP) payload-identically: ``encode`` / ``decode`` are the exact
seed operators from ``core/compression.py`` (tests/test_codecs.py pins
them bit-exact on fixed seeds).  ``int4`` and ``sign`` widen the ladder —
rungs the old four-layer hard-coding could not host without touching
compression, sync, knapsack and the scheduler at once:

  * ``int4``: packed two-nibbles-per-byte with blockwise absmax scale —
    dense like INT8 at half the wire bytes;
  * ``sign``: 1-bit sign with per-block mean-magnitude scale and
    majority-vote pod aggregation (signSGD with majority vote; "When Less
    is More" shows such formats can converge faster with fewer bits).

Each codec's Pallas path lives in ``repro/kernels`` and is selected by
``use_pallas`` (see ``repro.kernels.ops.default_use_pallas``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.codecs.base import (POD_AXIS, Codec, n_blocks, pack_bits,
                               register_codec, unpack_bits)
from repro.core.compression import (BLOCK, int8_compress, int8_decompress,
                                    pad_to_blocks, topk_compress,
                                    topk_decompress)
from repro.kernels import ops
from repro.kernels.decode import (FIXED_POINT_BITS, fixed_point,
                                  from_fixed_point)
from repro.kernels.quantize import _int4_body, pack_nibbles, unpack_nibbles
from repro.kernels.sign import lane_mean


@register_codec
class FullCodec(Codec):
    """Dense bf16 — the psum rung.  Wire bytes are the bf16 ring
    all-reduce volume, and the exchange really is a bf16 psum (the seed
    psum'd in f32 while pricing bf16 — the analytic/traced drift this
    refactor removes).  Note: backends without native bf16 reduction (the
    CPU container) promote the all-reduce to f32 in HLO; on TPU it stays
    bf16 (tests/test_collectives.py accepts both byte totals)."""
    name = "full"
    value_bits = 16
    #: the exchange is a psum, not a payload gather: there is no per-peer
    #: decode for the ring to hide (XLA already pipelines the all-reduce),
    #: so FULL stays on its one-shot path.
    supports_ring = False

    def wire_bytes(self, n: int, n_pods: int, block: int = BLOCK) -> int:
        if n_pods <= 1 or n <= 0:
            return 0
        # bf16 ring all-reduce: 2 * (P-1)/P * 2n bytes on the wire
        return int(2 * (n_pods - 1) / n_pods * 2 * n)

    def payload_bytes(self, n: int, block: int = BLOCK) -> int:
        return 2 * n  # bf16 (informational; the exchange is a psum)

    def encode(self, blocks):
        return {"wire": blocks.astype(jnp.bfloat16)}

    def decode(self, payload, block: int = BLOCK):
        return payload["wire"].astype(jnp.float32)

    def ef_encode(self, flat, e_flat, *, gamma, block=BLOCK,
                  use_pallas=False):
        ef = flat + gamma * e_flat
        wire = ef.astype(jnp.bfloat16)
        own = wire.astype(jnp.float32)
        return {"wire": wire}, own, ef - own

    def pod_exchange(self, payload, omega, *, n, block=BLOCK,
                     axis=POD_AXIS, **_kw):
        raise NotImplementedError("FULL aggregates inside ef_sync (psum)")

    def ef_sync(self, flat, e_flat, omega, omega_own, *, gamma, n_pods,
                block=BLOCK, axis=POD_AXIS, use_pallas=False,
                deterministic=None, fixed_bits=None):
        """The psum exchange is already cross-pod deterministic on any
        pod count: XLA's all-reduce hands every participant the SAME
        reduced bits (whatever internal order it reduces in), so pods
        cannot drift apart — ``deterministic`` needs no special mode
        here.  (The inherited accumulation trio still supports the
        fixed-point mode, so a gather-style fold of FULL payloads — e.g.
        a future ring variant — is order-insensitive for free.)"""
        payload, own, new_e = self.ef_encode(flat, e_flat, gamma=gamma,
                                             block=block)
        if n_pods > 1:
            # omega folded in before the psum so the collective itself
            # moves bf16 — exactly what wire_bytes prices.
            contrib = (own * omega_own).astype(jnp.bfloat16)
            agg = jax.lax.psum(contrib, axis).astype(jnp.float32)
        else:
            agg = own * omega_own
        return agg, new_e


@register_codec
class Int8Codec(Codec):
    """Dense blockwise-absmax int8 (+ f32 scale per 1024-block)."""
    name = "int8"
    value_bits = 8
    supports_hier = True  # dense quantiser: tier-2 re-encode is faithful
    producer_fused = True  # gather fuses into the encode kernel

    def payload_bytes(self, n: int, block: int = BLOCK) -> int:
        nb = n_blocks(n, block)
        return nb * block + 4 * nb  # int8 payload (block-padded) + scales

    def value_fraction(self) -> float:
        return 0.97

    def encode(self, blocks):
        q, scale = int8_compress(blocks)
        return {"q": q, "scale": scale}

    def decode(self, payload, block: int = BLOCK):
        return int8_decompress(payload["q"], payload["scale"])

    def ef_encode(self, flat, e_flat, *, gamma, block=BLOCK,
                  use_pallas=False):
        if not use_pallas or block != ops.LANES:
            return super().ef_encode(flat, e_flat, gamma=gamma, block=block)
        n = flat.shape[0]
        ef = flat + gamma * e_flat
        q, s, r, _ = ops.quantize_int8(ef, use_pallas=True)
        nb = n_blocks(n, block)
        # kernel tiles pad to 8-row multiples; only the nb real blocks
        # ever reach the wire (analytic bytes == traced bytes).  r IS the
        # next residual; own (dead on the multi-pod path) is one fused
        # elementwise pass.
        payload = {"q": q[:nb], "scale": s[:nb, 0]}
        return payload, ef - r, r

    def ef_encode_gather(self, fb, eb, perm, *, gamma, block=BLOCK,
                         use_pallas=False):
        if not use_pallas or block != ops.LANES:
            return super().ef_encode_gather(fb, eb, perm, gamma=gamma,
                                            block=block,
                                            use_pallas=use_pallas)
        q, s, r = ops.gather_ef_int8(fb, eb, perm, gamma=gamma,
                                     use_pallas=True)
        # own (dead-code on the multi-pod path) re-derives ef lazily
        own = (fb[perm] + gamma * eb[perm]).reshape(-1) - r
        return {"q": q, "scale": s[:, 0]}, own, r

    def decode_accumulate(self, acc, payload, weight, *, block=BLOCK,
                          use_pallas=False, deterministic=False,
                          fixed_bits=FIXED_POINT_BITS):
        if not use_pallas or block != ops.LANES:
            return super().decode_accumulate(
                acc, payload, weight, block=block,
                deterministic=deterministic, fixed_bits=fixed_bits)
        return ops.decode_accum_int8(
            acc, payload["q"], payload["scale"], weight, use_pallas=True,
            fixed_bits=fixed_bits if deterministic else None)


@register_codec
class TopKCodec(Codec):
    """Block-local top-k, int8-quantised values + uint16 indices.

    The ring decode-accumulate is a float scatter-add — inherently
    fold-order sensitive — so the deterministic P >= 3 mode uses the
    canonical-order buffering path (``canonical_fold``): each chunk's
    peer payloads are buffered over the hop chain and folded in pod
    order 0..P-1, the exact association of the one-shot fold."""
    name = "topk"
    value_bits = 8
    canonical_fold = True
    producer_fused = True  # gather fuses into the selection kernel

    def __init__(self, ratio: float = 0.1):
        if not 0.0 < ratio < 1.0:
            raise ValueError(f"topk ratio must be in (0, 1), got {ratio}")
        self.keep_ratio = float(ratio)

    def block_k(self, block: int = BLOCK) -> int:
        """Static k per block (multiple of 8 lanes, >= 8)."""
        k = int(round(self.keep_ratio * block))
        return max(8, ((k + 7) // 8) * 8)

    def payload_bytes(self, n: int, block: int = BLOCK) -> int:
        nb = n_blocks(n, block)
        k = self.block_k(block)
        return nb * k * (1 + 2) + 4 * nb  # int8 vals + u16 idx + f32 scales

    def value_fraction(self) -> float:
        return self.keep_ratio ** 0.5 * 0.97

    def encode(self, blocks):
        q, idx, scale = topk_compress(blocks, self.block_k(blocks.shape[1]))
        return {"q": q, "idx": idx, "scale": scale}

    def decode(self, payload, block: int = BLOCK):
        return topk_decompress(payload["q"], payload["idx"],
                               payload["scale"], block)

    def ef_encode(self, flat, e_flat, *, gamma, block=BLOCK,
                  use_pallas=False):
        if not use_pallas or block != ops.LANES:
            return super().ef_encode(flat, e_flat, gamma=gamma, block=block)
        n = flat.shape[0]
        k = self.block_k(block)
        # one fused HBM pass: EF accumulate + bisection top-k selection
        sel, res = ops.ef_topk(flat, e_flat, gamma=gamma, k=k,
                               use_pallas=True)
        # pack the (≈k-sparse) selected tile into the wire format; the
        # residual picks up both the dropped entries (res) and the int8
        # quantisation error of the kept ones (sel - own).
        payload = self.encode(pad_to_blocks(sel, block))
        own = self.decode(payload, block).reshape(-1)[:n]
        return payload, own, (sel - own) + res

    def ef_encode_gather(self, fb, eb, perm, *, gamma, block=BLOCK,
                         use_pallas=False):
        if not use_pallas or block != ops.LANES:
            return super().ef_encode_gather(fb, eb, perm, gamma=gamma,
                                            block=block,
                                            use_pallas=use_pallas)
        n = perm.shape[0] * block
        k = self.block_k(block)
        sel, res = ops.gather_ef_topk(fb, eb, perm, gamma=gamma, k=k,
                                      use_pallas=True)
        payload = self.encode(sel)          # sel is already (S, block)
        own = self.decode(payload, block).reshape(-1)[:n]
        return payload, own, (sel.reshape(-1) - own) + res

    def decode_accumulate(self, acc, payload, weight, *, block=BLOCK,
                          use_pallas=False, deterministic=False,
                          fixed_bits=FIXED_POINT_BITS):
        # never called with deterministic=True: canonical_fold routes the
        # P >= 3 ring through the buffered canonical-order float fold
        assert not deterministic, "topk folds canonically, not fixed-point"
        if not use_pallas or block != ops.LANES:
            return super().decode_accumulate(acc, payload, weight,
                                             block=block)
        return ops.topk_scatter_accum(acc, payload["q"], payload["idx"],
                                      payload["scale"], weight,
                                      use_pallas=True)


@register_codec
class SkipCodec(Codec):
    """Transmit nothing; the whole EF accumulator becomes the residual."""
    name = "skip"
    value_bits = 0
    keep_ratio = 0.0
    supports_ring = False           # nothing on the wire, nothing to ring

    def payload_bytes(self, n: int, block: int = BLOCK) -> int:
        return 0

    def wire_bytes(self, n: int, n_pods: int, block: int = BLOCK) -> int:
        return 0

    def value_fraction(self) -> float:
        return 0.0

    def encode(self, blocks):
        return {}

    def decode(self, payload, block: int = BLOCK):
        raise NotImplementedError("SKIP has no payload to decode")

    def ef_sync(self, flat, e_flat, omega, omega_own, *, gamma, n_pods,
                block=BLOCK, axis=POD_AXIS, use_pallas=False,
                deterministic=None, fixed_bits=None):
        ef = flat + gamma * e_flat
        return jnp.zeros_like(flat), ef


@register_codec
class Int4Codec(Codec):
    """Dense packed int4: two nibbles per byte + blockwise absmax scale."""
    name = "int4"
    value_bits = 4
    supports_hier = True  # dense quantiser: tier-2 re-encode is faithful
    producer_fused = True  # gather fuses into the encode kernel

    def payload_bytes(self, n: int, block: int = BLOCK) -> int:
        nb = n_blocks(n, block)
        return nb * (block // 2) + 4 * nb

    def value_fraction(self) -> float:
        return 0.90

    def encode(self, blocks):
        q, scale = _int4_body(blocks)
        return {"q": pack_nibbles(q), "scale": scale[:, 0]}

    def decode(self, payload, block: int = BLOCK):
        q = unpack_nibbles(payload["q"])
        return q * payload["scale"][:, None]

    def ef_encode(self, flat, e_flat, *, gamma, block=BLOCK,
                  use_pallas=False):
        if not use_pallas or block != ops.LANES:
            return super().ef_encode(flat, e_flat, gamma=gamma, block=block)
        n = flat.shape[0]
        p, s, r, _ = ops.ef_int4(flat, e_flat, gamma=gamma, use_pallas=True)
        nb = n_blocks(n, block)
        payload = {"q": p[:nb], "scale": s[:nb, 0]}
        own = (flat + gamma * e_flat) - r  # dead-code on the multi-pod path
        return payload, own, r

    def ef_encode_gather(self, fb, eb, perm, *, gamma, block=BLOCK,
                         use_pallas=False):
        if not use_pallas or block != ops.LANES:
            return super().ef_encode_gather(fb, eb, perm, gamma=gamma,
                                            block=block,
                                            use_pallas=use_pallas)
        p, s, r = ops.gather_ef_int4(fb, eb, perm, gamma=gamma,
                                     use_pallas=True)
        own = (fb[perm] + gamma * eb[perm]).reshape(-1) - r
        return {"q": p, "scale": s[:, 0]}, own, r

    def decode_accumulate(self, acc, payload, weight, *, block=BLOCK,
                          use_pallas=False, deterministic=False,
                          fixed_bits=FIXED_POINT_BITS):
        if not use_pallas or block != ops.LANES:
            return super().decode_accumulate(
                acc, payload, weight, block=block,
                deterministic=deterministic, fixed_bits=fixed_bits)
        return ops.decode_accum_int4(
            acc, payload["q"], payload["scale"], weight, use_pallas=True,
            fixed_bits=fixed_bits if deterministic else None)


@register_codec
class SignCodec(Codec):
    """1-bit sign + per-block mean-|ef| scale, majority-vote aggregation."""
    name = "sign"
    value_bits = 1
    producer_fused = True  # gather fuses into the encode kernel

    def payload_bytes(self, n: int, block: int = BLOCK) -> int:
        nb = n_blocks(n, block)
        return nb * (block // 8) + 4 * nb

    def value_fraction(self) -> float:
        # 1 bit per entry keeps direction only; rank it between the
        # topk1 and topk10 rungs (signSGD-style convergence).
        return 0.25

    def encode(self, blocks):
        scale = lane_mean(jnp.abs(blocks))[:, 0].astype(jnp.float32)
        return {"q": pack_bits(blocks >= 0), "scale": scale}

    def decode(self, payload, block: int = BLOCK):
        signs = unpack_bits(payload["q"], block).astype(jnp.float32) * 2 - 1
        return signs * payload["scale"][:, None]

    def ef_encode(self, flat, e_flat, *, gamma, block=BLOCK,
                  use_pallas=False):
        if not use_pallas or block != ops.LANES:
            return super().ef_encode(flat, e_flat, gamma=gamma, block=block)
        n = flat.shape[0]
        sg, s, r, _ = ops.ef_sign(flat, e_flat, gamma=gamma,
                                  use_pallas=True)
        nb = n_blocks(n, block)
        payload = {"q": pack_bits(sg[:nb] > 0), "scale": s[:nb, 0]}
        own = (flat + gamma * e_flat) - r  # dead-code on the multi-pod path
        return payload, own, r

    def ef_encode_gather(self, fb, eb, perm, *, gamma, block=BLOCK,
                         use_pallas=False):
        if not use_pallas or block != ops.LANES:
            return super().ef_encode_gather(fb, eb, perm, gamma=gamma,
                                            block=block,
                                            use_pallas=use_pallas)
        sg, s, r = ops.gather_ef_sign(fb, eb, perm, gamma=gamma,
                                      use_pallas=True)
        payload = {"q": pack_bits(sg > 0), "scale": s[:, 0]}
        own = (fb[perm] + gamma * eb[perm]).reshape(-1) - r
        return payload, own, r

    # ---- ring pipeline: majority vote in the compressed domain ---------
    # The pod exchange itself is the BASE all_gather + trio fold (the
    # majority vote of Bernstein et al.'s signSGD expressed as partial
    # counts): agg = sign(sum_k omega_k * sign_k) scaled by the
    # omega-weighted mean magnitude.
    def accum_init(self, nb, block=BLOCK, *, deterministic=False):
        """Partial vote counts + partial magnitude — the compressed-domain
        state the ring circulates instead of a dense decode.  The
        deterministic mode keeps INTEGER vote counts (fixed-point omega x
        exact ±1 signs) and a fixed-point magnitude — both commutative,
        so any fold order reaches the same bits."""
        dt = jnp.int32 if deterministic else jnp.float32
        return {"vote": jnp.zeros((nb, block), dt),
                "mag": jnp.zeros((nb,), dt)}

    def decode_accumulate(self, acc, payload, weight, *, block=BLOCK,
                          use_pallas=False, deterministic=False,
                          fixed_bits=FIXED_POINT_BITS):
        if use_pallas and block == ops.LANES:
            vote, mag = ops.sign_vote_accum(
                acc["vote"], acc["mag"], payload["q"], payload["scale"],
                weight, use_pallas=True,
                fixed_bits=fixed_bits if deterministic else None)
            return {"vote": vote, "mag": mag}
        signs = unpack_bits(payload["q"], block).astype(jnp.float32) * 2 - 1
        if deterministic:
            wq = fixed_point(weight, fixed_bits)
            return {"vote": acc["vote"] + wq * signs.astype(jnp.int32),
                    "mag": acc["mag"] + fixed_point(
                        weight * payload["scale"], fixed_bits)}
        return {"vote": acc["vote"] + weight * signs,
                "mag": acc["mag"] + weight * payload["scale"]}

    def accum_finalize(self, acc, n, block=BLOCK, *, deterministic=False,
                       fixed_bits=FIXED_POINT_BITS):
        vote, mag = acc["vote"], acc["mag"]
        if deterministic:
            # votes only feed sign(); int32 -> f32 is exact here (the
            # count magnitude is far below 2^24)
            vote = vote.astype(jnp.float32)
            mag = from_fixed_point(mag, fixed_bits)
        agg = jnp.sign(vote) * mag[:, None]
        return agg.reshape(-1)[:n]
