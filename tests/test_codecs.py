"""Codec subsystem tests: registry round-trip, bit-exact payload parity of
the four migrated seed rungs, error-feedback recomposition for every
registered codec (oracle AND Pallas path), packed-wire-size == analytic
accounting, and Level -> codec resolution."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip, the rest of the module runs
    from hypothesis_stub import given, settings, st

from repro.codecs import (Codec, build_codec, get_codec,
                          list_codecs, pack_bits, pack_payload,
                          plan_wire_bytes, register_codec, unpack_bits,
                          unpack_payload)
from repro.core import compression as C
from repro.core.compression import Level
from repro.core.scheduler import SyncPlan

BUILTINS = ["full", "int4", "int8", "sign", "skip", "topk"]


def _rand(n, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(n)
                       .astype(np.float32))


def _default(name):
    return build_codec(name)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_builtins_registered(self):
        assert list_codecs() == BUILTINS

    def test_build_and_get(self):
        for name in list_codecs():
            c = build_codec(name)
            assert isinstance(c, Codec)
            assert c.name == name
            assert get_codec(name) is type(c)

    def test_unknown_name_raises(self):
        with pytest.raises(KeyError, match="unknown codec"):
            get_codec("no-such-codec")

    def test_register_rejects_empty_and_duplicate(self):
        with pytest.raises(ValueError, match="non-empty"):
            register_codec(type("Anon", (Codec,), {}))
        with pytest.raises(ValueError, match="already registered"):
            register_codec(type("Clash", (Codec,), {"name": "int8"}))

    def test_topk_requires_valid_ratio(self):
        with pytest.raises(ValueError, match="ratio"):
            build_codec("topk", ratio=1.5)


# ---------------------------------------------------------------------------
# Level -> codec resolution
# ---------------------------------------------------------------------------


class TestLevelResolution:
    @pytest.mark.parametrize("level,codec_name", [
        (Level("FULL", 1.0, 16), "full"),
        (Level("INT8", 1.0, 8), "int8"),
        (Level("INT4", 1.0, 4), "int4"),
        (Level("SIGN1", 1.0, 1), "sign"),
        (Level("TOPK10_INT8", 0.10, 8), "topk"),
        (Level("SKIP", 0.0, 0), "skip"),
    ])
    def test_semantics(self, level, codec_name):
        assert level.codec.name == codec_name

    def test_topk_carries_ratio(self):
        assert Level("T", 0.25, 8).codec.keep_ratio == 0.25
        assert Level("T", 0.25, 8).codec.block_k(1024) == 256

    def test_resolution_cached(self):
        assert Level("A", 0.1, 8).codec is Level("B", 0.1, 8).codec


# ---------------------------------------------------------------------------
# bit-exact payload parity vs the seed operators
# ---------------------------------------------------------------------------


def _seed_topk_compress(blocks, k):
    """The seed's compression.topk_compress, frozen verbatim."""
    mag = jnp.abs(blocks)
    _, idx = jax.lax.top_k(mag, k)
    vals = jnp.take_along_axis(blocks, idx, axis=1)
    scale = jnp.max(jnp.abs(vals), axis=1) / 127.0
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(vals / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, idx.astype(jnp.uint16), scale.astype(jnp.float32)


def _seed_int8_compress(blocks):
    """The seed's compression.int8_compress, frozen verbatim."""
    scale = jnp.max(jnp.abs(blocks), axis=1) / 127.0
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(blocks / scale[:, None]), -127, 127
                 ).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


class TestSeedPayloadParity:
    """The four seed rungs must migrate payload-identically: same bytes on
    the wire for the same input, bit for bit."""

    @pytest.mark.parametrize("seed", [0, 1, 7])
    @pytest.mark.parametrize("ratio", [0.25, 0.10, 0.01])
    def test_topk_bit_exact(self, seed, ratio):
        blocks = C.pad_to_blocks(_rand(8192, seed))
        codec = build_codec("topk", ratio=ratio)
        pay = codec.encode(blocks)
        q, idx, scale = _seed_topk_compress(blocks, codec.block_k(1024))
        np.testing.assert_array_equal(np.asarray(pay["q"]), np.asarray(q))
        np.testing.assert_array_equal(np.asarray(pay["idx"]),
                                      np.asarray(idx))
        np.testing.assert_array_equal(np.asarray(pay["scale"]),
                                      np.asarray(scale))

    @pytest.mark.parametrize("seed", [0, 3])
    def test_int8_bit_exact(self, seed):
        blocks = C.pad_to_blocks(_rand(4096, seed) * 10)
        pay = build_codec("int8").encode(blocks)
        q, scale = _seed_int8_compress(blocks)
        np.testing.assert_array_equal(np.asarray(pay["q"]), np.asarray(q))
        np.testing.assert_array_equal(np.asarray(pay["scale"]),
                                      np.asarray(scale))

    def test_full_bit_exact(self):
        blocks = C.pad_to_blocks(_rand(2048, 5))
        pay = build_codec("full").encode(blocks)
        np.testing.assert_array_equal(
            np.asarray(pay["wire"]),
            np.asarray(blocks.astype(jnp.bfloat16)))

    def test_skip_empty(self):
        assert build_codec("skip").encode(
            C.pad_to_blocks(_rand(1024))) == {}

    def test_wire_bytes_parity_with_seed_formulas(self):
        """FULL (ring psum) and TOPK (all_gather) keep the seed's exact
        byte formulas; INT8 now prices the block-padded payload that is
        actually packed on the wire."""
        n, P, block = 1_000_000, 2, 1024
        nb = (n + block - 1) // block
        assert Level("FULL", 1.0, 16).wire_bytes(n, P) == \
            int(2 * (P - 1) / P * 2 * n)
        for ratio in (0.25, 0.10, 0.01):
            lvl = Level("T", ratio, 8)
            k = lvl.block_k(block)
            assert lvl.wire_bytes(n, P) == (nb * k * 3 + 4 * nb) * (P - 1)
        assert Level("INT8", 1.0, 8).wire_bytes(n, P) == \
            (nb * block + 4 * nb) * (P - 1)
        # every codec is free when there is nobody to talk to
        for name in list_codecs():
            assert _default(name).wire_bytes(n, 1) == 0


# ---------------------------------------------------------------------------
# roundtrip + error-feedback recomposition properties
# ---------------------------------------------------------------------------


def _roundtrip_tol(codec, blocks):
    """Per-codec bound on |decode(encode(x)) - x| for kept entries."""
    absmax = float(jnp.max(jnp.abs(blocks)))
    if codec.name == "full":
        return absmax * 2 ** -8  # bf16 mantissa
    if codec.name == "int8":
        return absmax / 127.0 * 0.51 + 1e-6
    if codec.name == "int4":
        return absmax / 7.0 * 0.51 + 1e-6
    return None  # topk/sign/skip: lossy beyond a pointwise bound


class TestRoundTrip:
    @pytest.mark.parametrize("name", ["full", "int8", "int4"])
    def test_dense_roundtrip_error_bounded(self, name):
        codec = _default(name)
        blocks = C.pad_to_blocks(_rand(4096, 11) * 3)
        back = codec.decode(codec.encode(blocks), 1024)
        tol = _roundtrip_tol(codec, blocks)
        np.testing.assert_allclose(np.asarray(back), np.asarray(blocks),
                                   atol=tol)

    def test_sign_roundtrip_magnitude(self):
        codec = _default("sign")
        blocks = C.pad_to_blocks(_rand(2048, 12))
        back = codec.decode(codec.encode(blocks), 1024)
        # every reconstructed entry is +-(block mean magnitude), signs match
        scale = np.asarray(jnp.mean(jnp.abs(blocks), axis=1))
        np.testing.assert_allclose(
            np.abs(np.asarray(back)),
            np.broadcast_to(scale[:, None], back.shape), rtol=1e-6)
        assert np.all((np.asarray(back) >= 0) == (np.asarray(blocks) >= 0))

    def test_int4_roundtrip_through_level(self):
        out = C.roundtrip(_rand(3000, 13), Level("INT4", 1.0, 4))
        assert out.shape == (3000,)
        err = np.abs(np.asarray(out) - np.asarray(_rand(3000, 13)))
        assert err.max() <= float(jnp.abs(_rand(3000, 13)).max()) / 7 * 0.51 \
            + 1e-6

    @given(st.integers(min_value=0, max_value=2 ** 31 - 1))
    @settings(max_examples=15, deadline=None)
    def test_ef_recomposition_every_codec(self, seed):
        """agg/omega + new_e == g + gamma*e for EVERY registered codec —
        the lossless transmit/residual split error feedback relies on."""
        g = _rand(2048 + seed % 7, seed % 1000)
        e = _rand(g.shape[0], (seed + 1) % 1000) * 0.1
        om = jnp.ones((1,), jnp.float32)
        gamma = 0.7
        ef = np.asarray(g) + gamma * np.asarray(e)
        for name in list_codecs():
            agg, new_e = _default(name).ef_sync(
                g, e, om, om[0], gamma=gamma, n_pods=1, block=1024)
            np.testing.assert_allclose(np.asarray(agg + new_e), ef,
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=name)

    @pytest.mark.parametrize("name", ["topk", "int8", "int4", "sign"])
    def test_ef_recomposition_pallas_path(self, name):
        """Same invariant through the fused Pallas kernels (interpret on
        CPU) — the path grad_sync/delta_sync exercise on accelerators."""
        g = _rand(5000, 21)
        e = _rand(5000, 22) * 0.2
        om = jnp.ones((1,), jnp.float32)
        agg, new_e = _default(name).ef_sync(
            g, e, om, om[0], gamma=1.0, n_pods=1, block=1024,
            use_pallas=True)
        np.testing.assert_allclose(np.asarray(agg + new_e),
                                   np.asarray(g + e), rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("name", ["int8", "int4", "sign"])
    def test_pallas_payload_matches_oracle(self, name):
        """Dense codecs: fused-kernel payload == oracle payload bit-exact
        (top-k is excluded: its bisection select tolerates threshold
        ties, covered by tests/test_kernels.py)."""
        g = _rand(3000, 31)
        e = _rand(3000, 32) * 0.3
        codec = _default(name)
        pay_o, own_o, _ = codec.ef_encode(g, e, gamma=0.9, block=1024,
                                          use_pallas=False)
        pay_p, own_p, _ = codec.ef_encode(g, e, gamma=0.9, block=1024,
                                          use_pallas=True)
        assert sorted(pay_o) == sorted(pay_p)
        for k in pay_o:
            a, b = np.asarray(pay_o[k]), np.asarray(pay_p[k])
            if a.dtype == np.float32:
                # fma-order differences (kernel vs oracle) reach ~1 ulp
                np.testing.assert_allclose(a, b, rtol=1e-6,
                                           err_msg=f"{name}/{k}")
            else:
                # a 1-ulp scale wiggle may flip a value sitting exactly on
                # a rounding boundary; allow <=0.1% of entries
                assert (a != b).mean() <= 1e-3, f"{name}/{k}"
        np.testing.assert_allclose(np.asarray(own_o), np.asarray(own_p),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# chunked ring pipeline: decode-accumulate parity with the one-shot path
# ---------------------------------------------------------------------------


RING_CODECS = ["int8", "int4", "sign", "topk"]


def _payloads(codec, n, n_pods=2, block=1024):
    """One payload per virtual pod (different gradients per peer)."""
    outs = []
    for p in range(n_pods):
        pay, _, _ = codec.ef_encode(_rand(n, 40 + p),
                                    _rand(n, 50 + p) * 0.1, gamma=0.8,
                                    block=block)
        outs.append(pay)
    return outs


def _one_shot_agg(codec, payloads, omega, n, block=1024):
    """The one-shot path's aggregation math (what pod_exchange computes
    per peer from the gathered buffer), independent of the ring code."""
    if codec.name == "sign":
        vote = mag = None
        for w, pl_ in zip(omega, payloads):
            signs = unpack_bits(pl_["q"], block).astype(jnp.float32) * 2 - 1
            contrib, scale_c = w * signs, w * pl_["scale"]
            vote = contrib if vote is None else vote + contrib
            mag = scale_c if mag is None else mag + scale_c
        return (jnp.sign(vote) * mag[:, None]).reshape(-1)[:n]
    agg = jnp.zeros((n,), jnp.float32)
    for w, pl_ in zip(omega, payloads):
        agg = agg + w * codec.decode(pl_, block).reshape(-1)[:n]
    return agg


def _ring_agg(codec, payloads, omega, n, n_chunks, block=1024):
    """The ring path's math: chunk slices folded through accum_init /
    decode_accumulate / accum_finalize in the same peer order."""
    nb = (n + block - 1) // block
    assert nb % n_chunks == 0
    cb = nb // n_chunks
    parts = []
    for i in range(n_chunks):
        acc = codec.accum_init(cb, block)
        for w, pl_ in zip(omega, payloads):
            acc = codec.decode_accumulate(
                acc, codec._chunk_payload(pl_, i, cb), w, block=block)
        parts.append(codec.accum_finalize(acc, cb * block, block))
    return jnp.concatenate(parts)[:n]


class TestRingParity:
    @pytest.mark.parametrize("name", RING_CODECS)
    @pytest.mark.parametrize("n_chunks", [1, 2, 4])
    def test_ring_accumulate_bit_exact(self, name, n_chunks):
        """Chunked decode-accumulate == the one-shot aggregation, bit for
        bit, for every ring-capable codec (the exchange-level pin runs in
        tests/test_collectives.py on a real pod mesh)."""
        codec = _default(name)
        n = 4 * 1024
        omega = (0.6, 0.4)
        payloads = _payloads(codec, n)
        one = _one_shot_agg(codec, payloads, omega, n)
        ring = _ring_agg(codec, payloads, omega, n, n_chunks)
        np.testing.assert_array_equal(np.asarray(one), np.asarray(ring),
                                      err_msg=name)

    @pytest.mark.parametrize("name", RING_CODECS)
    def test_decode_accumulate_pallas_matches_oracle(self, name):
        """The fused Pallas decode-accumulate kernels (interpret on CPU)
        == the oracle acc + w * decode path."""
        codec = _default(name)
        n = 3 * 1024  # odd block count: exercises the ROWS padding
        pay, _, _ = codec.ef_encode(_rand(n, 60), jnp.zeros((n,)),
                                    gamma=1.0, block=1024)
        nb = 3
        w = jnp.float32(0.37)
        acc0 = codec.accum_init(nb, 1024)
        if name == "sign":
            acc0 = {"vote": jnp.asarray(
                        np.random.RandomState(1).randn(nb, 1024)
                        .astype(np.float32)),
                    "mag": jnp.abs(jnp.asarray(
                        np.random.RandomState(2).randn(nb)
                        .astype(np.float32)))}
        else:
            acc0 = jnp.asarray(np.random.RandomState(1).randn(nb, 1024)
                               .astype(np.float32))
        o = codec.decode_accumulate(acc0, pay, w, block=1024,
                                    use_pallas=False)
        p = codec.decode_accumulate(acc0, pay, w, block=1024,
                                    use_pallas=True)
        for a, b in zip(jax.tree.leaves(o), jax.tree.leaves(p)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7,
                                       err_msg=name)

    @pytest.mark.parametrize("name", ["int8", "int4", "sign"])
    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_deterministic_fold_is_order_insensitive(self, name,
                                                     use_pallas):
        """The P >= 3 mode: fixed-point / integer-vote partial sums reach
        bit-identical aggregates in ANY fold order (the float fold does
        not — that is the cross-pod drift the mode removes), and the
        fused Pallas kernels match the oracle bit for bit."""
        codec = _default(name)
        n = 4 * 1024
        omega = jnp.asarray([0.5, 0.3, 0.2], jnp.float32)
        payloads = _payloads(codec, n, n_pods=3)
        nb = 4

        def fold(order, det, up):
            acc = codec.accum_init(nb, 1024, deterministic=det)
            for j in order:
                acc = codec.decode_accumulate(acc, payloads[j], omega[j],
                                              block=1024, use_pallas=up,
                                              deterministic=det)
            return np.asarray(codec.accum_finalize(acc, n, 1024,
                                                   deterministic=det))

        a = fold([0, 1, 2], True, use_pallas)
        for order in ([2, 0, 1], [1, 2, 0], [2, 1, 0]):
            np.testing.assert_array_equal(a, fold(order, True, use_pallas),
                                          err_msg=f"{name}/{order}")
        # the dequant-add codecs also stay within the 2^-16 fixed-point
        # quantisation of the float fold (sign is excluded: a vote that
        # TIES in exact arithmetic legitimately resolves to 0 where the
        # float fold's rounding noise picked a side)
        if name != "sign":
            f = fold([0, 1, 2], False, use_pallas)
            np.testing.assert_allclose(a, f, atol=4 * 2.0 ** -16,
                                       err_msg=name)

    @pytest.mark.parametrize("name", ["int8", "int4", "sign"])
    def test_deterministic_pallas_matches_oracle_bitwise(self, name):
        """Integer accumulation admits no ulp wiggle: the fused fp
        kernels and the jnp oracle must agree EXACTLY."""
        codec = _default(name)
        n = 3 * 1024
        pay, _, _ = codec.ef_encode(_rand(n, 60), jnp.zeros((n,)),
                                    gamma=1.0, block=1024)
        w = jnp.float32(0.37)
        acc = codec.accum_init(3, 1024, deterministic=True)
        o = codec.decode_accumulate(acc, pay, w, block=1024,
                                    use_pallas=False, deterministic=True)
        p = codec.decode_accumulate(acc, pay, w, block=1024,
                                    use_pallas=True, deterministic=True)
        for a, b in zip(jax.tree.leaves(o), jax.tree.leaves(p)):
            assert a.dtype == b.dtype and a.dtype in (jnp.int32,)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)

    def test_deterministic_one_shot_matches_ring_fold(self):
        """pod_exchange's deterministic fold (canonical gather order) ==
        the ring's arrival-order fold: exact accumulation makes the order
        irrelevant, so ring <-> one-shot replans never move the bits."""
        for name in ["int8", "int4", "sign"]:
            codec = _default(name)
            n = 4 * 1024
            omega = jnp.asarray([0.2, 0.5, 0.3], jnp.float32)
            payloads = _payloads(codec, n, n_pods=3)
            nb = 4
            # one-shot: pods 0..P-1; ring at pod 1: own, then 0, then 2
            accs = []
            for order in ([0, 1, 2], [1, 0, 2]):
                acc = codec.accum_init(nb, 1024, deterministic=True)
                for j in order:
                    acc = codec.decode_accumulate(
                        acc, payloads[j], omega[j], block=1024,
                        deterministic=True)
                accs.append(np.asarray(codec.accum_finalize(
                    acc, n, 1024, deterministic=True)))
            np.testing.assert_array_equal(accs[0], accs[1], err_msg=name)

    def test_old_style_trio_signature_stays_compatible(self):
        """A codec subclassed against the PRE-deterministic trio
        signature (no deterministic/fixed_bits kwargs) keeps working on
        every float path: the base exchange forwards the new kwargs only
        when the deterministic mode engages (Codec._det_kwargs)."""
        from repro.codecs.builtin import Int8Codec

        class OldTrio(Int8Codec):
            name = ""  # not registered

            def accum_init(self, nb, block=1024):
                return jnp.zeros((nb, block), jnp.float32)

            def decode_accumulate(self, acc, payload, weight, *,
                                  block=1024, use_pallas=False):
                return acc + weight * self.decode(payload, block)

            def accum_finalize(self, acc, n, block=1024):
                return acc.reshape(-1)[:n]

        old = OldTrio()
        init_kw, fold_kw = old._det_kwargs(False, 16)
        assert init_kw == {} and fold_kw == {}
        pay, _, _ = old.ef_encode(_rand(2048, 5), jnp.zeros((2048,)),
                                  gamma=1.0, block=1024)
        acc = old.accum_init(2, 1024, **init_kw)
        acc = old.decode_accumulate(acc, pay, jnp.float32(0.5),
                                    block=1024, **fold_kw)
        out = old.accum_finalize(acc, 2048, 1024, **fold_kw)
        assert out.shape == (2048,)
        # ...while the deterministic mode demands the new contract
        init_kw, fold_kw = old._det_kwargs(True, 16)
        assert init_kw == {"deterministic": True}
        assert fold_kw == {"deterministic": True, "fixed_bits": 16}

    def test_legacy_float_ring_fold_is_loud_error_on_p3(self):
        """Satellite pin: the order-sensitive float fold is unreachable
        on P >= 3 — explicitly requesting it raises instead of silently
        drifting (the old forced-ring bypass)."""
        codec = _default("int8")
        g, e = _rand(2048, 80), jnp.zeros((2048,))
        om = jnp.full((3,), 1 / 3, jnp.float32)
        with pytest.raises(ValueError, match="deterministic"):
            codec.ef_sync_ring(g, e, om, om[0], gamma=1.0, n_pods=3,
                               n_chunks=2, block=1024,
                               deterministic=False)

    @pytest.mark.parametrize("name", BUILTINS)
    def test_ring_single_pod_equals_one_shot(self, name):
        """ef_sync_ring degenerates to ef_sync off-mesh (and for the
        non-ring codecs FULL/SKIP it IS ef_sync by definition)."""
        codec = _default(name)
        g, e = _rand(2500, 70), _rand(2500, 71) * 0.2
        om = jnp.ones((1,), jnp.float32)
        a1, e1 = codec.ef_sync(g, e, om, om[0], gamma=0.9, n_pods=1,
                               block=1024)
        a2, e2 = codec.ef_sync_ring(g, e, om, om[0], gamma=0.9, n_pods=1,
                                    n_chunks=3, block=1024)
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
        np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))


# ---------------------------------------------------------------------------
# packed wire buffer == analytic accounting
# ---------------------------------------------------------------------------


class TestPackedBytes:
    @pytest.mark.parametrize("n", [1024, 3000, 8192, 100_000])
    @pytest.mark.parametrize("name", ["int8", "int4", "sign", "topk"])
    def test_packed_size_equals_payload_bytes(self, n, name):
        """What pack_payload puts on the all_gather wire must be exactly
        what wire_bytes prices (the analytic == traced contract)."""
        codec = _default(name)
        payload, _, _ = codec.ef_encode(_rand(n, 3), jnp.zeros((n,)),
                                        gamma=1.0, block=1024)
        wire, meta = pack_payload(payload)
        assert wire.size == codec.payload_bytes(n, 1024)
        back = unpack_payload(wire, meta)
        for k in payload:
            np.testing.assert_array_equal(np.asarray(payload[k]),
                                          np.asarray(back[k]))

    def test_bit_pack_roundtrip(self):
        r = np.random.RandomState(0)
        bools = jnp.asarray(r.rand(4, 1024) > 0.5)
        packed = pack_bits(bools)
        assert packed.shape == (4, 128) and packed.dtype == jnp.uint8
        bits = unpack_bits(packed, 1024)
        np.testing.assert_array_equal(np.asarray(bits),
                                      np.asarray(bools).astype(np.uint8))


# ---------------------------------------------------------------------------
# bucketed plan pricing
# ---------------------------------------------------------------------------


class TestPlanPricing:
    def _plan(self, idx, omega=(0.5, 0.5)):
        cfg_levels = (Level("FULL", 1.0, 16), Level("INT8", 1.0, 8),
                      Level("TOPK10", 0.10, 8), Level("SKIP", 0.0, 0))
        return SyncPlan(tuple(idx), cfg_levels, omega, 1)

    def test_same_level_groups_priced_block_aligned(self):
        """Two same-level groups share ONE buffer and one collective, but
        each leaf is block-aligned in the static layout (the price of the
        retrace-free gather/scatter exchange): the bucket is priced at the
        sum of per-leaf block counts, exactly what per-group pricing
        gives — the knapsack's per-group accounting is exact."""
        sizes = [1500, 1500]  # 2 blocks each -> a 4-block bucket
        plan = self._plan([2, 2])
        bucketed = plan_wire_bytes(plan, sizes, 2)
        separate = sum(plan.levels[2].wire_bytes(n, 2) for n in sizes)
        assert bucketed == separate
        assert bucketed == plan.levels[2].wire_bytes(4 * 1024, 2)

    def test_mixed_plan_sums_buckets(self):
        sizes = [2048, 1024, 4096, 512]
        plan = self._plan([0, 1, 2, 3])
        expect = (plan.levels[0].wire_bytes(2048, 2)
                  + plan.levels[1].wire_bytes(1024, 2)
                  + plan.levels[2].wire_bytes(4096, 2))
        assert plan_wire_bytes(plan, sizes, 2) == expect

    def test_single_pod_free(self):
        plan = self._plan([0, 1, 2, 3], omega=(1.0,))
        assert plan_wire_bytes(plan, [1024] * 4, 1) == 0


# ---------------------------------------------------------------------------
# knapsack ladder with the widened rungs
# ---------------------------------------------------------------------------


class TestWidenedLadder:
    def test_default_ladder_resolves(self):
        from repro.configs.base import ACESyncConfig
        from repro.core.scheduler import levels_from_config
        names = {l.codec.name for l in levels_from_config(ACESyncConfig())}
        assert names == {"full", "int8", "int4", "sign", "topk", "skip"}

    def test_knapsack_prunes_dominated_rungs(self):
        """INT4 is cheaper AND higher-value than TOPK25, so a budget that
        can afford INT4 must never pick TOPK25."""
        from repro.configs.base import ACESyncConfig
        from repro.core import knapsack
        from repro.core.scheduler import levels_from_config
        levels = levels_from_config(ACESyncConfig())
        sizes = [10 ** 6] * 4
        full = sum(levels[0].wire_bytes(n, 2) for n in sizes)
        for frac in (0.1, 0.3, 0.6, 1.0):
            choice = knapsack.solve([1.0] * 4, sizes, levels, full * frac, 2)
            assert not any(levels[c].name == "TOPK25_INT8" for c in choice)

    def test_knapsack_value_monotone_in_budget_widened(self):
        from repro.configs.base import ACESyncConfig
        from repro.core import knapsack
        from repro.core.scheduler import levels_from_config
        levels = levels_from_config(ACESyncConfig())
        sizes = [10 ** 6, 5 * 10 ** 5, 10 ** 5]
        imp = [0.9, 0.5, 0.2]
        full = sum(levels[0].wire_bytes(n, 2) for n in sizes)
        prev = -1.0
        for frac in (0.0, 0.05, 0.15, 0.4, 0.8, 1.0):
            choice = knapsack.solve(imp, sizes, levels, full * frac, 2)
            val = sum(knapsack.level_value(levels[c]) * imp[i]
                      for i, c in enumerate(choice))
            assert val >= prev - 1e-9
            prev = val


# ---------------------------------------------------------------------------
# backend dispatch caching
# ---------------------------------------------------------------------------


class TestDispatchCaching:
    @staticmethod
    def _clear(ops):
        ops.interpret_mode.cache_clear()
        ops.default_use_pallas.cache_clear()

    def test_cached_and_env_override(self, monkeypatch):
        from repro.kernels import ops
        try:
            monkeypatch.setenv(ops.FORCE_INTERPRET_ENV, "1")
            self._clear(ops)
            assert ops.interpret_mode() is True
            assert ops.default_use_pallas() is True
            # on the CPU the kernels always interpret; "0" only keeps the
            # sync path on the oracle math
            monkeypatch.setenv(ops.FORCE_INTERPRET_ENV, "0")
            self._clear(ops)
            assert ops.interpret_mode() is True
            assert ops.default_use_pallas() is False
            # cached: flipping the env without a cache clear is invisible
            monkeypatch.setenv(ops.FORCE_INTERPRET_ENV, "1")
            assert ops.default_use_pallas() is False
        finally:
            monkeypatch.delenv(ops.FORCE_INTERPRET_ENV, raising=False)
            self._clear(ops)

    def test_accelerator_never_interprets(self, monkeypatch):
        """Off the CPU the kernels compile, and the force switch is an
        error instead of a silent fallback to the interpreter."""
        from repro.kernels import ops
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        try:
            monkeypatch.delenv(ops.FORCE_INTERPRET_ENV, raising=False)
            self._clear(ops)
            assert ops.interpret_mode() is False
            assert ops.default_use_pallas() is True
            monkeypatch.setenv(ops.FORCE_INTERPRET_ENV, "1")
            self._clear(ops)
            with pytest.raises(RuntimeError, match="CPU-only"):
                ops.interpret_mode()
            with pytest.raises(RuntimeError, match="CPU-only"):
                ops.default_use_pallas()
        finally:
            monkeypatch.undo()
            self._clear(ops)
