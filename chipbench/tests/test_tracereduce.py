"""The reduction from trace events to the per-layer numbers, on made-up
events whose answers are known."""
import pytest

import tracereduce as T


def test_union_merges_overlaps_and_keeps_gaps():
    assert T.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [[0, 2.5],
                                                               [3, 4]]


def test_clip_to_window():
    assert T.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]


FUSION = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
ALL_REDUCE = "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %fusion.1)"
KERNEL = ('%ef_int8.2 = (s8[8,1024]{1,0}) custom-call(f32[8,1024]{1,0} %a),'
          ' custom_call_target="tpu_custom_call"')
PERMUTE = ("%collective-permute-start.4 = (f32[8]{0}, f32[8]{0}) "
           "collective-permute-start(f32[8]{0} %q)")
LOOP = "%while.7 = (s32[]) while((s32[]) %t), condition=%c, body=%b"


def devices_two_chips():
    return {
        "/device:TPU:0": [(FUSION, 0.0, 1.0), (ALL_REDUCE, 1.0, 1.5),
                          (KERNEL, 2.0, 2.5)],
        "/device:TPU:1": [(FUSION, 0.0, 2.0), (PERMUTE, 2.0, 2.2)],
    }


def test_opcodes_and_collectives():
    assert T.short_name(FUSION) == "fusion.1"
    assert [T.opcode(h) for h in (FUSION, ALL_REDUCE, KERNEL, PERMUTE,
                                  LOOP)] == [
        "fusion", "all-reduce", "custom-call", "collective-permute-start",
        "while"]
    # an operand named after a collective does not make a fusion one
    assert not T.is_collective(FUSION.replace("%p", "%all-gather.2"))
    assert T.is_collective(ALL_REDUCE) and T.is_collective(PERMUTE)


def test_busy_is_the_union_averaged_over_chips():
    red = T.reduce_events(devices_two_chips(), [], 0.0, 4.0)
    # chip 0 busy 2.0 s, chip 1 busy 2.2 s
    assert red["busy_s"] == pytest.approx(2.1)
    assert red["window_s"] == 4.0
    assert red["per_op"]["fusion.1"] == pytest.approx(1.5)
    assert red["collective_s"] == pytest.approx((0.5 + 0.2) / 2)
    assert red["pallas"]["ef_int8.2"]["s"] == pytest.approx(0.25)
    assert red["pallas"]["ef_int8.2"]["n"] == pytest.approx(0.5)
    assert red["top_ops"][0][0] == "fusion.1 = f32[8] fusion kLoop"


def test_describe_keeps_types_opcode_and_kind():
    assert T.describe(KERNEL) == (
        "ef_int8.2 = (s8[8,1024]) custom-call")
    assert T.describe(PERMUTE) == ("collective-permute-start.4 = "
                                   "(f32[8], f32[8]) collective-permute-start")


def test_nested_events_count_their_self_time():
    devices = {"/device:TPU:0": [(LOOP, 0.0, 3.0), (FUSION, 0.5, 1.5),
                                 (ALL_REDUCE, 2.0, 2.5)]}
    red = T.reduce_events(devices, [], 0.0, 3.0)
    assert red["busy_s"] == pytest.approx(3.0)
    assert red["per_op"]["while.7"] == pytest.approx(1.5)
    assert sum(red["per_op"].values()) == pytest.approx(3.0)


def test_events_outside_the_window_are_cut():
    red = T.reduce_events(devices_two_chips(), [], 0.5, 1.25)
    assert red["busy_s"] == pytest.approx(0.75)
    assert red["per_op"]["all-reduce.3"] == pytest.approx(0.125)


def test_idle_gaps_take_what_the_host_was_doing():
    spans = [("data", 1.5, 1.9), ("dispatch", 1.9, 2.0),
             ("replan", 2.6, 3.0)]
    host = [("np.asarray(jax.Array)", 2.5, 4.0)]
    red = T.reduce_events(devices_two_chips(), spans, 0.0, 4.0, host)
    # chip 0's gaps: 1.5-2.0 (the data span covers 0.4 of 0.5 s) and
    # 2.5-4.0 (the replan span covers 0.4 of 1.5 s: the host event wins)
    assert red["idle_gaps"] == [["np.asarray(jax.Array)", pytest.approx(1.5)],
                                ["data", pytest.approx(0.5)]]


def test_clock_offset_matches_spans_from_the_end():
    trace = [("data", 101.0, 101.1), ("data", 102.0, 102.1),
             ("dispatch", 102.2, 102.3)]
    host = [("data", 0.0, 0.1), ("data", 1.0, 1.1), ("data", 2.0, 2.1),
            ("dispatch", 2.2, 2.3)]
    assert T.clock_offset(trace, host) == pytest.approx(100.0)


def test_clock_offset_needs_a_span():
    with pytest.raises(ValueError):
        T.clock_offset([], [("data", 0.0, 1.0)])


# -- a trace recorded on the chip -------------------------------------------
# ``testdata/ace-grad.xplane.pb.gz``: the window of a traced run of
# p350m.ace-grad on one TPU v5e (16 steps of acesync H = 1, two Pallas
# INT8 encode calls a step, one 1.2 s idle gap while the host waited on a
# metrics fetch).

import gzip
import os

import arith
from conftest import BENCH


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    src = os.path.join(BENCH, "testdata", "ace-grad.xplane.pb.gz")
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(src, "rb") as f:
        path.write_bytes(f.read())
    tr = T.load(str(path))
    evs = tr["devices"]["/device:TPU:0"]
    lo, hi = min(e[1] for e in evs), max(e[2] for e in evs)
    return tr, T.reduce_events(tr["devices"], tr["spans"], lo, hi,
                               tr["host"])


def test_chip_trace_self_times_add_up_to_busy(chip_trace):
    tr, red = chip_trace
    assert list(tr["devices"]) == ["/device:TPU:0"]
    assert red["busy_s"] == pytest.approx(4.183955, rel=1e-5)
    assert sum(red["per_op"].values()) == pytest.approx(red["busy_s"])
    assert red["collective_s"] == 0.0
    # the while loops' own time is their body's, not on top of it
    assert red["per_op"]["while.150"] < 0.01


def test_chip_trace_pallas_calls_and_their_work(chip_trace):
    _, red = chip_trace
    assert sorted(red["pallas"]) == ["quantize_int8_gather.2",
                                     "quantize_int8_gather.3"]
    rows = {"quantize_int8_gather.2": 49960, "quantize_int8_gather.3": 101405}
    for name, k in red["pallas"].items():
        assert k["n"] == 16
        nbytes, ops = arith.kernel_work(k["hlo"])
        assert nbytes == arith.codec_bytes("INT8", rows[name] * 1024)
        assert ops == arith.codec_ops("INT8", rows[name] * 1024)


def test_chip_trace_per_layer_readers(chip_trace):
    import loader
    _, red = chip_trace
    ctx = {"trace": red, "sync_steps": 16,
           "peaks": arith.peaks_for("TPU v5 lite")}
    ms = loader.reader("exchange_kernel_ms_per_step")(ctx)
    assert ms == pytest.approx(1e3 * (0.458206 + 0.228893) / 16, rel=1e-4)
    share = loader.reader("kernel_roofline")(ctx)
    assert 0 < share < 100
    assert loader.reader("idle_share")(ctx) == pytest.approx(
        100 * (1 - 4.183955 / red["window_s"]), rel=1e-4)


def test_chip_trace_breakdown_names_the_kernels(chip_trace):
    _, red = chip_trace
    assert red["top_ops"][0][0] == (
        "quantize_int8_gather.3 = (s8[101405,1,1024], f32[101405,1,1], "
        "f32[101405,1,1024]) custom-call")


def test_chip_trace_gap_labelled_by_the_host(chip_trace):
    _, red = chip_trace
    name, seconds = red["idle_gaps"][0]
    assert seconds == pytest.approx(1.2047, abs=1e-3)
    assert name == "np.asarray(jax.Array)"


def test_non_codec_pallas_call_is_left_out(chip_trace):
    """A Pallas call that is no codec kernel (an attention kernel, say)
    is charged to neither codec reader."""
    import loader
    _, red = chip_trace
    other = {"hlo": "%flash_attention.4 = bf16[8,16,512,64]{3,2,1,0} "
                    "custom-call(bf16[8,16,512,64]{3,2,1,0} %q), "
                    "custom_call_target=\"tpu_custom_call\"",
             "s": 5.0, "n": 16}
    ctx = {"trace": red, "sync_steps": 16,
           "peaks": arith.peaks_for("TPU v5 lite")}
    with_other = dict(ctx, trace=dict(
        red, pallas=dict(red["pallas"], **{"flash_attention.4": other})))
    for name in ("exchange_kernel_ms_per_step", "kernel_roofline"):
        read = loader.reader(name)
        assert read(with_other) == pytest.approx(read(ctx), rel=1e-12)
