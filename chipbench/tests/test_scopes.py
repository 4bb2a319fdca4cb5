"""The phase reduction (``scopes.py``): the rule that maps an
instruction's ``op_name`` to a phase, the HLO proto reader, the
restriction to the step module's events and the per-phase self-time sums,
on synthetic events and on the chip trace in ``testdata/``."""
import gzip
import os
import shutil

import pytest

import scopes
import tracereduce
from conftest import BENCH

TRACE = os.path.join(BENCH, "testdata", "ace-grad.xplane.pb.gz")


@pytest.mark.parametrize("op_name,phase", [
    ("jit(step)/jvp(forward)/dot_general", "forward"),
    ("jit(step)/jvp(forward)/transpose", "forward"),
    ("jit(step)/transpose(jvp(forward))/dot_general", "backward"),
    ("jit(step)/transpose(jvp(forward))/while/body/checkpoint/"
     "rematted_computation/mul", "backward"),
    ("jit(step)/optimizer/sqrt", "optimizer"),
    ("jit(step)/exchange/scatter/optimizer/mul", "optimizer"),
    ("jit(step)/exchange/encode_INT8/jit(quantize_int8_gather)/"
     "quantize_int8_gather/pallas_call", "exchange"),
    ("jit(step)/exchange/importance/reduce_sum", "exchange"),
    ("jit(step)/forward_fn/mul", "unscoped"),
    ("state['params']['embed']", "unscoped"),
    ("", "unscoped"),
])
def test_phase_rule(op_name, phase):
    assert scopes.phase_of(op_name) == phase


@pytest.mark.parametrize("op_name,part", [
    ("jit(s)/exchange/encode_INT8/jit(q)/pallas_call", "encode_INT8"),
    ("jit(s)/exchange/pack/concatenate", "pack"),
    ("jit(s)/exchange/collective/all_gather", "collective"),
    ("jit(s)/exchange/concatenate", "other"),
    ("jit(s)/exchange/packed/mul", "other"),
])
def test_exchange_part(op_name, part):
    assert scopes.exchange_part(op_name) == part


def _ev(name, s, e):
    return (f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p)", s, e)


def test_phase_seconds_sums_self_time_per_phase():
    # a while loop (backward) holding two fusions, one of them an
    # exchange op; then an optimizer op
    evs = [_ev("while.1", 0.0, 10.0), _ev("fusion.1", 1.0, 3.0),
           _ev("fusion.2", 4.0, 9.0), _ev("fusion.3", 10.0, 12.0)]
    names = {"while.1": "jit(s)/transpose(jvp(forward))/while",
             "fusion.1": "jit(s)/transpose(jvp(forward))/while/body/mul",
             "fusion.2": "jit(s)/exchange/encode_INT8/mul",
             "fusion.3": "jit(s)/optimizer/add"}
    per, parts = scopes.phase_seconds(evs, names, 0.0, 12.0)
    assert per == {"backward": pytest.approx(3.0 + 2.0),
                   "exchange": pytest.approx(5.0),
                   "optimizer": pytest.approx(2.0)}
    assert parts == {"encode_INT8": pytest.approx(5.0)}
    # clipped to the window: the while's self time scales with its share
    per, _ = scopes.phase_seconds(evs, names, 0.0, 5.0)
    assert per["backward"] == pytest.approx(3.0 * 0.5 + 2.0)
    assert per["exchange"] == pytest.approx(5.0 * 0.2)
    assert "optimizer" not in per
    # an instruction the module does not name is unscoped
    per, _ = scopes.phase_seconds([_ev("copy.9", 0.0, 1.0)], names, 0.0, 2.0)
    assert per == {"unscoped": pytest.approx(1.0)}


def test_only_the_step_modules_ops_count():
    mods = [("jit_step(1)", 0.0, 10.0), ("jit_small(2)", 10.5, 11.0),
            ("jit_step(1)", 11.0, 21.0)]
    evs = [_ev("a.1", 1.0, 2.0), _ev("b.1", 10.6, 10.9),
           _ev("a.2", 12.0, 20.0), _ev("c.1", 21.5, 22.0)]
    assert scopes.step_module({"/device:TPU:0": mods}, 0.0, 30.0) \
        == "jit_step(1)"
    assert scopes.step_module({"/device:TPU:0": mods}, 10.4, 11.0) \
        == "jit_small(2)"
    assert [e[0] for e in scopes.module_ops(mods, evs, "jit_step(1)")] \
        == [evs[0][0], evs[2][0]]
    tr = {"modules": {"/device:TPU:0": mods}, "ops": {"/device:TPU:0": evs},
          "hlo": {"jit_step(1)": {"a.1": "jit(s)/jvp(forward)/mul",
                                  "a.2": "jit(s)/optimizer/mul"},
                  "jit_small(2)": {"b.1": "jit(t)/jvp(forward)/mul"}}}
    red = scopes.reduce(tr, 0.0, 30.0)
    assert red["module"] == "jit_step(1)"
    assert red["phases"] == {"forward": pytest.approx(1.0),
                             "optimizer": pytest.approx(8.0)}


# -- a tiny protobuf writer for synthetic HLO protos -----------------------

def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _instr(iid, name, op_name="", operands=(), called=()):
    msg = _field(1, name) + _field(35, iid)
    if op_name:
        msg += _field(7, _field(2, op_name))
    for num, ids in ((36, operands), (38, called)):
        if ids:     # packed repeated int64
            msg += _field(num, b"".join(_varint(i) for i in ids))
    return _field(2, msg)


def test_instruction_op_names_fill_in_what_xla_left_unnamed():
    entry = _field(5, 1) \
        + _instr(1, "while.1", "jit(s)/transpose(jvp(forward))/while",
                 called=(2, 3)) \
        + _instr(2, "reshape.1", "jit(s)/exchange/pack/reshape") \
        + _instr(6, "param.1", "state['m']") \
        + _instr(3, "dus_fusion.1", operands=(6, 2)) \
        + _instr(4, "dus_fusion.2", operands=(3,)) \
        + _instr(5, "fusion.7", called=(4,))
    body = _field(5, 2) + _instr(10, "mul.1", "checkpoint/mul") \
        + _instr(11, "copy.1")
    cond = _field(5, 3) + _instr(20, "lt.1", "jit(s)/lt")
    fused = _field(5, 4) + _instr(30, "add.3", "jit(s)/optimizer/add")
    module = _field(1, "jit_s") + b"".join(
        _field(3, c) for c in (entry, body, cond, fused)) + _field(6, 1)
    names = scopes.instruction_op_names(_field(1, module))
    assert names == {
        "while.1": "jit(s)/transpose(jvp(forward))/while",
        # a while body's op_names are relative to the while's
        "mul.1": "jit(s)/transpose(jvp(forward))/while/checkpoint/mul",
        "copy.1": "jit(s)/transpose(jvp(forward))/while",
        "lt.1": "jit(s)/lt",
        "reshape.1": "jit(s)/exchange/pack/reshape",
        "param.1": "state['m']",
        # unnamed: the first operand's in a phase, down a chain
        "dus_fusion.1": "jit(s)/exchange/pack/reshape",
        "dus_fusion.2": "jit(s)/exchange/pack/reshape",
        # unnamed: what it fuses
        "fusion.7": "jit(s)/optimizer/add",
        "add.3": "jit(s)/optimizer/add"}
    assert scopes.phase_of(names["mul.1"]) == "backward"


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("trace") / "ace-grad.xplane.pb")
    with gzip.open(TRACE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return scopes.load(path)


def test_chip_trace_names_every_op_of_its_step(chip_trace):
    """The chip trace (a program without the scopes) carries the step
    module's HLO proto: every op event is named there, no op reads a
    phase, and the step module's ops are all of the device's op time."""
    mods = chip_trace["modules"]["/device:TPU:0"]
    evs = chip_trace["ops"]["/device:TPU:0"]
    lo, hi = mods[0][1], mods[-1][2]
    red = scopes.reduce(chip_trace, lo, hi)
    assert red["module"].startswith("jit__unknown(")
    names = chip_trace["hlo"][red["module"]]
    assert len(names) > 5000
    assert {tracereduce.short_name(h) for h, _, _ in evs} <= set(names)
    assert set(red["phases"]) == {"unscoped"}
    total = sum(own for _, _, _, own in tracereduce.self_times(evs))
    assert red["phases"]["unscoped"] == pytest.approx(total, rel=1e-3)


def _window_ctx(tr, monkeypatch, host_steps=30):
    """A run's context over the chip trace's whole step span, its host
    clock 7 s behind the trace's."""
    spans = {}
    for n, s, e in tr["spans"]:
        spans.setdefault(n, []).append((s - 7.0, e - 7.0))
    mods = tr["modules"]["/device:TPU:0"]

    class Spans:
        rec = spans

    monkeypatch.setattr(scopes, "_CACHE", {})
    monkeypatch.setattr(scopes, "window_trace", lambda t0: "chip")
    monkeypatch.setattr(scopes, "load", lambda path: tr)
    return {"t0": mods[0][1] - 7.0, "t_end": mods[-1][2] - 7.0,
            "host_steps": host_steps, "sync_steps": host_steps // 2,
            "spans": Spans()}


def test_reading_of_a_program_without_scopes_is_nothing(chip_trace,
                                                        monkeypatch):
    assert scopes.reading(_window_ctx(chip_trace, monkeypatch)) is None


def test_reading_partitions_the_step(chip_trace, monkeypatch):
    """The chip trace with scopes laid over its instructions: the phases
    read in ms per step sum to the step module's self time, exchange per
    exchanging step."""
    module = scopes.reduce(chip_trace, 0.0, float("inf"))["module"]
    names = chip_trace["hlo"][module]
    fake = {}
    for i, name in enumerate(sorted(names)):
        fake[name] = ("jit(s)/jvp(forward)/x", "jit(s)/optimizer/x",
                      "jit(s)/exchange/pack/x", "")[i % 4]
    tr = dict(chip_trace, hlo=dict(chip_trace["hlo"], **{module: fake}))
    ctx = _window_ctx(tr, monkeypatch, host_steps=34)
    ms = scopes.reading(ctx)
    evs = chip_trace["ops"]["/device:TPU:0"]
    total = 1e3 * sum(own for _, _, _, own in tracereduce.self_times(evs))
    assert ms["backward"] == 0.0
    for phase in ("forward", "optimizer", "exchange", "unscoped"):
        assert ms[phase] > 0.0
    summed = (ms["forward"] + ms["optimizer"] + ms["unscoped"]) * 34 \
        + ms["exchange"] * 17
    assert summed == pytest.approx(total, rel=1e-3)
