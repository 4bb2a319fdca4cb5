"""The benchmark's yardstick arithmetic: model FLOPs per token, the bytes a
codec rung must move, and the table of device peaks."""
from __future__ import annotations

import json
import math
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
BLOCK = 1024


def param_count(cfg: dict) -> int:
    """Parameters of the dense decoder LM at the configuration's widths
    and depth, counting the vocabulary's own rows (not the embedding's
    padding) once for the tied embedding and head."""
    L, D, F = cfg["n_layers"], cfg["d_model"], cfg["d_ff"]
    HD = cfg["n_heads"] * cfg["head_dim"]
    KD = cfg["n_kv_heads"] * cfg["head_dim"]
    per_layer = D * HD + 2 * D * KD + HD * D + 3 * D * F + 2 * D
    return cfg["vocab_size"] * D + L * per_layer + D


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Training FLOPs per token: 6 N for the weights' forward and backward
    matmuls plus 12 L S (heads x head_dim) for the attention scores and
    values over a causal-free S x S window (recomputation not counted)."""
    attn = 12 * cfg["n_layers"] * seq_len * cfg["n_heads"] * cfg["head_dim"]
    return 6.0 * param_count(cfg) + attn


#: bytes of a rung's payload per element and per 1024-element block
#: (the per-block f32 scale)
_PAYLOAD = {"INT8": (1.0, 4), "INT4": (0.5, 4), "SIGN1": (0.125, 4)}


def n_blocks(n: int) -> int:
    return -(-n // BLOCK)


def payload_bytes(rung: str, n: int) -> int:
    """Bytes of one rung's coded payload for ``n`` elements, blocks
    padded to 1024."""
    per_elem, per_block = _PAYLOAD[rung]
    nb = n_blocks(n)
    return int(nb * BLOCK * per_elem) + per_block * nb


def codec_bytes(rung: str, n: int) -> int:
    """HBM bytes the algorithm of one error-fed encode must move for ``n``
    elements: read the f32 gradient and residual, write the f32 residual
    and the payload."""
    padded = n_blocks(n) * BLOCK
    return 3 * 4 * padded + payload_bytes(rung, n)


def codec_ops(rung: str, n: int) -> int:
    """Elementwise operations of the same encode: the error-feedback
    multiply-add, the absmax, the divide, round, clip and multiply back,
    and the residual subtract (about 8 per element)."""
    return 8 * n_blocks(n) * BLOCK


#: the codec kernels' function names, as the trace names their calls:
#: encode (error feedback + compress) of a rung, and decode-accumulate
ENCODE_KERNELS = {"quantize_int8": "INT8", "ef_int4": "INT4",
                  "ef_sign": "SIGN1"}
DECODE_KERNELS = ("dequant", "sign_vote_accum")
_ARRAY = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64)"
                    r"\[([0-9,]*)\]")
_ITEM = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
         "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
         "f64": 8}


def _arrays(text: str):
    """[(dtype, dims)] of the array types written in an HLO fragment."""
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _ARRAY.findall(text)]


def _nbytes(arrays) -> int:
    return sum(_ITEM[dt] * math.prod(dims) for dt, dims in arrays)


def kernel_work(hlo: str):
    """(bytes, operations) the algorithm of one codec kernel call needs,
    from the call's HLO instruction as the trace names it; None for a
    kernel this table does not know.

    An encode call's elements are its gathered rows (the 1-D int32 perm
    operand) or, unfused, the rows of its residual output, times 1024;
    its bytes are ``codec_bytes`` of its rung.  A decode-accumulate call
    reads each operand and writes each result once."""
    name, rest = hlo.split(" = ", 1)
    base = re.sub(r"\.\d+$", "", name.lstrip("%"))
    results = _arrays(rest.split(" custom-call(", 1)[0])
    operands = _arrays(rest.split(" custom-call(", 1)[-1]
                       .split("), custom_call_target", 1)[0])
    for prefix, rung in ENCODE_KERNELS.items():
        if base.startswith(prefix):
            perm = [d for dt, d in operands if dt == "s32" and len(d) == 1]
            if "gather" in base and perm:
                rows = perm[0][0]
            else:
                rows = max((d[0] for dt, d in results if dt == "f32"),
                           default=0)
            n = rows * BLOCK
            return codec_bytes(rung, n), codec_ops(rung, n)
    if base.startswith(DECODE_KERNELS):
        elems = max((math.prod(d) for _, d in results), default=0)
        return _nbytes(operands) + _nbytes(results), 4 * elems
    return None


def load_peaks() -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        return json.load(f)


def peaks_for(device_kind: str) -> dict:
    """The peak table's row for a device kind; an unknown kind is an
    error, never a default."""
    table = load_peaks()["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"chipbench/peaks.json")
    return table[device_kind]
