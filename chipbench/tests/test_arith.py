"""The yardstick arithmetic against the program's own shapes."""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import arith
import run
from conftest import BENCH, ROOT

CONFIGS = tuple(c["name"] for c in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["configs"])


def load_cfg(name, rehearsal=False):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    return dict(cfg, **cfg["rehearsal"]) if rehearsal else cfg


def program_model(cfg):
    from repro.models.registry import build_model
    return build_model(run.model_config(cfg))


@pytest.mark.parametrize("rehearsal", [False, True])
@pytest.mark.parametrize("name", CONFIGS)
def test_param_count_matches_program_specs(name, rehearsal):
    """N, as the configuration's family counts it, is the program's
    parameter count less the embedding's padding rows, which no token
    ever reads: at the cell's sizes and at the rehearsal's."""
    cfg = load_cfg(name, rehearsal)
    specs = program_model(cfg).param_specs()
    total = sum(math.prod(s.shape) for s in jax.tree.leaves(specs))
    pad_rows = cfg["embedding_rows"] - cfg["vocab_size"]
    assert arith.param_count(cfg) == total - pad_rows * cfg["d_model"]


@pytest.mark.parametrize("name,layers,params,gflop", [
    ("paper-350m", 17, (336.5e6, 337.0e6), (2.12e9, 2.135e9))])
def test_flops_per_token_of_the_cells(name, layers, params, gflop):
    """6 N + 12 L S (heads x head_dim) at seq 512: 336.8 M parameters
    and about 2.13 GFLOP per token at 17 layers."""
    cfg = load_cfg(name)
    n = arith.param_count(cfg)
    assert params[0] < n < params[1]
    f = arith.flops_per_token(cfg, 512)
    assert f == 6 * n + 12 * layers * 512 * 16 * 64
    assert gflop[0] < f < gflop[1]


@pytest.mark.parametrize("rung,codec_name", [("INT8", "int8"),
                                             ("INT4", "int4"),
                                             ("SIGN1", "sign")])
@pytest.mark.parametrize("n", [1024, 5000, 3 * 1024 * 8 + 17])
def test_payload_bytes_match_codec_payload(rung, codec_name, n):
    """The payload the algorithm writes is what the codec's encode
    returns, byte for byte."""
    from repro.codecs import build_codec
    codec = build_codec(codec_name)
    x = jax.random.normal(jax.random.PRNGKey(n), (n,), jnp.float32)
    payload, _, _ = codec.ef_encode(x, jnp.zeros_like(x), gamma=1.0)
    got = sum(int(np.asarray(v).nbytes) for v in jax.tree.leaves(payload))
    assert got == arith.payload_bytes(rung, n)
    assert got == codec.payload_bytes(n)


@pytest.mark.parametrize("rung", ["INT8", "INT4", "SIGN1"])
def test_codec_bytes_read_grad_and_residual_write_residual_and_payload(rung):
    n = 10 * 1024 + 3
    padded = 11 * 1024
    assert arith.codec_bytes(rung, n) == (
        4 * padded * 3 + arith.payload_bytes(rung, n))
    assert arith.codec_ops(rung, n) == 8 * padded


def test_peaks_known_device():
    row = arith.peaks_for("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in arith.load_peaks()["source"]


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5e", ""])
def test_peaks_refuse_unknown_device(kind):
    with pytest.raises(KeyError):
        arith.peaks_for(kind)
