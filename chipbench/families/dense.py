"""The ``dense`` family: the decoder LM of a configuration file, written
out in straightforward ``jax.numpy`` and float32 from its equations alone
(tied embeddings, RMSNorm with a ``1 + w`` gain, rotary attention over
half-split head dims, SwiGLU MLP), and the yardstick's parameter and FLOP
counts.  It imports nothing of the program under test.

A family module gives the harness (``loader.family``):

* ``param_shapes(cfg)``: the leaf shapes, in the program's tree;
* ``init_params(key, cfg)``: seeded float32 weights of those shapes;
* ``nll_sum(params, tokens, labels, cfg, rnd)``: the summed next-token
  cross-entropy of a block of rows, ``rnd`` applied to every matmul
  operand;
* ``param_count(cfg)`` and ``flops_per_token(cfg, seq_len)``.
"""
from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def embedding_rows(cfg: dict) -> int:
    return int(cfg["embedding_rows"])


def param_shapes(cfg: dict) -> Dict:
    """Leaf shapes of the LM, layers stacked on a leading axis."""
    L, D, F = cfg["n_layers"], cfg["d_model"], cfg["d_ff"]
    HD = cfg["n_heads"] * cfg["head_dim"]
    KD = cfg["n_kv_heads"] * cfg["head_dim"]
    return {
        "embed": (embedding_rows(cfg), D),
        "final_norm": (D,),
        "blocks": {"slot0": {
            "attn": {"wq": (L, D, HD), "wk": (L, D, KD), "wv": (L, D, KD),
                     "wo": (L, HD, D)},
            "ffn": {"w_gate": (L, D, F), "w_up": (L, D, F),
                    "w_down": (L, F, D)},
            "ln1": (L, D), "ln2": (L, D)}},
    }


def init_params(key, cfg: dict):
    """Seeded weights: matrices normal / sqrt(fan_in), the embedding
    normal * 0.02, norm gains zero.  Traced once, in float32."""
    shapes = param_shapes(cfg)
    paths = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))[0]
    keys = jax.random.split(key, len(paths))
    out = []
    for k, (path, shape) in zip(keys, paths):
        name = path[-1].key
        if name == "embed":
            out.append(jax.random.normal(k, shape, jnp.float32) * 0.02)
        elif len(shape) == 3:
            out.append(jax.random.normal(k, shape, jnp.float32)
                       / math.sqrt(shape[-2]))
        else:
            out.append(jnp.zeros(shape, jnp.float32))     # norm gains
    tdef = jax.tree.structure(shapes, is_leaf=lambda s: isinstance(s, tuple))
    return jax.tree.unflatten(tdef, out)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    S, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs   # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def nll_sum(params, tokens, labels, cfg: dict, rnd: Callable):
    """Summed next-token cross-entropy of a block of rows."""
    H, KV, Dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_eps"], cfg["rope_theta"]

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)

    emb = params["embed"]
    x = emb[tokens]
    B, S, _ = x.shape
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        h = _rms(x, p["ln1"], eps)
        q = _rope(mm(h, p["attn"]["wq"]).reshape(B, S, H, Dh), theta)
        k = _rope(mm(h, p["attn"]["wk"]).reshape(B, S, KV, Dh), theta)
        v = mm(h, p["attn"]["wv"]).reshape(B, S, KV, Dh)
        if H != KV:
            k = jnp.repeat(k, H // KV, axis=2)
            v = jnp.repeat(v, H // KV, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", rnd(q), rnd(k),
                       precision=HIGHEST) / math.sqrt(Dh)
        s = jnp.where(causal, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", rnd(a), rnd(v), precision=HIGHEST)
        x = x + mm(o.reshape(B, S, H * Dh), p["attn"]["wo"])
        h = _rms(x, p["ln2"], eps)
        f = jax.nn.silu(mm(h, p["ffn"]["w_gate"])) * mm(h, p["ffn"]["w_up"])
        return x + mm(f, p["ffn"]["w_down"]), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["blocks"]["slot0"])
    x = _rms(x, params["final_norm"], eps)

    def chunk_nll(xc, lc):
        logits = mm(xc, emb.T)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - gold)

    # one batch row at a time keeps the (S, rows) logits small
    tot = jax.lax.map(lambda xl: jax.checkpoint(chunk_nll)(*xl),
                      (x, labels))
    return jnp.sum(tot)


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
