"""The plain reference: ACE-Sync's training step written out in
straightforward ``jax.numpy`` and float32, from the configuration's
equations alone.  It imports nothing of the program under test.

What it computes, for a configuration file and a traffic file:

* the dense decoder LM of the configuration (tied embeddings, RMSNorm with
  a ``1 + w`` gain, rotary attention over half-split head dims, SwiGLU
  MLP), its mean token cross-entropy over the embedding's rows, and the
  gradient;
* the gradient clipped to a global norm;
* the exchange of one replica on every step, per parameter leaf on the
  rung the plan assigns it: error feedback ``ef = g + gamma * e``, the
  rung's blockwise code (FULL rounds to bfloat16, INT8 / INT4 round to
  ``absmax / 127`` or ``/ 7`` per 1024-element block), the decoded value
  weighted by omega as the aggregate and ``ef - decoded`` as the next
  residual;
* AdamW with a linear-warmup cosine schedule and bias correction.

``rnd`` is applied to every matmul operand in the forward pass.  The
reference passes the identity; the control passes a rounding to a lower
precision (float8 with a straight-through gradient).
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1024
HIGHEST = jax.lax.Precision.HIGHEST


def identity(x):
    return x


def fp8_round(x):
    """Round to float8 e4m3 with a per-tensor absmax scale, as fp8
    training does, in the forward pass; the gradient passes through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    y = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(y - x)


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------


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


def nll_sum(params, tokens, labels, cfg: dict, rnd: Callable = identity):
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


# ---------------------------------------------------------------------------
# exchange
# ---------------------------------------------------------------------------


def _blocks(x):
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % BLOCK
    return jnp.pad(flat, (0, pad)).reshape(-1, BLOCK)


def rung_code(rung: str, ef):
    """Decoded value of one leaf's error-fed gradient on ``rung``."""
    if rung == "FULL":
        return ef.astype(jnp.bfloat16).astype(jnp.float32)
    levels = {"INT8": 127.0, "INT4": 7.0}
    if rung not in levels:
        raise ValueError(f"rung {rung!r} has no code in the reference")
    top = levels[rung]
    b = _blocks(ef)
    scale = jnp.maximum(jnp.max(jnp.abs(b), axis=1, keepdims=True) / top,
                        1e-30)
    q = jnp.clip(jnp.round(b / scale), -top, top)
    return (q * scale).reshape(-1)[:ef.size].reshape(ef.shape)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def learning_rate(step: int, opt: dict) -> float:
    s = float(step)
    warm = min(s / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((s - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    cos = 0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * cos


def adam_scalars(step: int, opt: dict):
    """(lr, 1 - beta1^t, 1 - beta2^t) of step ``step`` (0-based)."""
    t = step + 1
    return (np.float32(learning_rate(step, opt)),
            np.float32(1 - opt["beta1"] ** t), np.float32(1 - opt["beta2"] ** t))


def adamw(p, g, m, v, lr, bc1, bc2, *, beta1, beta2, weight_decay):
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * g * g
    p = p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + 1e-8) + weight_decay * p)
    return p, m, v


# ---------------------------------------------------------------------------
# the training run
# ---------------------------------------------------------------------------


def _norm(x) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(x))))


class Reference:
    """Steps one replica of the configuration from seeded weights.

    ``rungs`` maps each leaf (in flattening order) to its rung name and
    ``omega`` weights the replica's decoded gradient.  The loss is the
    mean over the batch, its gradient summed over blocks of
    ``ROWS_PER_BLOCK`` rows so that a large batch fits.

    ``fault`` plants one of the faults the check must catch, for the
    control test: ``"half_batch"`` (the loss over the first half of the
    rows)."""

    ROWS_PER_BLOCK = 8

    def __init__(self, cfg: dict, opt: dict, *, rungs: Sequence[str],
                 omega: Sequence[float], device=None,
                 rnd: Callable = identity, fault: Optional[str] = None):
        self.cfg, self.opt = cfg, opt
        self.rungs = list(rungs)
        self.omega = float(omega[0])
        self.device = device or jax.devices()[0]
        self.fault = fault
        grad = jax.value_and_grad(lambda p, t, l: nll_sum(p, t, l, cfg, rnd))

        def accumulate(acc, params, tok, lab):
            loss, g = grad(params, tok, lab)
            return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g))
        self._accumulate = jax.jit(accumulate, donate_argnums=(0,))
        self._init = jax.jit(lambda k: init_params(k, cfg))

        def apply(p, g, m, v, e, scale, lr, bc1, bc2, *, rung):
            ef = g * scale + opt["gamma"] * e
            d = rung_code(rung, ef)
            e, g = ef - d, d * self.omega
            p, m, v = adamw(p, g, m, v, lr, bc1, bc2, beta1=opt["beta1"],
                            beta2=opt["beta2"],
                            weight_decay=opt["weight_decay"])
            return p, m, v, e, jnp.sqrt(jnp.sum(jnp.square(g)))
        self._apply = jax.jit(apply, static_argnames=("rung",),
                              donate_argnums=(0, 2, 3, 4))
        self._sq = jax.jit(lambda t: sum(jnp.sum(jnp.square(x))
                                         for x in jax.tree.leaves(t)))
        self._change = jax.jit(lambda p, k: [
            jnp.sqrt(jnp.sum(jnp.square(a - b)))
            for a, b in zip(jax.tree.leaves(p),
                            jax.tree.leaves(init_params(k, cfg)))])

    def _grads(self, params, tok, lab):
        """(mean loss, gradient of the mean loss) over all rows."""
        if self.fault == "half_batch":
            tok, lab = tok[:len(tok) // 2], lab[:len(lab) // 2]
        acc = (jnp.zeros((), jnp.float32),
               jax.tree.map(jnp.zeros_like, params))
        acc = jax.device_put(acc, self.device)
        for r in range(0, len(tok), self.ROWS_PER_BLOCK):
            rows = slice(r, r + self.ROWS_PER_BLOCK)
            acc = self._accumulate(
                acc, params, jax.device_put(jnp.asarray(tok[rows]),
                                            self.device),
                jax.device_put(jnp.asarray(lab[rows]), self.device))
        n = float(tok.size)
        return float(acc[0]) / n, acc[1], n

    def run(self, key, batches: List, n_steps: int):
        """``batches[s]``: the (tokens, labels) numpy rows of host step s;
        ``key`` the weights' PRNG key.  Returns per-step losses and
        pre-clip gradient norms, and per leaf the norm of the first
        gradient the optimizer received and of the parameters' change
        over ``n_steps``."""
        key = jax.device_put(key, self.device)
        params = self._init(key)
        tdef = jax.tree.structure(params)
        zeros = lambda: [jnp.zeros_like(x) for x in jax.tree.leaves(params)]
        m, v = zeros(), zeros()
        err = zeros()
        losses, gnorms, first = [], [], None
        for s in range(n_steps):
            tok, lab = batches[s]
            loss, grads, n = self._grads(params, tok, lab)
            # the summed gradient's norm, scaled to the mean's
            norm = math.sqrt(float(self._sq(grads))) / n
            losses.append(loss)
            gnorms.append(norm)
            scale = np.float32(min(1.0, self.opt["grad_clip"]
                                   / max(norm, 1e-12)) / n)
            scalars = adam_scalars(s, self.opt)
            p, g = jax.tree.leaves(params), jax.tree.leaves(grads)
            del params, grads
            applied = []
            for i in range(len(p)):
                p[i], m[i], v[i], err[i], gn = self._apply(
                    p[i], g[i], m[i], v[i], err[i], scale, *scalars,
                    rung=self.rungs[i])
                g[i] = None
                applied.append(gn)
            if first is None:
                first = [float(x) for x in applied]
            params = jax.tree.unflatten(tdef, p)
        change = [float(x) for x in self._change(params, key)]
        return {"losses": losses, "grad_norms": gnorms, "first": first,
                "change": change}
