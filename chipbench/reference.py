"""The plain reference: ACE-Sync's training step written out in
straightforward ``jax.numpy`` and float32, from the configuration's
equations alone.  It imports nothing of the program under test.

The model is the configuration's family module (``families/<family>.py``:
its weights, its summed cross-entropy and their gradient); what this file
adds does not depend on the model.  For a configuration file, a traffic
file and the family module it computes, for each of ``pods`` replicas:

* the gradient of the replica's own rows, clipped to a global norm;
* with ``sync_interval`` 1 (one replica only), the exchange on every
  step, per parameter leaf on the rung the plan assigns it: error feedback
  ``ef = g + gamma * e``, the rung's blockwise code (FULL rounds to
  bfloat16, INT8 / INT4 round to ``absmax / 127`` or ``/ 7`` per
  1024-element block), the decoded value weighted by omega as the
  aggregate and ``ef - decoded`` as the next residual;
* with ``sync_interval`` H > 1, a local step on every host step (each
  replica its own rows, clip and AdamW state) and on every H-th the delta
  exchange: ``ef_p = (theta_p - anchor) + gamma * e_p`` coded per leaf as
  above, the omega-weighted sum over replicas folded as the program folds
  it (FULL: a bfloat16 sum of bfloat16 terms; the other rungs, with three
  replicas or more: each term rounded to ``accum_bits`` fractional bits of
  int32 fixed point and summed exactly), every replica set to
  ``anchor + agg`` and the anchor reset;
* AdamW with a linear-warmup cosine schedule and bias correction.

``rnd`` is applied to every matmul operand in the forward pass.  The
reference passes the identity; the control passes a rounding to a lower
precision (float8 with a straight-through gradient).
"""
from __future__ import annotations

import math
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1024
#: the largest f32 that casts to int32 without overflow (2^31 - 128)
INT32_SAT = 2147483520.0


def identity(x):
    return x


def fp8_round(x):
    """Round to float8 e4m3 with a per-tensor absmax scale, as fp8
    training does, in the forward pass; the gradient passes through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    y = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(y - x)


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


def fixed_point(x, bits: int):
    """``x`` as int32 fixed point with ``bits`` fractional bits:
    rounded to nearest even, saturating at the int32 range."""
    s = jnp.round(x * jnp.float32(2.0 ** bits))
    return jnp.clip(s, -INT32_SAT, INT32_SAT).astype(jnp.int32)


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


class Reference:
    """Steps ``pods`` replicas of the configuration from seeded weights.

    ``family`` is the configuration's family module; ``rungs`` maps each
    leaf (in flattening order) to its rung name and ``omega`` weights each
    replica's decoded term.  A replica's loss is the mean over its rows,
    its gradient summed over blocks of ``ROWS_PER_BLOCK`` rows so that a
    large batch fits.  Replica ``p`` lives on ``devices[p]`` (round robin
    over the devices given), so that the replicas' steps run side by side
    and each holds one replica's state.

    ``fault`` plants one of the faults the check must catch, for the
    control test: ``"half_batch"`` (each replica's loss over the first
    half of its rows), ``"drop_pod"`` (the last replica's term left out of
    every delta exchange), ``"no_exchange"`` (every delta exchange left
    out, each replica keeping its own parameters)."""

    ROWS_PER_BLOCK = 8

    def __init__(self, cfg: dict, opt: dict, family, *,
                 rungs: Sequence[str], omega: Sequence[float],
                 pods: int = 1, sync_interval: int = 1, devices=None,
                 rnd: Callable = identity, fault: Optional[str] = None):
        if sync_interval == 1 and pods != 1:
            raise ValueError("a gradient exchange on every step is "
                             "followed for one replica only")
        self.cfg, self.opt = cfg, opt
        self.rungs = list(rungs)
        self.omega = [float(w) for w in omega]
        self.pods, self.H = pods, sync_interval
        devices = list(devices or jax.devices())
        self.devices = [devices[p % len(devices)] for p in range(pods)]
        self.fault = fault
        grad = jax.value_and_grad(
            lambda p, t, l: family.nll_sum(p, t, l, cfg, rnd))

        def accumulate(acc, params, tok, lab):
            loss, g = grad(params, tok, lab)
            return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g))
        self._accumulate = jax.jit(accumulate, donate_argnums=(0,))
        self._init = jax.jit(lambda k: family.init_params(k, cfg))
        adam = dict(beta1=opt["beta1"], beta2=opt["beta2"],
                    weight_decay=opt["weight_decay"])

        def apply(p, g, m, v, e, scale, lr, bc1, bc2, *, rung):
            ef = g * scale + opt["gamma"] * e
            d = rung_code(rung, ef)
            e, g = ef - d, d * self.omega[0]
            p, m, v = adamw(p, g, m, v, lr, bc1, bc2, **adam)
            return p, m, v, e, jnp.sqrt(jnp.sum(jnp.square(g)))
        self._apply = jax.jit(apply, static_argnames=("rung",),
                              donate_argnums=(0, 2, 3, 4))

        def local(p, g, m, v, scale, lr, bc1, bc2):
            g = g * scale
            p, m, v = adamw(p, g, m, v, lr, bc1, bc2, **adam)
            return p, m, v, jnp.sqrt(jnp.sum(jnp.square(g)))
        self._local = jax.jit(local, donate_argnums=(0, 2, 3))

        def encode(p, a, e, *, rung):
            ef = (p - a) + opt["gamma"] * e
            d = rung_code(rung, ef)
            return d, ef - d
        self._encode = jax.jit(encode, static_argnames=("rung",),
                               donate_argnums=(2,))
        bits = opt["accum_bits"]

        def term(d, w, *, kind):
            if kind == "bf16":
                return (d * w).astype(jnp.bfloat16)
            if kind == "fixed":
                return fixed_point(w * d, bits)
            return w * d
        self._term = jax.jit(term, static_argnames=("kind",))

        def fold(terms, *, kind):
            if kind == "bf16":
                tot = sum(t.astype(jnp.float32) for t in terms)
                return tot.astype(jnp.bfloat16).astype(jnp.float32)
            if kind == "fixed":
                tot = sum(terms[1:], terms[0])
                return tot.astype(jnp.float32) * jnp.float32(2.0 ** -bits)
            return sum(terms[1:], terms[0])
        self._fold = jax.jit(fold, static_argnames=("kind",))
        self._add = jax.jit(jnp.add)
        self._norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(x)))
                                          for x in xs])
        self._sq = jax.jit(lambda t: sum(jnp.sum(jnp.square(x))
                                         for x in jax.tree.leaves(t)))
        self._change = jax.jit(lambda p, k: [
            jnp.sqrt(jnp.sum(jnp.square(a - b)))
            for a, b in zip(jax.tree.leaves(p),
                            jax.tree.leaves(family.init_params(k, cfg)))])

    def _grads(self, params, tok, lab, device):
        """(summed loss, summed gradient, rows x tokens) of one replica's
        rows, dispatched on ``device`` and not waited for."""
        if self.fault == "half_batch":
            tok, lab = tok[:len(tok) // 2], lab[:len(lab) // 2]
        acc = (jnp.zeros((), jnp.float32),
               jax.tree.map(jnp.zeros_like, params))
        acc = jax.device_put(acc, device)
        for r in range(0, len(tok), self.ROWS_PER_BLOCK):
            rows = slice(r, r + self.ROWS_PER_BLOCK)
            acc = self._accumulate(
                acc, params, jax.device_put(jnp.asarray(tok[rows]), device),
                jax.device_put(jnp.asarray(lab[rows]), device))
        return acc[0], acc[1], float(tok.size)

    def _fold_kind(self, rung: str) -> str:
        """How the program folds a rung's terms over the replicas: one
        replica takes its own decoded value; FULL is a bfloat16 sum; the
        other rungs fold in int32 fixed point from three replicas on, and
        two in float (a sum of two terms is the same in either order)."""
        if self.pods == 1:
            return "float"
        if rung == "FULL":
            return "bf16"
        return "fixed" if self.pods >= 3 else "float"

    def _exchange(self, params, anchor, err):
        """The delta exchange, leaf by leaf: every replica's error-fed
        delta coded on its rung, the omega-weighted terms folded on the
        first replica's device, every replica and the anchor set to
        ``anchor + agg``."""
        devs, P = self.devices, self.pods
        # one replica takes its own decoded value whatever the plan's omega
        omega = self.omega if P > 1 else [1.0]
        keep = range(P - 1) if self.fault == "drop_pod" else range(P)
        for i, rung in enumerate(self.rungs):
            kind = self._fold_kind(rung)
            terms = []
            for p in range(P):
                d, err[p][i] = self._encode(params[p][i], anchor[p][i],
                                            err[p][i], rung=rung)
                if p in keep:
                    terms.append(jax.device_put(
                        self._term(d, np.float32(omega[p]), kind=kind),
                        devs[0]))
            agg = self._fold(terms, kind=kind)
            for p in range(P):
                a = jax.device_put(agg, devs[p])
                # two buffers: the replica's step donates its parameters
                params[p][i] = self._add(anchor[p][i], a)
                anchor[p][i] = self._add(anchor[p][i], a)

    def run(self, key, batches: List, n_steps: int):
        """``batches[s]``: the (tokens, labels) numpy rows of host step s,
        replica ``p`` taking the ``p``-th equal share of the rows; ``key``
        the weights' PRNG key.  Returns per-step losses and pre-clip
        gradient norms (each the mean over replicas), and per leaf of
        replica 0 the norm of the first gradient the optimizer received,
        of the parameters' change over ``n_steps`` and of the
        error-feedback residual after them."""
        P, devs = self.pods, self.devices
        params, tdef = [], None
        for dev in devs:
            tree = self._init(jax.device_put(key, dev))
            tdef = jax.tree.structure(tree)
            params.append(jax.tree.leaves(tree))
        zeros = lambda p: [jnp.zeros_like(x) for x in params[p]]
        m = [zeros(p) for p in range(P)]
        v = [zeros(p) for p in range(P)]
        err = [zeros(p) for p in range(P)]
        anchor = ([[self._add(x, np.float32(0)) for x in params[p]]
                   for p in range(P)] if self.H > 1 else None)
        losses, gnorms, first = [], [], None
        for s in range(n_steps):
            tok, lab = batches[s]
            rows = len(tok) // P
            out = [self._grads(jax.tree.unflatten(tdef, params[p]),
                               tok[p * rows:(p + 1) * rows],
                               lab[p * rows:(p + 1) * rows], devs[p])
                   for p in range(P)]
            sqs = [self._sq(g) for _, g, _ in out]
            step_loss, step_norm, scales = [], [], []
            for (loss, _, n), sq in zip(out, sqs):
                # the summed gradient's norm, scaled to the mean's
                norm = math.sqrt(float(sq)) / n
                step_loss.append(float(loss) / n)
                step_norm.append(norm)
                scales.append(np.float32(min(1.0, self.opt["grad_clip"]
                                             / max(norm, 1e-12)) / n))
            losses.append(sum(step_loss) / P)
            gnorms.append(sum(step_norm) / P)
            scalars = adam_scalars(s, self.opt)
            for p in range(P):
                g = jax.tree.leaves(out[p][1])
                out[p] = None
                applied = []
                for i in range(len(g)):
                    if self.H == 1:
                        (params[p][i], m[p][i], v[p][i], err[p][i],
                         gn) = self._apply(params[p][i], g[i], m[p][i],
                                           v[p][i], err[p][i], scales[p],
                                           *scalars, rung=self.rungs[i])
                    else:
                        params[p][i], m[p][i], v[p][i], gn = self._local(
                            params[p][i], g[i], m[p][i], v[p][i],
                            scales[p], *scalars)
                    g[i] = None
                    applied.append(gn)
                if first is None:
                    first = [float(x) for x in applied]
            if (self.H > 1 and (s + 1) % self.H == 0
                    and self.fault != "no_exchange"):
                self._exchange(params, anchor, err)
        change = [float(x) for x in self._change(
            jax.tree.unflatten(tdef, params[0]),
            jax.device_put(key, devs[0]))]
        resid = [float(x) for x in self._norms(err[0])]
        return {"losses": losses, "grad_norms": gnorms, "first": first,
                "change": change, "resid": resid}
