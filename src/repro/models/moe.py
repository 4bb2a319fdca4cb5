"""Mixture-of-Experts FFN (GShard/Switch-style capacity dispatch) with
expert parallelism over the "model" mesh axis and FSDP over "data".

Dispatch pipeline (all global ops; XLA SPMD inserts the all-to-alls between
the token-sharded and expert-sharded layouts):
  router logits -> top-k experts/token -> position-in-expert via one-hot
  cumsum -> scatter into (E*C, D) buffer -> batched expert FFN -> gather back
  -> gate-weighted combine.  Tokens over capacity are dropped (standard).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def moe_init(rng, cfg, n_layers: int):
    D, Fe, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    k = jax.random.split(rng, 4)
    def init(key, *sh):
        return jax.random.normal(key, sh, jnp.float32) / math.sqrt(sh[-2])
    return {
        "router": jax.random.normal(k[0], (n_layers, D, E), jnp.float32) * 0.02,
        "w_gate": init(k[1], n_layers, E, D, Fe),
        "w_up": init(k[2], n_layers, E, D, Fe),
        "w_down": init(k[3], n_layers, E, Fe, D),
    }


def moe_specs(cfg, n_layers: int):
    D, Fe, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {"router": (n_layers, D, E),
            "w_gate": (n_layers, E, D, Fe),
            "w_up": (n_layers, E, D, Fe),
            "w_down": (n_layers, E, Fe, D)}


def moe_shardings(cfg):
    # experts over "model" (EP), embed dim over "data" (FSDP)
    return {"router": P(None, None, None),
            "w_gate": P(None, "model", "data", None),
            "w_up": P(None, "model", "data", None),
            "w_down": P(None, "model", None, "data")}


def capacity(n_tokens: int, cfg) -> int:
    c = int(math.ceil(cfg.capacity_factor * n_tokens *
                      cfg.experts_per_token / cfg.n_experts))
    # round up to a lane-friendly multiple, floor of 8
    return max(8, ((c + 7) // 8) * 8)


def _dispatch_local(xf, logits, cfg, C):
    """Device-local capacity dispatch. xf: (T, D); logits: (T, E) f32.
    Returns (ebuf (E, C, D), eidx (T, K), pos_c (T, K), gate_keep (T, K))."""
    T, D = xf.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    dt = xf.dtype
    gates, eidx = jax.lax.top_k(logits, K)                  # (T, K)
    gates = jax.nn.softmax(gates, axis=-1)
    flat_e = eidx.reshape(T * K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)     # (T*K, E)
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1
    keep = pos < C
    pos_c = jnp.where(keep, pos, C).reshape(T, K)           # C = drop row
    gate_keep = (gates * keep.reshape(T, K)).astype(dt)
    vals = (xf[:, None, :] * jnp.ones((1, K, 1), dt)).reshape(T * K, D)
    vals = vals * keep[:, None].astype(dt)
    ebuf = jnp.zeros((E, C, D), dt)
    ebuf = ebuf.at[flat_e, pos_c.reshape(-1)].add(vals, mode="drop")
    return ebuf, eidx, pos_c, gate_keep


def _combine_local(out_ebuf, eidx, pos_c, gate_keep):
    """Inverse of dispatch: gather (T, K, D) rows and gate-combine."""
    E, C, D = out_ebuf.shape
    picked = out_ebuf[eidx, jnp.minimum(pos_c, C - 1)]      # (T, K, D)
    return (picked * gate_keep[..., None]).sum(axis=1)      # (T, D)


def _expert_ffn(ebuf, wg, wu, wd):
    dt = ebuf.dtype
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ebuf, wg.astype(dt))) * \
        jnp.einsum("ecd,edf->ecf", ebuf, wu.astype(dt))
    return jnp.einsum("ecf,efd->ecd", h, wd.astype(dt))


def moe_apply(p, x, cfg):
    """x: (B, S, D) -> (B, S, D).

    With a mesh: explicit expert parallelism inside a shard_map — tokens
    stay in their (data, model) shard, experts live on "model" peers, and
    the dispatch/return travel via all_to_all over "model"; expert weights
    (FSDP over "data") are all-gathered just-in-time.  Without a mesh the
    same math runs single-device.
    """
    from repro.models.shardctx import (current_mesh, current_exclude,
                                       fit_spec)
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    mesh = current_mesh()

    def local(xl, router, wg, wu, wd, *, ep_axis=None, fsdp_axis=None):
        Bl, Sl, Dl = xl.shape
        T = Bl * Sl
        xf = xl.reshape(T, Dl)
        if fsdp_axis is not None:
            wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_axis, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_axis, axis=2, tiled=True)
        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
        C = capacity(T, cfg)
        ebuf, eidx, pos_c, gk = _dispatch_local(xf, logits, cfg, C)
        if ep_axis is not None:
            # (E, C, D) -> (E_loc, P*C, D): send each expert to its owner
            ebuf = jax.lax.all_to_all(ebuf, ep_axis, split_axis=0,
                                      concat_axis=1, tiled=True)
        out = _expert_ffn(ebuf, wg, wu, wd)
        if ep_axis is not None:
            out = jax.lax.all_to_all(out, ep_axis, split_axis=1,
                                     concat_axis=0, tiled=True)
        y = _combine_local(out, eidx, pos_c, gk)
        return y.reshape(Bl, Sl, Dl)

    if mesh is None:
        return local(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    excl = current_exclude()
    names = set(mesh.axis_names) - set(excl)
    if not names:
        # fully-manual enclosing region (the per-pod train step):
        # tokens/weights are device-local replicas — run the single-device
        # math
        return local(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    ep_axis = "model" if ("model" in names and E % mesh.shape["model"] == 0) \
        else None
    fsdp_axis = "data" if "data" in names else None
    x_spec = fit_spec(P(("pod", "data"), "model", None), x.shape, mesh, excl)
    if ep_axis is None or "model" not in str(x_spec):
        # tokens not seq-sharded (decode) — still fine, compute replicated
        pass
    w_specs = {k: fit_spec(v, p[k].shape, mesh, excl)
               for k, v in (("router", P(None, None)),
                            ("w_gate", P("model", "data", None)),
                            ("w_up", P("model", "data", None)),
                            ("w_down", P("model", None, "data")))}
    if ep_axis is None:
        w_specs = {k: fit_spec(P(*([None] * len(p[k].shape))), p[k].shape,
                               mesh, excl) for k in w_specs}
        fsdp = None
    else:
        fsdp = fsdp_axis
    out_spec = x_spec

    fn = functools.partial(local, ep_axis=ep_axis, fsdp_axis=fsdp)
    smapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(x_spec, w_specs["router"], w_specs["w_gate"],
                  w_specs["w_up"], w_specs["w_down"]),
        out_specs=out_spec, axis_names=names, check_vma=False)
    return smapped(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def load_balance_loss(logits_f32, eidx, cfg):
    """Switch-style auxiliary load-balance loss (optional)."""
    E = cfg.n_experts
    me = jnp.mean(jax.nn.softmax(logits_f32, -1), axis=0)       # router prob mass
    ce = jnp.mean(jax.nn.one_hot(eidx[:, 0], E, dtype=jnp.float32), axis=0)
    return E * jnp.sum(me * ce)
