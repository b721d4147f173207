"""Mixture-of-Experts layer: GShard-style einsum dispatch (default) and a
gather/scatter alternative, both capacity-based with top-k renormalization.

EP sharding: the expert axis of the stacked expert weights maps to the
"model" mesh axis; token groups ride the "data" axis, so GSPMD materializes
the dispatch as all-to-all-class collectives.  The einsum path is the
GShard-faithful baseline; the scatter path removes the dispatch-einsum FLOPs
and is evaluated in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init

__all__ = ["moe_init", "moe_apply"]


def _ep_axes(cfg: ModelConfig):
    """(group_axes, expert_axis) for EP sharding constraints, from the
    launcher-set act_spec.  Groups ride the non-expert batch axes; experts
    ride 'model'.  None when unconstrained (tests, single device)."""
    if cfg.act_spec is None:
        return None, None
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return None, None
    b = cfg.act_spec[0]
    flat = b if isinstance(b, tuple) else ((b,) if b else ())
    if "model" not in flat:
        return None, None
    g = tuple(a for a in flat if a != "model") or None
    return g, "model"


def _constrain_ep(cfg: ModelConfig, xe):
    """xe: [G, E, C, d] expert-major buffer -> groups x data, experts x model.

    Anchors the all-to-all dispatch layout.  Without it GSPMD is free to
    replicate the stacked expert weights instead of exchanging tokens —
    measured as a 3.9 TB/device arctic-480b dry-run before this constraint.
    """
    g, e = _ep_axes(cfg)
    if e is None:
        return xe
    P = jax.sharding.PartitionSpec
    return jax.lax.with_sharding_constraint(xe, P(g, e, None, None))


def moe_init(cfg: ModelConfig, key) -> dict:
    dt = cfg.jdtype
    ks = jax.random.split(key, 4)
    E, d, ff = cfg.n_experts, cfg.d_model, cfg.d_ff
    import math
    p = {
        "router": dense_init(ks[0], d, E, jnp.float32),
        "wi": (jax.random.normal(ks[1], (E, d, ff), jnp.float32) / math.sqrt(d)).astype(dt),
        "wg": (jax.random.normal(ks[2], (E, d, ff), jnp.float32) / math.sqrt(d)).astype(dt),
        "wo": (jax.random.normal(ks[3], (E, ff, d), jnp.float32) / math.sqrt(ff)).astype(dt),
    }
    return p


def _router(cfg: ModelConfig, p: dict, x):
    """x: [..., d] -> (probs [..., E]) in f32."""
    logits = x.astype(jnp.float32) @ p["router"]
    return jax.nn.softmax(logits, axis=-1)


def _topk(probs, k: int):
    """Returns (weights [..., k], indices [..., k]) renormalized over top-k."""
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return w, idx


def _expert_ffn(cfg: ModelConfig, p: dict, xe):
    """xe: [..., E, C, d] -> [..., E, C, d] through per-expert SwiGLU."""
    h = jnp.einsum("...ecd,edf->...ecf", xe, p["wi"])
    g = jnp.einsum("...ecd,edf->...ecf", xe, p["wg"])
    h = jax.nn.silu(g) * h
    return jnp.einsum("...ecf,efd->...ecd", h, p["wo"])


def _capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    c = int(tokens_per_group * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(c, cfg.top_k)


def _group(cfg: ModelConfig, x):
    """[B,T,d] -> ([G,S,d], valid [G,S], ungroup fn).  Pads to whole groups;
    padded slots are masked out of routing so they never consume capacity."""
    B, T, d = x.shape
    flat = x.reshape(B * T, d)
    S = min(cfg.moe_group_size, B * T)
    pad = (-(B * T)) % S
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    G = flat.shape[0] // S
    valid = (jnp.arange(G * S) < B * T).reshape(G, S)

    def ungroup(y):
        return y.reshape(G * S, d)[: B * T].reshape(B, T, d)

    return flat.reshape(G, S, d), valid, S, G, ungroup


def moe_apply_einsum(cfg: ModelConfig, p: dict, x):
    """GShard dense-dispatch MoE.  x: [B, T, d] -> [B, T, d]."""
    B, T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    xg, valid, S, G, ungroup = _group(cfg, x)
    C = _capacity(cfg, S)

    probs = _router(cfg, p, xg)  # [G,S,E]
    w, idx = _topk(probs, K)  # [G,S,K]
    w = w * valid[..., None]

    # position of each (token, choice) in its expert's capacity buffer
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [G,S,K,E]
    onehot = onehot * valid[..., None, None]  # padding takes no capacity
    flat = onehot.reshape(G, S * K, E)
    pos = jnp.cumsum(flat, axis=1) - 1.0  # [G,S*K,E]
    pos = jnp.sum(pos * flat, axis=-1).reshape(G, S, K)  # [G,S,K]
    keep = pos < C
    w = jnp.where(keep, w, 0.0)

    # dispatch/combine tensors [G,S,E,C]
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, C).astype(jnp.int32), C,
                            dtype=jnp.float32)  # [G,S,K,C]
    dispatch = jnp.einsum("gske,gskc->gsec", onehot, pos_oh)
    combine = jnp.einsum("gske,gskc,gsk->gsec", onehot, pos_oh, w)

    xe = jnp.einsum("gsec,gsd->gecd", dispatch.astype(x.dtype), xg)
    xe = _constrain_ep(cfg, xe)  # all-to-all: tokens to their expert shard
    ye = _constrain_ep(cfg, _expert_ffn(cfg, p, xe))
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), ye)
    return ungroup(y)


def moe_apply_scatter(cfg: ModelConfig, p: dict, x):
    """Gather/scatter MoE: no dispatch-einsum FLOPs (beyond-GShard path)."""
    B, T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    xg, valid, S, G, ungroup = _group(cfg, x)
    C = _capacity(cfg, S)

    probs = _router(cfg, p, xg)
    w, idx = _topk(probs, K)  # [G,S,K]
    w = w * valid[..., None]

    flat_e = idx.reshape(G, S * K)
    flat_valid = jnp.repeat(valid, K, axis=1).reshape(G, S * K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32) * flat_valid[..., None]
    pos = jnp.cumsum(onehot, axis=1) - 1
    pos = jnp.take_along_axis(pos, flat_e[..., None], axis=-1)[..., 0]  # [G,S*K]
    keep = (pos < C) & flat_valid.astype(bool)
    pos_c = jnp.where(keep, pos, C)  # row C = overflow bin

    xr = jnp.repeat(xg, K, axis=1)  # [G,S*K,d] token per choice
    buf = jnp.zeros((G, E, C + 1, d), x.dtype)
    buf = buf.at[
        jnp.arange(G)[:, None], flat_e, pos_c
    ].add(xr, mode="drop")
    xe = _constrain_ep(cfg, buf[:, :, :C])
    ye = _constrain_ep(cfg, _expert_ffn(cfg, p, xe))  # [G,E,C,d]
    ye = jnp.pad(ye, ((0, 0), (0, 0), (0, 1), (0, 0)))
    out = ye[jnp.arange(G)[:, None], flat_e, pos_c]  # [G,S*K,d]
    out = out * jnp.where(keep, w.reshape(G, S * K), 0.0)[..., None].astype(x.dtype)
    y = out.reshape(G, S, K, d).sum(axis=2)
    return ungroup(y)


def moe_apply(cfg: ModelConfig, p: dict, x):
    if cfg.moe_impl == "scatter":
        return moe_apply_scatter(cfg, p, x)
    return moe_apply_einsum(cfg, p, x)
