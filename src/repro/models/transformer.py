"""Decoder LM assembly for every assigned architecture family.

Layers are grouped into *stages*: maximal runs of layers with the same
attention-window class (for hymba: SWA runs split by the three global
layers; for everything else: one stage).  Each stage's params/caches are
stacked on a leading layer axis and executed with ``jax.lax.scan`` —
constant-size HLO regardless of depth (qwen110b's 80 layers compile as one
scanned body), remat policy applied at the scan boundary.  The stage
structure doubles as the pipeline-parallel cut points
(distributed/pipeline.py).

Entry points (all pure):
    init_lm(cfg, key)                          -> params
    lm_apply(cfg, params, tokens/embeds, ...)  -> hidden or (logits, caches)
    lm_loss(cfg, params, batch)                -> scalar (chunked vocab CE)
    prefill(cfg, params, tokens)               -> (last_logits, caches)
    decode_step(cfg, params, tokens, caches, pos) -> (logits, caches)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models import moe as MoE

__all__ = ["plan_stages", "init_lm", "lm_apply", "lm_loss", "prefill",
           "decode_step", "init_caches", "Stage"]


@dataclasses.dataclass(frozen=True)
class Stage:
    start: int
    length: int
    window: int  # 0 = full attention


def plan_stages(cfg: ModelConfig) -> list[Stage]:
    if not cfg.global_layers or cfg.attn_window == 0:
        return [Stage(0, cfg.n_layers, cfg.attn_window)]
    stages: list[Stage] = []
    i = 0
    globals_ = set(cfg.global_layers)
    while i < cfg.n_layers:
        if i in globals_:
            stages.append(Stage(i, 1, 0))
            i += 1
        else:
            j = i
            while j < cfg.n_layers and j not in globals_:
                j += 1
            stages.append(Stage(i, j - i, cfg.attn_window))
            i = j
    return stages


# ---------------------------------------------------------------------------
# activation sharding constraints (GSPMD anchor points)
# ---------------------------------------------------------------------------
def _constrain(cfg: ModelConfig, x, kind: str = "act"):
    """Re-anchor activation sharding at layer boundaries.

    Without these, one unshardable op (e.g. the embedding gather) lets GSPMD
    run the whole residual stream replicated — measured as a 188 GiB/device
    temp arena on olmo-1b before this constraint existed (EXPERIMENTS.md
    §Perf).  ``cfg.act_spec`` is set by the launcher; None (tests, single
    device) is a no-op.
    """
    if cfg.act_spec is None:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    b, s, v = cfg.act_spec
    if kind == "act":  # [B, T, d]
        spec = jax.sharding.PartitionSpec(b, s, None)
    elif kind in ("loss_h", "logits"):
        # Loss region: trade sequence parallelism for vocab TP.  With the
        # seq dim on `model`, every loss chunk's dW_head is a full [d, V]
        # partial reduced over `model` — 5 GB x chunks x microbatches of
        # all-reduce on a 110B model.  Re-sharding h to (batch, -, -) and
        # the logits to (batch, -, model) keeps dW_head shard-local; the
        # price is one 64 MB h all-gather per chunk (§Perf cell B).
        if not cfg.loss_vocab_tp:  # baseline: loss follows the act sharding
            spec = jax.sharding.PartitionSpec(b, s, None if kind == "loss_h"
                                              else v)
            return jax.lax.with_sharding_constraint(x, spec)
        v_eff = v
        if v is None and s == "model":
            n = dict(mesh.shape).get("model", 1)
            if n > 1 and cfg.vocab_size % n == 0:
                v_eff = "model"
        if kind == "loss_h":
            spec = jax.sharding.PartitionSpec(b, None, None)
        else:
            spec = jax.sharding.PartitionSpec(b, None, v_eff)
    else:  # [B, T]
        spec = jax.sharding.PartitionSpec(b, s)
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# per-layer params
# ---------------------------------------------------------------------------
def _layer_init(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 6)
    p: dict[str, Any] = {"norm1": L.norm_init(cfg, ks[0])}
    if cfg.has_attention:
        p["attn"] = L.attention_init(cfg, ks[1])
    if cfg.has_ssm:
        p["ssm"] = M.mamba_init(cfg, ks[2])
    if cfg.family == "hybrid":
        p["beta_attn"] = jnp.ones((), jnp.float32)
        p["beta_ssm"] = jnp.ones((), jnp.float32)
    if cfg.is_moe:
        p["norm2"] = L.norm_init(cfg, ks[3])
        p["moe"] = MoE.moe_init(cfg, ks[4])
        if cfg.moe_dense_residual:
            p["dense_mlp"] = L.mlp_init(cfg, ks[5], d_ff=cfg.dense_ff or 2 * cfg.d_model)
    elif cfg.d_ff > 0:
        p["norm2"] = L.norm_init(cfg, ks[3])
        p["mlp"] = L.mlp_init(cfg, ks[4])
    return p


def init_lm(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, cfg.n_layers + 3)
    stages = plan_stages(cfg)
    stage_params = []
    for st in stages:
        per_layer = [_layer_init(cfg, ks[st.start + i]) for i in range(st.length)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
        stage_params.append(stacked)
    params = {
        "embed": L.embed_init(ks[-1], cfg.vocab_size, cfg.d_model, cfg.jdtype),
        "stages": stage_params,
        "final_norm": L.norm_init(cfg, ks[-2]),
    }
    if not cfg.tie_embeddings:
        params["head"] = L.dense_init(ks[-3], cfg.d_model, cfg.vocab_size, cfg.jdtype)
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------
def init_caches(cfg: ModelConfig, batch: int, seq_len: int) -> list[dict]:
    """Per-stage stacked caches sized by window class (SWA: ring buffers)."""
    caches = []
    for st in plan_stages(cfg):
        c: dict[str, Any] = {}
        if cfg.has_attention:
            one = L.attention_cache_init(cfg, batch, seq_len, st.window)
            c["attn"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (st.length,) + x.shape), one
            )
        if cfg.has_ssm:
            one = M.mamba_cache_init(cfg, batch)
            c["ssm"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (st.length,) + x.shape), one
            )
        caches.append(c)
    return caches


# ---------------------------------------------------------------------------
# layer body
# ---------------------------------------------------------------------------
def _sp_enter(cfg, h):
    """Megatron-SP block entry: all-gather the seq-sharded residual so the
    block's GEMMs see full sequences and the weights STAY sharded (GSPMD
    otherwise replicates the ff weights per layer — §Perf cell B).  The
    residual stream stays seq-sharded between blocks (saved activations
    keep the 1/TP footprint); only the transient block input is gathered.
    """
    if cfg.act_spec is None or not cfg.megatron_sp:
        return h
    b, s, _ = cfg.act_spec
    if s is None:
        return h
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return h
    return jax.lax.with_sharding_constraint(
        h, jax.sharding.PartitionSpec(b, None, None))


def _layer_apply(cfg, lp, x, positions, window, attn_cache, ssm_cache, cache_pos):
    h = _sp_enter(cfg, L.apply_norm(cfg, lp["norm1"], x))
    new_ac, new_sc = attn_cache, ssm_cache
    if cfg.family == "hybrid":
        a, new_ac = L.attention_apply(cfg, lp["attn"], h, positions, window=window,
                                      cache=attn_cache, cache_pos=cache_pos)
        if x.shape[1] == 1 and ssm_cache is not None:
            s, new_sc = M.mamba_step(cfg, lp["ssm"], h, ssm_cache)
        else:
            s, new_sc = M.mamba_apply(cfg, lp["ssm"], h, cache=ssm_cache)
        ba = lp["beta_attn"].astype(x.dtype)
        bs = lp["beta_ssm"].astype(x.dtype)
        x = x + (ba * a + bs * s) / (ba + bs)
    elif cfg.family == "ssm":
        if x.shape[1] == 1 and ssm_cache is not None:
            s, new_sc = M.mamba_step(cfg, lp["ssm"], h, ssm_cache)
        else:
            s, new_sc = M.mamba_apply(cfg, lp["ssm"], h, cache=ssm_cache)
        x = x + s
    else:
        a, new_ac = L.attention_apply(cfg, lp["attn"], h, positions, window=window,
                                      cache=attn_cache, cache_pos=cache_pos)
        x = x + a
    if cfg.is_moe:
        h2 = _sp_enter(cfg, L.apply_norm(cfg, lp["norm2"], x))
        y = MoE.moe_apply(cfg, lp["moe"], h2)
        if cfg.moe_dense_residual:
            y = y + L.mlp_apply(cfg, lp["dense_mlp"], h2)
        x = x + y
    elif cfg.d_ff > 0:
        x = x + L.mlp_apply(cfg, lp["mlp"],
                            _sp_enter(cfg, L.apply_norm(cfg, lp["norm2"], x)))
    return _constrain(cfg, x), new_ac, new_sc


def _remat_wrap(cfg, fn):
    if cfg.remat == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.remat == "dots":
        # NOT checkpoint_dots: that would save the (B,H,Tq,Tk) attention-score
        # dots — the exact O(T^2) tensor flash attention exists to avoid.
        # Batched dots (scores, attn@v, MoE dispatch) are recomputed; only
        # weight-matmul outputs (qkv/o/ff projections) are saved.
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def _stage_apply(cfg, stacked, x, positions, window, cache, cache_pos):
    """Scan the stacked layers of one stage."""
    has_cache = cache is not None and len(cache) > 0

    if has_cache:
        def body(carry, per_layer):
            lp, pc = per_layer
            xo, nac, nsc = _layer_apply(cfg, lp, carry, positions, window,
                                        pc.get("attn"), pc.get("ssm"), cache_pos)
            out = {}
            if nac is not None:
                out["attn"] = nac
            if nsc is not None:
                out["ssm"] = nsc
            return xo, out

        body = _remat_wrap(cfg, body)
        x, new_cache = jax.lax.scan(body, x, (stacked, cache))
        return x, new_cache

    def body_nc(carry, lp):
        xo, _, _ = _layer_apply(cfg, lp, carry, positions, window, None, None,
                                cache_pos)
        return xo, None

    body_nc = _remat_wrap(cfg, body_nc)
    x, _ = jax.lax.scan(body_nc, x, stacked)
    return x, None


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------
def _embed(cfg, params, tokens=None, embeds=None):
    if embeds is not None:
        return _constrain(cfg, embeds.astype(cfg.jdtype))
    return _constrain(cfg, params["embed"][tokens])


def _head(cfg, params, h):
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    return h @ w


def lm_apply(cfg, params, tokens=None, *, embeds=None, positions=None,
             caches=None, cache_pos=None):
    """Backbone forward.  Returns (hidden [B,T,d], new_caches or None)."""
    x = _embed(cfg, params, tokens, embeds)
    B, T, _ = x.shape
    if positions is None:
        positions = jnp.arange(T)
    stages = plan_stages(cfg)
    new_caches = []
    for si, st in enumerate(stages):
        cache = caches[si] if caches is not None else None
        x, nc = _stage_apply(cfg, params["stages"][si], x, positions, st.window,
                             cache, cache_pos)
        new_caches.append(nc)
    x = L.apply_norm(cfg, params["final_norm"], x)
    return x, (new_caches if caches is not None else None)


def lm_logits(cfg, params, hidden):
    out = _head(cfg, params, hidden)
    if cfg.act_spec is not None and out.ndim == 2:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.axis_names:
            b, _, v = cfg.act_spec
            out = jax.lax.with_sharding_constraint(
                out, jax.sharding.PartitionSpec(b, v))
    return out


def lm_loss(cfg, params, tokens, labels, *, embeds=None, loss_chunk: int = 512):
    """Next-token CE, chunked over sequence so [B,S,V] never materializes."""
    hidden, _ = lm_apply(cfg, params, tokens, embeds=embeds)
    B, T, D = hidden.shape
    C = min(loss_chunk, T)
    pad = (-T) % C
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    n = hidden.shape[1] // C
    hc = hidden.reshape(B, n, C, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n, C).transpose(1, 0, 2)

    def chunk_loss(carry, inp):
        h, lab = inp
        h = _constrain(cfg, h, "loss_h")
        logits = _constrain(cfg,
                            _head(cfg, params, h).astype(cfg.loss_dtype),
                            "logits")
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lab, 0)[..., None], axis=-1
        )[..., 0]
        valid = lab >= 0
        ce = jnp.where(valid, logz - gold, 0.0)
        # dtype-explicit: global x64 mode must not change the carry signature
        return (carry[0] + ce.sum(dtype=jnp.float32),
                carry[1] + valid.sum(dtype=jnp.int32)), None

    # checkpoint: recompute each [B, C, V] logits chunk in the backward
    # instead of stacking all n chunks of f32 logits as scan residuals.
    (tot, cnt), _ = jax.lax.scan(jax.checkpoint(chunk_loss),
                                 (jnp.float32(0), jnp.int32(0)), (hc, lc))
    return tot / jnp.maximum(cnt, 1)


def prefill(cfg, params, tokens=None, *, embeds=None, max_len: int | None = None):
    """Run the prompt, return (last-position logits [B,V], caches).

    ``max_len`` sets the KV-cache capacity (prompt + decode headroom)."""
    if tokens is not None:
        batch, seq_len = tokens.shape
    else:
        batch, seq_len = embeds.shape[0], embeds.shape[1]
    caches = init_caches(cfg, batch, max_len or seq_len)
    hidden, caches = lm_apply(cfg, params, tokens, embeds=embeds, caches=caches)
    return lm_logits(cfg, params, hidden[:, -1]), caches


def decode_step(cfg, params, tokens, caches, pos):
    """One token for the whole batch.  tokens [B,1]; pos: scalar position
    shared by every row, or an int32 [B] vector of per-slot positions —
    continuous batching admits prompts of different lengths, so each slot
    must decode (RoPE) and write KV at its OWN position, not the batch
    max (PR 9 bugfix)."""
    if jnp.ndim(pos) > 0:
        pos = jnp.asarray(pos, jnp.int32).reshape(-1)
        positions = pos[:, None]
    else:
        positions = jnp.full((tokens.shape[0], 1), pos, jnp.int32)
    hidden, caches = lm_apply(cfg, params, tokens, positions=positions,
                              caches=caches, cache_pos=pos)
    return lm_logits(cfg, params, hidden[:, 0]), caches
