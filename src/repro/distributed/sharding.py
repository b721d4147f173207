"""Sharding rules: named-parameter paths -> mesh PartitionSpecs.

The rules implement the distribution design of DESIGN.md §6:

  * TP   — output-feature / expert / vocab / head dims on the ``model`` axis,
  * FSDP — the complementary weight dim on the ``data`` axis (ZeRO-3 via GSPMD),
  * DP   — batch over ``("pod", "data")``; the ``pod`` axis replicates params
           (hierarchical scheme: FSDP inside a pod, plain DP across pods, so
           the slow inter-pod links carry only gradient all-reduces),
  * EP   — the stacked expert axis of MoE weights on ``model``,
  * SP   — long-context KV/state caches sharded on the sequence dim.

Every rule is divisibility-checked.  A dim that does not divide its mesh axis
falls back to replication and the fallback is recorded in the
:class:`ShardingReport` (e.g. qwen2-7b: 28 heads % 16 != 0 -> attention
runs FSDP-sharded while its MLP is TP-sharded).

GSPMD treats these specs as layout constraints, not as a rewrite of the
program: any spec is semantically correct, the compiler inserts the
collectives implied by the layout.  The rules below therefore only encode
the *performance* intent; correctness is the compiler's job.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec

__all__ = [
    "spec_for_param",
    "make_param_shardings",
    "make_batch_sharding",
    "make_cache_shardings",
    "ShardingReport",
]


@dataclasses.dataclass
class ShardingReport:
    """Record of which rules fired and which fell back to replication."""

    assigned: dict[str, str] = dataclasses.field(default_factory=dict)
    fallbacks: list[str] = dataclasses.field(default_factory=list)

    def note(self, path: str, spec: P) -> None:
        self.assigned[path] = str(spec)

    def fallback(self, path: str, dim: int, size: int, axis: str, n: int) -> None:
        self.fallbacks.append(
            f"{path}: dim {dim} ({size}) % mesh[{axis}]={n} != 0 -> replicated"
        )


def _axis_size(mesh: Mesh, axis: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)


def _fits(size: int, mesh: Mesh, axis: str) -> bool:
    n = _axis_size(mesh, axis)
    return n > 1 and size % n == 0


def _maybe(size: int, mesh: Mesh, axis: str, path: str, dim: int,
           report: ShardingReport | None):
    """axis if divisible else None (+ report the fallback)."""
    if _fits(size, mesh, axis):
        return axis
    if report is not None and _axis_size(mesh, axis) > 1:
        report.fallback(path, dim, size, axis, _axis_size(mesh, axis))
    return None


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


# ---------------------------------------------------------------------------
# per-parameter rules
# ---------------------------------------------------------------------------
def spec_for_param(path: str, shape: tuple[int, ...], cfg: ModelConfig,
                   mesh: Mesh, report: ShardingReport | None = None) -> P:
    """PartitionSpec for one parameter leaf, identified by its tree path.

    ``path`` is '/'-joined (e.g. ``stages/0/attn/wq``).  Leading stacked-layer
    axes (from the scan representation) are never sharded.
    """
    name = path.rsplit("/", 1)[-1]
    parent = path.rsplit("/", 2)[-2] if "/" in path else ""
    nd = len(shape)

    def m(i: int, axis: str):
        return _maybe(shape[i], mesh, axis, path, i, report)

    # ---- embeddings / head -------------------------------------------------
    if name == "embed":  # (V, d): vocab->model (TP), d->data (FSDP)
        return P(m(0, "model"), m(1, "data"))
    if name == "head":  # (d, V)
        return P(m(0, "data"), m(1, "model"))

    # ---- MoE ---------------------------------------------------------------
    if parent == "moe":
        if name == "router":  # (L, d, E): E stays whole (routing is local)
            return P(*([None] * (nd - 2)), m(nd - 2, "data"), None)
        if name in ("wi", "wg"):  # (L, E, d, ff): EP on experts, FSDP on d
            return P(*([None] * (nd - 3)), m(nd - 3, "model"), m(nd - 2, "data"), None)
        if name == "wo":  # (L, E, ff, d)
            return P(*([None] * (nd - 3)), m(nd - 3, "model"), None, m(nd - 1, "data"))

    # ---- attention ---------------------------------------------------------
    if parent == "attn":
        if name in ("wq", "wk", "wv"):  # (L, d, H*hd): heads->model, d->data
            return P(*([None] * (nd - 2)), m(nd - 2, "data"), m(nd - 1, "model"))
        if name == "wo":  # (L, H*hd, d)
            return P(*([None] * (nd - 2)), m(nd - 2, "model"), m(nd - 1, "data"))
        if name in ("bq", "bk", "bv"):  # (L, H*hd)
            return P(*([None] * (nd - 1)), m(nd - 1, "model"))

    # ---- dense MLP (also arctic's dense residual) --------------------------
    if parent in ("mlp", "dense_mlp"):
        if name in ("wi", "wg"):  # (L, d, ff)
            return P(*([None] * (nd - 2)), m(nd - 2, "data"), m(nd - 1, "model"))
        if name == "wo":  # (L, ff, d)
            return P(*([None] * (nd - 2)), m(nd - 2, "model"), m(nd - 1, "data"))

    # ---- SSM (Mamba-2) ------------------------------------------------------
    if parent == "ssm":
        if name == "in_proj":  # (L, d, 2di+2N+nh)
            return P(*([None] * (nd - 2)), m(nd - 2, "data"), m(nd - 1, "model"))
        if name == "out_proj":  # (L, di, d)
            return P(*([None] * (nd - 2)), m(nd - 2, "model"), m(nd - 1, "data"))
        if name in ("conv_w", "conv_b", "norm_w"):  # channel dim last
            return P(*([None] * (nd - 1)), m(nd - 1, "model"))

    # ---- everything else (norms, scalars, A_log, D, dt_bias, betas) --------
    return P(*([None] * nd))


def make_param_shardings(cfg: ModelConfig, mesh: Mesh, params: Any,
                         report: ShardingReport | None = None):
    """Tree of NamedShardings matching ``params`` (arrays or ShapeDtypeStructs)."""

    def leaf(path, x):
        p = _path_str(path)
        spec = spec_for_param(p, tuple(x.shape), cfg, mesh, report)
        if report is not None:
            report.note(p, spec)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf, params)


# ---------------------------------------------------------------------------
# parallelism plan + batch / cache shardings
# ---------------------------------------------------------------------------
def plan_parallelism(cfg: ModelConfig) -> str:
    """Per-arch parallelism mode over the fixed (pod, data, model) mesh.

      tp   — >=20B dense: activations replicated over ``model``; ff/head/vocab
             dims TP-sharded (the model axis earns its keep in the GEMMs).
      ep   — MoE: experts on ``model``, batch ALSO on ``model`` (each chip
             holds a token group and an expert shard; dispatch is the
             all-to-all class GShard expects).
      fsdp — small dense/SSM: batch over every axis; weights stay sharded
             (ZeRO-3) and are all-gathered per layer inside the scan.  TP for
             a 1-7B model would replicate activations 16x for GEMMs too small
             to care — measured as the 526 GB/device temp pathology in the
             first olmo dry-run (EXPERIMENTS.md §Perf).
    """
    if cfg.is_moe:
        return "ep"
    return "tp" if cfg.param_count() >= 20e9 else "fsdp"


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _batch_spec(batch: int, mesh: Mesh, report: ShardingReport | None,
                what: str, mode: str = "tp") -> Any:
    """First candidate axis-tuple (by preference) that divides ``batch``."""
    has_pod = "pod" in mesh.axis_names
    if mode in ("fsdp", "ep"):
        cands = [("pod", "data", "model"), ("pod", "data"),
                 ("data", "model"), ("data",)]
    else:
        cands = [("pod", "data"), ("data",)]
    if not has_pod:
        cands = [tuple(a for a in c if a != "pod") for c in cands]
        cands = [c for i, c in enumerate(cands) if c and c not in cands[:i]]
    for axes in cands:
        total = 1
        for a in axes:
            total *= _axis_size(mesh, a)
        if batch % total == 0:
            if report is not None and axes != cands[0]:
                report.fallbacks.append(
                    f"{what}: batch {batch} %% {cands[0]} != 0 -> {axes}")
            return axes if len(axes) > 1 else axes[0]
    if report is not None:
        report.fallback(what, 0, batch, "data", _axis_size(mesh, "data"))
    return None


def make_batch_sharding(cfg: ModelConfig, mesh: Mesh, shape: ShapeSpec,
                        report: ShardingReport | None = None) -> NamedSharding:
    """Sharding for a [global_batch, seq] token (or label) array."""
    mode = plan_parallelism(cfg)
    b = _batch_spec(shape.global_batch, mesh, report, f"batch[{shape.name}]",
                    mode)
    if b is None and shape.global_batch == 1 and shape.kind != "decode":
        # batch of one -> shard the *sequence* (SP); decode steps carry a
        # [B, 1] token whose length-1 seq dim cannot shard.
        seq_ax = "data" if _fits(shape.seq_len, mesh, "data") else None
        return NamedSharding(mesh, P(None, seq_ax))
    return NamedSharding(mesh, P(b, None))


def make_cache_shardings(cfg: ModelConfig, mesh: Mesh, shape: ShapeSpec,
                         caches: Any,
                         report: ShardingReport | None = None):
    """Decode caches: batch -> ('pod','data'), heads/state -> 'model'.

    KV caches are [L, B, Hkv, W, hd]; SSM state is [L, B, nh, hd, N] and the
    conv state [L, B, K, C].  For batch-1 long-context decode the KV length
    dim W is sharded instead (sequence parallelism over the cache).
    """
    mode = plan_parallelism(cfg)
    b = _batch_spec(shape.global_batch, mesh, report, f"cache[{shape.name}]",
                    mode)
    used = set(b) if isinstance(b, tuple) else ({b} if b else set())

    def free(axis: str) -> bool:
        return axis not in used

    def leaf(path, x):
        p = _path_str(path)
        nd = len(x.shape)
        spec = [None] * nd
        # layout convention: axis 0 = stacked layers, axis 1 = batch
        if nd >= 2:
            spec[1] = b
        name = p.rsplit("/", 1)[-1]
        if name in ("k", "v", "ks", "vs") and nd == 5:  # [L,B,Hkv,W,hd|1]
            if free("model") and _fits(x.shape[2], mesh, "model"):
                spec[2] = "model"
            else:
                # kv heads don't divide TP -> shard the cache *length* (SP):
                # a 32k x batch-128 KV cache replicated 16x would blow HBM.
                ax3 = []
                if free("model") and _fits(x.shape[3], mesh, "model"):
                    ax3.append("model")
                if b is None and _fits(x.shape[3] // (ax3 and
                        _axis_size(mesh, "model") or 1), mesh, "data"):
                    ax3.append("data")  # batch-1 long-context decode
                spec[3] = tuple(ax3) if len(ax3) > 1 else (ax3[0] if ax3 else None)
        elif name == "ssm" and nd == 5:  # SSM state [L,B,nh,P,N]
            if free("model"):
                spec[2] = _maybe(x.shape[2], mesh, "model", p, 2, report)
        elif name == "conv" and nd == 4:  # [L,B,K,C]
            if free("model"):
                spec[3] = _maybe(x.shape[3], mesh, "model", p, 3, report)
        if report is not None:
            report.note(p, P(*spec))
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(leaf, caches)
