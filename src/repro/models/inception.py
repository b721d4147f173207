"""Inception v3 — the paper's evaluation workload (Table I).

One structure definition drives ALL OF:
  * ``inception_v3_specs()`` — the per-branch LayerSpec list consumed by the
    Neural Cache mapper/simulator (reproduces Table I's Conv / Filter-MB
    columns exactly; see tests/test_inception.py),
  * ``init_params`` / ``apply`` — a runnable JAX forward pass (float and
    dynamically-quantized uint8, the paper's §IV-D pipeline), and
  * ``nc_forward`` — the same network executed *through the bit-serial
    emulation* (core/nc_layers.py): every conv/pool/fc runs on the packed
    word engine and the per-layer report pairs the emulation's arithmetic
    cycles with the analytic model's pass cycles (core/simulator.py),
    paper-style.

An :class:`InceptionConfig` scales the workload: ``FULL`` is the paper's
299x299 network; ``reduced_config()`` shrinks image size / channel widths /
class count (and optionally drops mixed stages) so the full forward pass is
emulation-tractable while still exercising every block type (3x3 stems,
1x1 packing, 5x5 splits, 7x1/1x7 factorizations, nested splits, pools).

BN is inference-folded into a per-channel scale/bias on every conv.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.cache_geometry import CacheGeometry, XEON_E5_35MB
from repro.core.mapper import LayerSpec
from repro.core import nc_layers as nc
from repro.core import quantize as q
from repro.core import schedule as sched
from repro.core import simulator as sim
from repro.core import bitserial as bs
from repro.core import backends as _backends

# ---------------------------------------------------------------------------
# Structure: op = ("conv", R, S, M, stride, pad) | ("maxpool"|"avgpool", R, stride, pad)
# A block is either a single op or a list of branches (each a list of ops).
# ---------------------------------------------------------------------------
STEM = [
    ("Conv2d_1a_3x3", ("conv", 3, 3, 32, 2, "VALID")),
    ("Conv2d_2a_3x3", ("conv", 3, 3, 32, 1, "VALID")),
    ("Conv2d_2b_3x3", ("conv", 3, 3, 64, 1, "SAME")),
    ("MaxPool_3a_3x3", ("maxpool", 3, 2, "VALID")),
    ("Conv2d_3b_1x1", ("conv", 1, 1, 80, 1, "VALID")),
    ("Conv2d_4a_3x3", ("conv", 3, 3, 192, 1, "VALID")),
    ("MaxPool_5a_3x3", ("maxpool", 3, 2, "VALID")),
]


def _inception_a(pool_proj: int):  # Mixed_5x (35x35)
    return [
        [("conv", 1, 1, 64, 1, "SAME")],
        [("conv", 1, 1, 48, 1, "SAME"), ("conv", 5, 5, 64, 1, "SAME")],
        [
            ("conv", 1, 1, 64, 1, "SAME"),
            ("conv", 3, 3, 96, 1, "SAME"),
            ("conv", 3, 3, 96, 1, "SAME"),
        ],
        [("avgpool", 3, 1, "SAME"), ("conv", 1, 1, pool_proj, 1, "SAME")],
    ]


def _reduction_a():  # Mixed_6a (35 -> 17)
    return [
        [("conv", 3, 3, 384, 2, "VALID")],
        [
            ("conv", 1, 1, 64, 1, "SAME"),
            ("conv", 3, 3, 96, 1, "SAME"),
            ("conv", 3, 3, 96, 2, "VALID"),
        ],
        [("maxpool", 3, 2, "VALID")],
    ]


def _inception_b(c7: int):  # Mixed_6b..6e (17x17)
    return [
        [("conv", 1, 1, 192, 1, "SAME")],
        [
            ("conv", 1, 1, c7, 1, "SAME"),
            ("conv", 1, 7, c7, 1, "SAME"),
            ("conv", 7, 1, 192, 1, "SAME"),
        ],
        [
            ("conv", 1, 1, c7, 1, "SAME"),
            ("conv", 7, 1, c7, 1, "SAME"),
            ("conv", 1, 7, c7, 1, "SAME"),
            ("conv", 7, 1, c7, 1, "SAME"),
            ("conv", 1, 7, 192, 1, "SAME"),
        ],
        [("avgpool", 3, 1, "SAME"), ("conv", 1, 1, 192, 1, "SAME")],
    ]


def _reduction_b():  # Mixed_7a (17 -> 8)
    return [
        [("conv", 1, 1, 192, 1, "SAME"), ("conv", 3, 3, 320, 2, "VALID")],
        [
            ("conv", 1, 1, 192, 1, "SAME"),
            ("conv", 1, 7, 192, 1, "SAME"),
            ("conv", 7, 1, 192, 1, "SAME"),
            ("conv", 3, 3, 192, 2, "VALID"),
        ],
        [("maxpool", 3, 2, "VALID")],
    ]


def _inception_c():  # Mixed_7b/7c (8x8); nested split branches flattened
    return [
        [("conv", 1, 1, 320, 1, "SAME")],
        [("conv", 1, 1, 384, 1, "SAME"), ("split", [("conv", 1, 3, 384, 1, "SAME")], [("conv", 3, 1, 384, 1, "SAME")])],
        [
            ("conv", 1, 1, 448, 1, "SAME"),
            ("conv", 3, 3, 384, 1, "SAME"),
            ("split", [("conv", 1, 3, 384, 1, "SAME")], [("conv", 3, 1, 384, 1, "SAME")]),
        ],
        [("avgpool", 3, 1, "SAME"), ("conv", 1, 1, 192, 1, "SAME")],
    ]


MIXED = [
    ("Mixed_5b", _inception_a(32)),
    ("Mixed_5c", _inception_a(64)),
    ("Mixed_5d", _inception_a(64)),
    ("Mixed_6a", _reduction_a()),
    ("Mixed_6b", _inception_b(128)),
    ("Mixed_6c", _inception_b(160)),
    ("Mixed_6d", _inception_b(160)),
    ("Mixed_6e", _inception_b(192)),
    ("Mixed_7a", _reduction_b()),
    ("Mixed_7b", _inception_c()),
    ("Mixed_7c", _inception_c()),
]

IMG = 299


# ---------------------------------------------------------------------------
# Workload configuration: the full paper network, or a reduced-but-complete
# miniature for emulation-scale end-to-end runs.
# ---------------------------------------------------------------------------
def _scale_op(op, div: int):
    if op[0] == "conv":
        _, r, s, m, stride, pad = op
        return ("conv", r, s, max(1, m // div), stride, pad)
    if op[0] == "split":
        return ("split",) + tuple(
            [_scale_op(o, div) for o in sub] for sub in op[1:])
    return op


def _scale_blocks(blocks, div: int):
    if div == 1:
        return blocks
    out = []
    for name, entry in blocks:
        if isinstance(entry, tuple):  # single op (stem)
            out.append((name, _scale_op(entry, div)))
        else:  # list of branches
            out.append((name, [[_scale_op(o, div) for o in br]
                               for br in entry]))
    return out


@dataclasses.dataclass(frozen=True)
class InceptionConfig:
    """Workload geometry: image size, channel-width divisor, classes, and
    the stem/mixed structure (pre-scaled by :func:`_scale_blocks`)."""

    img: int = IMG
    classes: int = 1001
    stem: tuple = tuple((n, op) for n, op in STEM)
    mixed: tuple = tuple((n, br) for n, br in MIXED)

    @property
    def name(self) -> str:
        return f"inception_v3_{self.img}px_{self.classes}cls"


FULL = InceptionConfig()

_STAGE_BLOCKS = {
    "a": ("Mixed_5b",),
    "ra": ("Mixed_6a",),
    "b": ("Mixed_6b",),
    "rb": ("Mixed_7a",),
    "c": ("Mixed_7b",),
}


def reduced_config(img: int = 79, width_div: int = 4, classes: int = 32,
                   stages: Sequence[str] = ("a", "ra", "b", "rb", "c"),
                   ) -> InceptionConfig:
    """A miniature Inception v3: same topology, ``width_div``-narrower
    channels, one mixed block per requested stage.

    The default (79px, /4 widths) keeps every block type and both spatial
    reductions (7x7 -> 3x3 -> 1x1 mixed grids) while staying tractable for
    the bit-serial emulation; ``stages=("a",)`` with a smaller image is the
    test-sized variant.  Note Mixed_6a/7a need a >=7px mixed grid."""
    keep = [b for s in stages for b in _STAGE_BLOCKS[s]]
    mixed = tuple((n, br) for n, br in MIXED if n in keep)
    return InceptionConfig(
        img=img, classes=classes,
        stem=tuple(_scale_blocks(STEM, width_div)),
        mixed=tuple(_scale_blocks(mixed, width_div)),
    )


REDUCED = reduced_config()


def _out_size(h: int, r: int, stride: int, pad: str) -> int:
    if pad == "SAME":
        return math.ceil(h / stride)
    return (h - r) // stride + 1


# ---------------------------------------------------------------------------
# Spec generation for the mapper/simulator
# ---------------------------------------------------------------------------
def _op_specs(name, block, op, h, c, specs):
    """Append LayerSpecs for one op; return (out_h, out_c)."""
    if op[0] == "conv":
        _, r, s, m, stride, pad = op
        e = _out_size(h, max(r, s), stride, pad)
        specs.append(
            LayerSpec(name=name, kind="conv", H=h, R=r, S=s, C=c, M=m, E=e,
                      stride=stride, block=block)
        )
        return e, m
    if op[0] in ("maxpool", "avgpool"):
        _, r, stride, pad = op
        e = _out_size(h, r, stride, pad)
        specs.append(
            LayerSpec(name=name, kind=op[0], H=h, R=r, S=r, C=0, M=c, E=e,
                      stride=stride, block=block)
        )
        return e, c
    if op[0] == "split":
        out_c = 0
        e = h
        for i, sub in enumerate(op[1:]):
            hh, cc = h, c
            for j, sop in enumerate(sub):
                hh, cc = _op_specs(f"{name}_s{i}_{j}", block, sop, hh, cc, specs)
            out_c += cc
            e = hh
        return e, out_c
    raise ValueError(op)


def inception_v3_specs(config: InceptionConfig = FULL) -> list[LayerSpec]:
    specs: list[LayerSpec] = []
    h, c = config.img, 3
    for name, op in config.stem:
        h, c = _op_specs(name, name, op, h, c, specs)
    for bname, branches in config.mixed:
        out_c = 0
        out_h = h
        for bi, branch in enumerate(branches):
            hh, cc = h, c
            for oi, op in enumerate(branch):
                hh, cc = _op_specs(f"{bname}_b{bi}_{oi}", bname, op, hh, cc, specs)
            out_c += cc
            out_h = hh
        h, c = out_h, out_c
    # global average pool (8x8 window) + FC-as-1x1-conv (§IV-D)
    specs.append(LayerSpec("AvgPool", "avgpool", H=h, R=h, S=h, C=0, M=c, E=1,
                           stride=1, block="AvgPool"))
    specs.append(LayerSpec("FullyConnected", "fc", H=1, R=1, S=1, C=c,
                           M=config.classes, E=1, stride=1,
                           block="FullyConnected"))
    return specs


# ---------------------------------------------------------------------------
# Runnable JAX model (NHWC).  BN folded: per-channel scale/bias after conv.
# ---------------------------------------------------------------------------
def _conv_init(key, r, s, c, m, dtype=jnp.float32):
    fan_in = r * s * c
    w = jax.random.normal(key, (r, s, c, m), dtype) * (2.0 / fan_in) ** 0.5
    return {"w": w, "scale": jnp.ones((m,), dtype), "bias": jnp.zeros((m,), dtype)}


def _iter_convs(config: InceptionConfig = FULL):
    """Yield (path, r, s, c, m) for every conv in definition order."""
    specs = inception_v3_specs(config)
    for sp in specs:
        if sp.kind in ("conv", "fc"):
            yield sp.name, sp.R, sp.S, sp.C, sp.M


def init_params(key: jax.Array, dtype=jnp.float32,
                config: InceptionConfig = FULL) -> dict:
    params = {}
    convs = list(_iter_convs(config))
    keys = jax.random.split(key, len(convs))
    for k, (name, r, s, c, m) in zip(keys, convs):
        params[name] = _conv_init(k, r, s, c, m, dtype)
    return params


def _conv(x, p, stride, pad):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y * p["scale"] + p["bias"]


def _pool(x, kind, r, stride, pad):
    if kind == "maxpool":
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, r, r, 1), (1, stride, stride, 1), pad
        )
    ones = jax.lax.reduce_window(
        jnp.ones_like(x), 0.0, jax.lax.add, (1, r, r, 1), (1, stride, stride, 1), pad
    )
    s = jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, r, r, 1), (1, stride, stride, 1), pad
    )
    return s / ones


def _apply_op(x, name, op, params, quant: bool):
    if op[0] == "conv":
        _, r, s, m, stride, pad = op
        p = params[name]
        if quant:
            x = q.fake_quant(x)  # dynamic uint8 activations (§IV-D)
            wq, wscale = q.quantize_per_channel(p["w"], axis=-1)
            p = dict(p, w=wq.astype(jnp.float32) * wscale)
        y = _conv(x, p, stride, pad)
        return jax.nn.relu(y)
    if op[0] in ("maxpool", "avgpool"):
        _, r, stride, pad = op
        return _pool(x, op[0], r, stride, pad)
    if op[0] == "split":
        outs = []
        for i, sub in enumerate(op[1:]):
            y = x
            for j, sop in enumerate(sub):
                y = _apply_op(y, f"{name}_s{i}_{j}", sop, params, quant)
            outs.append(y)
        return jnp.concatenate(outs, axis=-1)
    raise ValueError(op)


def apply(params: dict, x: jax.Array, quant: bool = False,
          config: InceptionConfig = FULL) -> jax.Array:
    """Forward pass.  x: [N, H, W, 3] float32 in [0,1].  Returns [N, classes]."""
    for name, op in config.stem:
        x = _apply_op(x, name, op, params, quant)
    for bname, branches in config.mixed:
        outs = []
        for bi, branch in enumerate(branches):
            y = x
            for oi, op in enumerate(branch):
                y = _apply_op(y, f"{bname}_b{bi}_{oi}", op, params, quant)
            outs.append(y)
        x = jnp.concatenate(outs, axis=-1)
    x = jnp.mean(x, axis=(1, 2))  # global average pool
    if quant:
        x = q.fake_quant(x)
    p = params["FullyConnected"]
    logits = x @ p["w"][0, 0] * p["scale"] + p["bias"]
    return logits


# ---------------------------------------------------------------------------
# End-to-end quantized forward pass THROUGH THE EMULATION (§IV-D pipeline):
# every conv/pool/fc runs on the packed bit-serial engine; activations stay
# *quantized uint8 residents* between layers.  The per-layer dynamic range is
# computed IN-CACHE by the nc_minmax log tree — only the two integer scalars
# per image leave the array, the CPU answers with a fixed-point multiplier +
# zero point, and the requantization runs back in-cache.  No CPU-side float
# min/max ever touches an activation tensor in the layer loop; the only
# offline float ranges are the static weights'.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class NCLayerReport:
    """One emulated layer: arithmetic cycles charged by the engine next to
    the analytic model's serialized-pass cycles (paper-style)."""

    name: str
    kind: str
    out_shape: tuple
    emulated_cycles: int  # §III formulas per lane group (core/nc_layers.py)
    modeled_cycles: float  # calibrated per-pass model (core/simulator.py)
    serial_passes: int
    modeled_s: float  # modeled wall time incl. data movement
    lanes: int = 0
    zero_operand_lanes: int = 0  # EIE-style tag-skippable lanes (note only)
    batch: int = 1  # images folded into the packed lane axis
    minmax_cycles: int = 0  # §IV-D in-cache min/max tree (inside emulated)
    filter_loads: int = 0  # filter packs this batch (§VI-C residency: 1)
    skipped_passes: int = 0  # zero-filter passes the sparse plan dropped
    zero_filters: int = 0  # pruned filters the engine never ran
    overlap: bool = False  # §IV-E double buffering granted and executed
    integrity: bool = False  # ABFT checksum verification ran (PR 7)
    reexec_passes: int = 0  # fault-triggered pass re-executions
    faults_detected: int = 0  # verification mismatches caught
    quarantined_slices: tuple = ()  # slices retired by stuck-at recovery
    live_output_bytes: int = 0  # MEASURED max per-image non-zero-point
    # output bytes (conv only) — the warmup re-planner's observed occupancy


@dataclasses.dataclass(frozen=True)
class NCForwardReport:
    config_name: str
    layers: tuple[NCLayerReport, ...]
    batch: int = 1
    concat_requant_cycles: int = 0  # branch -> common-scale requant at concats

    @property
    def total_emulated_cycles(self) -> int:
        return sum(l.emulated_cycles for l in self.layers)

    @property
    def total_modeled_cycles(self) -> float:
        return sum(l.modeled_cycles for l in self.layers)

    @property
    def total_modeled_s(self) -> float:
        return sum(l.modeled_s for l in self.layers)

    @property
    def total_zero_operand_lanes(self) -> int:
        return sum(l.zero_operand_lanes for l in self.layers)

    @property
    def total_skipped_passes(self) -> int:
        return sum(l.skipped_passes for l in self.layers)

    def summary(self) -> str:
        """Paper-style per-layer cycle table (Figure 13 analogue)."""
        lines = [f"# {self.config_name}: per-layer cycles "
                 f"(emulated arithmetic | modeled passes)"]
        lines.append(f"{'layer':32s} {'kind':8s} {'emulated':>14s} "
                     f"{'modeled':>14s} {'passes':>7s} {'zero-lanes':>11s}")
        for l in self.layers:
            lines.append(
                f"{l.name:32s} {l.kind:8s} {l.emulated_cycles:14d} "
                f"{l.modeled_cycles:14.0f} {l.serial_passes:7d} "
                f"{l.zero_operand_lanes:11d}")
        lines.append(
            f"{'TOTAL':32s} {'':8s} {self.total_emulated_cycles:14d} "
            f"{self.total_modeled_cycles:14.0f} {'':7s} "
            f"{self.total_zero_operand_lanes:11d}")
        lines.append(f"# modeled latency {self.total_modeled_s * 1e3:.3f} ms")
        if self.total_skipped_passes:
            lines.append(f"# sparse schedule: {self.total_skipped_passes} "
                         f"zero-filter passes skipped per image")
        return "\n".join(lines)


_REQUANT_PASS_CYCLES = bs.mul_cycles(32) + bs.add_cycles(32)  # per lockstep pass


def prepare_conv_weights(params: dict, config: InceptionConfig) -> dict:
    """Offline weight quantization (the paper quantizes weights ahead of
    time — their float ranges are static and never enter the per-layer
    loop).  BN scale folds into the filter; bias is applied as an integer
    add in the requant epilogue.

    ``nc_forward`` calls this once per invocation by default; serving
    engines precompute it once and pass ``wpack=`` so resident filters are
    quantized exactly once per deployment, not once per batch."""
    packed = {}
    for name, _, _, _, _ in _iter_convs(config):
        p = params[name]
        wf = np.asarray(p["w"], np.float32) * np.asarray(p["scale"], np.float32)
        w_qp = q.choose_qparams(jnp.float32(wf.min()), jnp.float32(wf.max()))
        wq = nc._quantize_np(wf, w_qp).astype(np.uint8)
        packed[name] = (wq, w_qp, np.asarray(p["bias"], np.float32))
    return packed


# ---------------------------------------------------------------------------
# Value sparsity: occupancy metadata for the sparsity-aware scheduler.
# Filter occupancy is DETECTED from the quantized weights (deterministic —
# it earns exact skipped-pass credits); activation sparsity is an ESTIMATE
# threaded from the network structure (every conv output passes ReLU, so
# post-activation zeros are exact zeros in the uint8 resident format) and
# stays advisory: it sizes the EIE-style zero-operand word elision and the
# reports, never a cycle credit.
# ---------------------------------------------------------------------------
RELU_ZERO_FRACTION = 0.5  # prior for post-ReLU zeros (symmetric preactivation)


def _op_act_est(name, op, p_in, est):
    """Walk one op: record the conv's INPUT sparsity estimate, return the
    output estimate.  Pool zeros survive only when a whole window is zero
    (non-negative resident activations), so pools raise p to the window
    population; branch concats average their branches (an estimate — the
    channel weighting is not worth modeling)."""
    if op[0] == "conv":
        est[name] = p_in
        return RELU_ZERO_FRACTION
    if op[0] in ("maxpool", "avgpool"):
        _, r, stride, pad = op
        return float(p_in) ** (r * r)
    if op[0] == "split":
        outs = []
        for i, sub in enumerate(op[1:]):
            p = p_in
            for j, sop in enumerate(sub):
                p = _op_act_est(f"{name}_s{i}_{j}", sop, p, est)
            outs.append(p)
        return sum(outs) / len(outs)
    raise ValueError(op)


def activation_sparsity_estimates(config: InceptionConfig = REDUCED) -> dict:
    """ReLU-chain activation-sparsity estimates: for every conv/fc layer,
    the estimated fraction of exactly-zero INPUT activations (what the
    host engine's zero-operand word skipping can elide).  The input image
    is dense (0.0); the FC input comes through the global average pool, so
    it is effectively dense again."""
    est: dict[str, float] = {}
    p = 0.0  # raw image pixels
    for name, op in config.stem:
        p = _op_act_est(name, op, p, est)
    for bname, branches in config.mixed:
        outs = []
        for bi, branch in enumerate(branches):
            pb = p
            for oi, op in enumerate(branch):
                pb = _op_act_est(f"{bname}_b{bi}_{oi}", op, pb, est)
            outs.append(pb)
        p = sum(outs) / len(outs)
    est["FullyConnected"] = 0.0  # global avg of non-negative values
    return est


def network_occupancy(wpack: dict, config: InceptionConfig = REDUCED) -> dict:
    """Per-layer :class:`~repro.core.schedule.LayerOccupancy` from the
    quantized resident weights (:func:`prepare_conv_weights` output):
    zero-filter/dead-plane detection via the pack-time scan, with the
    ReLU-chain activation estimates threaded in.  Feed the result to
    ``plan_network(..., occupancy=...)`` to plan the pruned pass list."""
    est = activation_sparsity_estimates(config)
    occ = {}
    for name, r, s, c, m in _iter_convs(config):
        wq, w_qp, _ = wpack[name]
        rows = np.asarray(wq, np.int64).reshape(r * s * c, m).T
        occ[name] = sched.LayerOccupancy.from_filter_rows(
            rows, w_qp.bits, int(w_qp.zero_point),
            activation_sparsity=est.get(name, 0.0))
    return occ


def observed_occupancy(wpack: dict, config: InceptionConfig,
                       report: "NCForwardReport") -> dict:
    """Measured per-layer occupancy from a completed forward pass (PR 8
    warmup re-planning): the filter side re-runs the deterministic
    pack-time scan exactly like :func:`network_occupancy`, but the
    activation side is OBSERVED, not estimated — each conv's input
    sparsity comes from the engine's zero-operand lane counts and its
    ``live_outputs`` from the measured non-zero-point output bytes, so the
    §IV-D requant pass count shrinks to what the warmup batch actually
    produced.  The ReLU-chain estimate remains the prior for any layer the
    report did not cover."""
    est = activation_sparsity_estimates(config)
    by_name = {l.name: l for l in report.layers}
    occ = {}
    for name, r, s, c, m in _iter_convs(config):
        wq, w_qp, _ = wpack[name]
        rows = np.asarray(wq, np.int64).reshape(r * s * c, m).T
        rep = by_name.get(name)
        act = est.get(name, 0.0)
        live_out = None
        if rep is not None and rep.kind == "conv":
            if rep.lanes:
                act = rep.zero_operand_lanes / rep.lanes
            live_out = int(rep.live_output_bytes)
        base = sched.LayerOccupancy.from_filter_rows(
            rows, w_qp.bits, int(w_qp.zero_point), activation_sparsity=act)
        occ[name] = dataclasses.replace(base, live_outputs=live_out)
    return occ


def prune_wpack(wpack: dict, fraction: float = 0.5) -> dict:
    """Fixed filter pruning for the dense-vs-sparse gates: zero out (set to
    the quantized zero point) the LAST ``round(M * fraction)`` filters of
    every conv — the same last-k rule as ``schedule.prune_occupancy``, so
    a spec-driven plan matches what detection finds on these weights."""
    pruned = {}
    for name, (wq, w_qp, bias) in wpack.items():
        wq = np.array(wq, copy=True)
        k = int(round(wq.shape[-1] * fraction))
        if k:
            wq[..., wq.shape[-1] - k:] = int(w_qp.zero_point)
        pruned[name] = (wq, w_qp, bias)
    return pruned


def _requant_image(acc_b: np.ndarray, real_multiplier: float,
                   zero_point: int) -> np.ndarray:
    """In-cache fixed-point requantization of one image's int32 staging
    (§IV-D: integer multiply + round-shift, bit-exact with the shifter).
    Host int64 arithmetic — the jnp path truncates to int32 without
    ``jax_enable_x64`` and the 31-bit mantissa product needs 63 bits."""
    mult, shift = q.fixed_point_multiplier(jnp.float32(real_multiplier))
    mult, shift = int(mult), int(shift)
    rounded = (acc_b.astype(np.int64) * mult + (1 << (shift - 1))) >> shift
    return np.clip(rounded + zero_point, 0, 255).astype(np.uint8)


def _nc_run_conv(name, actq, act_qps, op, wpack, spec, plan, geom, const,
                 engine, records):
    _, r, s, m_, stride, pad = op
    wq, w_qp, bias = wpack[name]
    acc, cycles, stats = nc.nc_conv2d(
        actq, wq, act_qps, w_qp, stride, padding=pad, geom=geom,
        layer_spec=spec, plan=plan, engine=engine, return_stats=True)
    with TraceAnnotation("nc.conv.epilogue"):
        acc = np.asarray(acc, np.int64)  # [B, E, F, M] int32 staging
        B = acc.shape[0]
        # §IV-D epilogue, all in-cache: integer bias add (BN-folded),
        # MSB-masked ReLU, the min/max log tree, then fixed-point requant.
        # Only the two integer scalars per image leave the array.
        sxw = np.array([np.float32(qp.scale) * np.float32(w_qp.scale)
                        for qp in act_qps], np.float64)
        bias_q = np.round(bias[None, :] / sxw[:, None]).astype(np.int64)
        acc = np.maximum(acc + bias_q[:, None, None, :], 0)
        mn, mx, c_mm = nc.nc_minmax(acc.reshape(B, -1), bits=32, signed=True)
        cycles += int(c_mm)
        yq = np.empty(acc.shape, np.uint8)
        out_qps = []
        for b in range(B):
            # the CPU-side scalar step: two integers in, multiplier + zp out
            qp = q.choose_qparams(jnp.float32(mn[b] * sxw[b]),
                                  jnp.float32(mx[b] * sxw[b]))
            yq[b] = _requant_image(acc[b], sxw[b] / float(qp.scale),
                                   int(qp.zero_point))
            out_qps.append(qp)
    cycles += B * plan.quant_passes * _REQUANT_PASS_CYCLES
    with TraceAnnotation("nc.accounting"):
        # measured output occupancy for warmup re-planning: a lane holding
        # the image's zero point is an exact zero activation, so the max
        # over the batch of live (non-zero-point) output bytes is what the
        # §IV-D requant passes must actually cover
        live_out = max(int((yq[b] != int(out_qps[b].zero_point)).sum())
                       for b in range(B))
        # quarantine re-plans mid-layer: price the plan the engine actually
        # executed, plus the exact per-pass price of each fault re-execution
        eff_plan = stats.plan if stats.plan is not None else plan
        modeled = sim.modeled_layer_cycles(eff_plan, geom, const)
        records.append(NCLayerReport(
            name=name, kind="conv", out_shape=tuple(yq.shape),
            emulated_cycles=int(cycles),
            modeled_cycles=(modeled["total_cycles"]
                            + stats.reexec_passes
                            * modeled["reexec_pass_cycles"]),
            serial_passes=modeled["serial_passes"],
            modeled_s=modeled["total_s"],
            lanes=stats.lanes, zero_operand_lanes=stats.zero_operand_lanes,
            batch=B, minmax_cycles=int(c_mm),
            filter_loads=stats.filter_loads,
            skipped_passes=modeled["skipped_passes"],
            zero_filters=stats.zero_filters, overlap=stats.overlap,
            integrity=stats.integrity, reexec_passes=stats.reexec_passes,
            faults_detected=stats.faults_detected,
            quarantined_slices=stats.quarantined_slices,
            live_output_bytes=live_out))
    return yq, out_qps


def _nc_run_pool(name, actq, act_qps, op, spec, geom, const, records):
    kind, r, stride, pad = op
    with TraceAnnotation("nc.pool"):
        if kind == "maxpool":
            out_q, cycles = nc.nc_maxpool2d(actq, r, stride, padding=pad)
        else:
            out_q, cycles = nc.nc_avgpool2d(actq, r, stride, padding=pad)
        out_q = np.asarray(out_q, np.uint8)
    with TraceAnnotation("nc.accounting"):
        modeled = sim.modeled_layer_cycles(spec, geom, const)  # never skip
        records.append(NCLayerReport(
            name=name, kind=kind, out_shape=tuple(out_q.shape),
            emulated_cycles=int(cycles),
            modeled_cycles=modeled["total_cycles"],
            serial_passes=modeled["serial_passes"],
            modeled_s=modeled["total_s"], batch=out_q.shape[0]))
    # pooling is order/affine-transparent: quantization passes through
    return out_q, act_qps


@functools.partial(jax.profiler.annotate_function, name="nc.concat")
def _nc_concat(outs, state):
    """Concatenate branch outputs along channels, requantizing every branch
    to a per-image common scale in-cache (branches carry their own dynamic
    ranges; the CPU sees only their qparams — scalars that already left)."""
    B = outs[0][0].shape[0]
    cat_qps = []
    pieces = [np.empty(yq.shape, np.uint8) for yq, _ in outs]
    for b in range(B):
        lo = min(float((qp.qmin - int(qp.zero_point)) * np.float32(qp.scale))
                 for _, qps in outs for qp in (qps[b],))
        hi = max(float((qp.qmax - int(qp.zero_point)) * np.float32(qp.scale))
                 for _, qps in outs for qp in (qps[b],))
        qp_c = q.choose_qparams(jnp.float32(lo), jnp.float32(hi))
        for i, (yq, qps) in enumerate(outs):
            qp_i = qps[b]
            accq = yq[b].astype(np.int64) - int(qp_i.zero_point)
            pieces[i][b] = _requant_image(
                accq, float(qp_i.scale) / float(qp_c.scale),
                int(qp_c.zero_point))
        cat_qps.append(qp_c)
    state["concat_requant_cycles"] += B * len(outs) * _REQUANT_PASS_CYCLES
    return np.concatenate(pieces, axis=-1), cat_qps


def _nc_apply_op(actq, act_qps, name, op, wpack, specs, plans, geom, const,
                 engine, records, state):
    if op[0] == "conv":
        with TraceAnnotation("nc.layer", layer=name):
            return _nc_run_conv(name, actq, act_qps, op, wpack, specs[name],
                                plans[name], geom, const, engine, records)
    if op[0] in ("maxpool", "avgpool"):
        with TraceAnnotation("nc.layer", layer=name):
            return _nc_run_pool(name, actq, act_qps, op, specs[name], geom,
                                const, records)
    if op[0] == "split":
        outs = []
        for i, sub in enumerate(op[1:]):
            yq, qps = actq, act_qps
            for j, sop in enumerate(sub):
                yq, qps = _nc_apply_op(yq, qps, f"{name}_s{i}_{j}", sop,
                                       wpack, specs, plans, geom, const,
                                       engine, records, state)
            outs.append((yq, qps))
        return _nc_concat(outs, state)
    raise ValueError(op)


def _nc_stage_gen(x4, config, wpack, specs, plans, geom, const, engine,
                  records, state):
    """Generator over the network's serial stages (§IV-E layer order): one
    yield per stem op, per mixed block, and for the final pool + FC.

    This is the hook for cross-layer streaming: ``nc_forward`` drains one
    generator straight through for a normal run, while ``stream_chunk``
    advances several chunk generators in a skewed wavefront (chunk i at
    stage t while chunk i+1 runs stage t-1 — layer L of one image set
    computes while the next set's layer L-1 loads).  ``state["logits"]``
    holds the float logits after exhaustion."""
    B = x4.shape[0]
    # §IV-D input quantization: images arrive as uint8 pixels — a static
    # [0, 1] range, no min/max ever computed on an activation tensor.
    actq = np.clip(np.round(x4 * np.float32(255.0)), 0, 255).astype(np.uint8)
    act_qps = [q.QuantParams(scale=np.float32(1.0 / 255.0), zero_point=0)] * B
    for name, op in config.stem:
        actq, act_qps = _nc_apply_op(actq, act_qps, name, op, wpack, specs,
                                     plans, geom, const, engine, records,
                                     state)
        yield name
    for bname, branches in config.mixed:
        outs = []
        for bi, branch in enumerate(branches):
            yq, qps = actq, act_qps
            for oi, op in enumerate(branch):
                yq, qps = _nc_apply_op(yq, qps, f"{bname}_b{bi}_{oi}", op,
                                       wpack, specs, plans, geom, const,
                                       engine, records, state)
            outs.append((yq, qps))
        actq, act_qps = _nc_concat(outs, state)
        yield bname
    # global average pool through the array, then FC as a 1x1 conv
    h = actq.shape[1]
    with TraceAnnotation("nc.layer", layer="AvgPool"):
        actq, act_qps = _nc_run_pool("AvgPool", actq, act_qps,
                                     ("avgpool", h, 1, "VALID"),
                                     specs["AvgPool"], geom, const, records)
    actq = actq.reshape(B, -1)
    wq, w_qp, fc_bias = wpack["FullyConnected"]
    spec = specs["FullyConnected"]
    with TraceAnnotation("nc.layer", layer="FullyConnected"):
        acc, cycles, stats = nc.nc_fc(actq, wq[0, 0], act_qps, w_qp,
                                      geom=geom, layer_spec=spec,
                                      plan=plans["FullyConnected"],
                                      engine=engine, return_stats=True)
        with TraceAnnotation("nc.conv.epilogue"):
            sxw = np.array([np.float32(qp.scale) * np.float32(w_qp.scale)
                            for qp in act_qps], np.float32)
            logits = (np.asarray(acc, np.float32) * sxw[:, None]
                      + fc_bias[None, :].astype(np.float32))
        with TraceAnnotation("nc.accounting"):
            eff_plan = (stats.plan if stats.plan is not None
                        else plans["FullyConnected"])
            modeled = sim.modeled_layer_cycles(eff_plan, geom, const)
            records.append(NCLayerReport(
                name="FullyConnected", kind="fc",
                out_shape=tuple(logits.shape),
                emulated_cycles=int(cycles),
                modeled_cycles=(modeled["total_cycles"]
                                + stats.reexec_passes
                                * modeled["reexec_pass_cycles"]),
                serial_passes=modeled["serial_passes"],
                modeled_s=modeled["total_s"],
                lanes=stats.lanes,
                zero_operand_lanes=stats.zero_operand_lanes,
                batch=x4.shape[0], filter_loads=stats.filter_loads,
                skipped_passes=modeled["skipped_passes"],
                zero_filters=stats.zero_filters, overlap=stats.overlap,
                integrity=stats.integrity,
                reexec_passes=stats.reexec_passes,
                faults_detected=stats.faults_detected,
                quarantined_slices=stats.quarantined_slices))
    state["logits"] = logits
    yield "FullyConnected"


def _merge_chunk_records(per_chunk: list[list[NCLayerReport]],
                         B: int) -> list[NCLayerReport]:
    """Merge per-chunk layer reports into whole-batch reports: emulated
    counters sum across chunks; modeled numbers are PER IMAGE and
    batch-independent, so the first chunk's stand for all.  Note
    ``filter_loads`` sums to the chunk count — cross-layer streaming packs
    each layer's filter grid once per CHUNK, trading §VI-C's once-per-batch
    residency for the wavefront (the reports keep that honest)."""
    merged = []
    for recs in zip(*per_chunk):
        r0 = recs[0]
        merged.append(dataclasses.replace(
            r0,
            out_shape=(B,) + tuple(r0.out_shape[1:]),
            emulated_cycles=sum(r.emulated_cycles for r in recs),
            lanes=sum(r.lanes for r in recs),
            zero_operand_lanes=sum(r.zero_operand_lanes for r in recs),
            batch=B,
            minmax_cycles=sum(r.minmax_cycles for r in recs),
            filter_loads=sum(r.filter_loads for r in recs),
            reexec_passes=sum(r.reexec_passes for r in recs),
            faults_detected=sum(r.faults_detected for r in recs),
            quarantined_slices=tuple(sorted(
                {s for r in recs for s in r.quarantined_slices})),
            live_output_bytes=max(r.live_output_bytes for r in recs),
        ))
    return merged


@functools.partial(jax.profiler.annotate_function, name="nc.forward")
def nc_forward(params: dict, x: jax.Array,
               config: InceptionConfig = REDUCED,
               geom: CacheGeometry = XEON_E5_35MB,
               const: sim.SimConstants = sim.SimConstants(),
               engine: str | None = None,
               schedule: sched.NetworkSchedule | None = None,
               wpack: dict | None = None,
               sparse: bool = False,
               overlap: bool = False,
               integrity: bool = False,
               compressed: bool = False,
               stream_chunk: int | None = None):
    """Quantized Inception forward pass through the bit-serial emulation.

    x: [H, W, 3] or batched [B, H, W, 3] float32 in [0, 1].  Every conv,
    pool and the FC run on the packed word engine, tiled by the layer's
    :class:`~repro.core.schedule.SlicePlan` with the batch folded into the
    packed lane axis (one MAC+reduce serves a whole batch tile, filters
    packed once per layer per batch — §VI-C residency).

    Activations stay quantized uint8 between layers; each layer's dynamic
    range comes from the IN-CACHE ``nc_minmax`` log tree (§IV-D) — only
    two integer scalars per image leave the array, and the requantization
    runs back in-cache as a fixed-point multiply.  Quantization is
    per-image, so batched outputs are bit-identical to single-image runs.

    ``engine`` names a registered backend (``core/backends.py``).
    ``engine=None`` resolves by the standing precedence: the schedule's
    ``backend`` pin (``plan_network(..., backend=...)``) > the
    ``NC_BACKEND`` environment variable > the compiled Pallas kernels
    (``pallas``) where the platform is a TPU > the bucketed-jit engine
    once the compilation cache amortizes (batch >= 2), else the host
    engine.
    An explicit engine that contradicts a backend-carrying schedule
    raises (the schedule already decided).
    ``schedule`` accepts a precomputed :class:`NetworkSchedule` (the
    serving path plans once per batch size); by default one is planned
    here, and the SAME object prices the run via
    ``simulator.simulate_network(schedule)``.  ``wpack`` accepts the
    output of :func:`prepare_conv_weights` so resident filters quantize
    once per deployment instead of once per call.

    ``sparse=True`` plans against the weights' detected value sparsity
    (:func:`network_occupancy`): zero-filter passes are dropped from the
    executed pass list and credited in the modeled cycles, with outputs
    BYTE-IDENTICAL to the dense run on the same weights (the pruned
    filters' outputs are exact affine constants).  A ``schedule`` built
    with occupancy implies the same; ``sparse`` only controls the plan
    made here.

    ``overlap=True`` plans §IV-E double buffering: every layer the
    legality rule grants streams pass k+1's filter columns while pass k's
    MAC+reduce runs (core/nc_layers.py's depth-1 pipeline), with logits
    byte-identical to the serial run.  Like ``sparse``, it only controls
    the plan made here — a precomputed ``schedule`` already decided, and
    combining the two raises.

    ``integrity=True`` plans ABFT checksum verification (PR 7): every
    executed pass is verified against exact column/row checksums, detected
    corruption triggers bounded re-execution (and stuck-slice quarantine +
    re-plan under an active ``core.faults`` scope), and the modeled cycles
    pay the additive ``checksum_pass_cycles`` term.  Logits stay
    byte-identical to the unchecked run — verification never perturbs the
    data path.  Like the other plan flags it raises when combined with an
    explicit ``schedule`` (build that with ``plan_network(...,
    integrity=True)`` instead).

    ``compressed=True`` plans CSR bit-plane filter residency (PR 8):
    every conv/fc layer's resident footprint shrinks to the live bit
    planes plus a per-plane live-column bitmap
    (``mapper.compressed_filter_bytes``), the engine stores and streams
    filters through :class:`~repro.core.bitserial.CompressedPlanes`, and
    the modeled time earns the exact residency credit (dense minus
    compressed at filter bandwidth).  Logits stay BYTE-IDENTICAL to the
    dense store — decompression scatters live columns into zero words,
    the multiply identity.  Like the other plan flags it raises when
    combined with an explicit ``schedule``.

    ``stream_chunk=N`` additionally streams the batch through the network
    in chunks of ``N`` images advanced in a skewed wavefront — layer L of
    chunk i computes while chunk i+1 runs layer L-1 (cross-layer §VI-C
    streaming).  Logits stay byte-identical (quantization is per-image),
    but each chunk packs its own filter grids (``filter_loads`` in the
    report sums to the chunk count) and plans its own chunk-sized
    schedule, so it is an experiment flag, not the serving default.

    Returns ``(logits [B?, classes], NCForwardReport)`` — the report pairs
    each layer's emulated arithmetic cycles (min/max tree included) with
    the analytic model's serialized-pass cycles and modeled wall time.

    Profiler spans (recorded only while a profiler session runs): the
    call is one ``nc.forward``; each conv, pool and the FC one
    ``nc.layer`` with a ``layer`` stat naming it; inside them the host
    stages ``nc.conv.epilogue`` (bias, ReLU, min/max tree, requant),
    ``nc.pool`` and ``nc.accounting`` (modeled cycles and the report),
    and ``nc.concat`` at each branch concatenation.  docs/SERVING.md
    lists every span.
    """
    xin = np.asarray(x, np.float32)
    batched = xin.ndim == 4
    x4 = xin if batched else xin[None]
    assert x4.ndim == 4, "nc_forward takes [H, W, 3] or [B, H, W, 3]"
    B = x4.shape[0]
    if (engine is not None and schedule is not None
            and schedule.backend not in (None, engine)):
        raise ValueError("pick the backend through the schedule "
                         "(plan_network(..., backend=...)); engine= "
                         "contradicting a backend-carrying schedule is "
                         "ambiguous")
    engine = _backends.resolve_backend(
        engine, schedule.backend if schedule is not None else None,
        default="jit" if B >= 2 else "host")
    specs_list = inception_v3_specs(config)
    specs = {s.name: s for s in specs_list}
    if wpack is None:
        wpack = prepare_conv_weights(params, config)
    if schedule is not None and overlap:
        raise ValueError("request overlap through the schedule "
                         "(plan_network(..., overlap=True)); overlap= with "
                         "an explicit schedule is ambiguous")
    if schedule is not None and integrity:
        raise ValueError("request integrity through the schedule "
                         "(plan_network(..., integrity=True)); integrity= "
                         "with an explicit schedule is ambiguous")
    if schedule is not None and compressed:
        raise ValueError("request compression through the schedule "
                         "(plan_network(..., compressed=True)); compressed= "
                         "with an explicit schedule is ambiguous")
    if schedule is not None and stream_chunk is not None:
        raise ValueError("stream_chunk replans per chunk; it cannot honor "
                         "an explicit whole-batch schedule")
    occ = (network_occupancy(wpack, config)
           if sparse and schedule is None else None)

    if stream_chunk is not None and stream_chunk < B:
        # cross-layer streaming: chunk generators advanced in a skewed
        # wavefront — chunk i runs stage t while chunk i+1 runs stage t-1
        chunks = [x4[i:i + stream_chunk] for i in range(0, B, stream_chunk)]
        per_records: list[list[NCLayerReport]] = []
        per_states: list[dict] = []
        gens = []
        for xc in chunks:
            sc = sched.plan_network(specs_list, geom, batch=xc.shape[0],
                                    occupancy=occ, overlap=overlap,
                                    integrity=integrity,
                                    compressed=compressed)
            recs: list[NCLayerReport] = []
            st = {"concat_requant_cycles": 0}
            per_records.append(recs)
            per_states.append(st)
            gens.append(_nc_stage_gen(
                xc, config, wpack, specs,
                {p.spec.name: p for p in sc.layers}, geom, const, engine,
                recs, st))
        waiting = list(gens)
        active: list = []
        while waiting or active:
            if waiting:
                active.append(waiting.pop(0))  # next chunk enters, 1 behind
            for g in list(active):
                try:
                    next(g)
                except StopIteration:
                    active.remove(g)
        logits = np.concatenate([st["logits"] for st in per_states], axis=0)
        report = NCForwardReport(
            config.name, tuple(_merge_chunk_records(per_records, B)),
            batch=B,
            concat_requant_cycles=sum(st["concat_requant_cycles"]
                                      for st in per_states))
        return jnp.asarray(logits if batched else logits[0]), report

    if schedule is None:
        schedule = sched.plan_network(specs_list, geom, batch=B,
                                      occupancy=occ, overlap=overlap,
                                      integrity=integrity,
                                      compressed=compressed)
    plans = {p.spec.name: p for p in schedule.layers}
    records: list[NCLayerReport] = []
    state = {"concat_requant_cycles": 0}
    for _ in _nc_stage_gen(x4, config, wpack, specs, plans, geom, const,
                           engine, records, state):
        pass
    report = NCForwardReport(config.name, tuple(records), batch=B,
                             concat_requant_cycles=state["concat_requant_cycles"])
    return jnp.asarray(state["logits"] if batched
                       else state["logits"][0]), report
