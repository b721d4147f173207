"""The Neural Cache network executor: one network description, run layer
by layer through the packed bit-serial engine (core/nc_layers.py).

A network is data.  A configuration object carries ``name``, ``img`` (the
square input size), ``classes`` and ``stages``, a tuple of ``(name, op)``
pairs run in order; a global average pool (``AvgPool``) and the
classifier (``FullyConnected``, a 1x1 conv, §IV-D) close every network.
The ops:

* ``("conv", R, S, M, stride, pad)`` with an optional seventh element,
  the activation: ``"relu"`` (the default) or ``"linear"``;
* ``("maxpool" | "avgpool", R, stride, pad)``;
* ``("split", [ops], [ops], ...)``: branches run on the same input and
  concatenated along channels; layer ``j`` of branch ``i`` is named
  ``{name}_s{i}_{j}``;
* ``("mixed", [[ops], [ops], ...])``: the same, with layers named
  ``{name}_b{i}_{j}`` (Inception's mixed blocks);
* ``("residual", [(suffix, op), ...], [(suffix, op), ...])``: a body and
  a shortcut (an empty shortcut is the identity) on the same input,
  joined by the in-cache residual add with ReLU (:func:`_nc_residual`);
  their layers are named ``{name}_{suffix}`` and the join ``{name}_add``.

Models (models/inception.py, models/resnet.py) hold their topology as
such data; this module turns a description into the mapper's
:class:`~repro.core.mapper.LayerSpec` list (:func:`network_specs`), its
resident quantized filters (:func:`prepare_conv_weights`), their
occupancy for the sparse plan (:func:`network_occupancy`), and runs it
(:func:`nc_forward`).

Activations stay *quantized uint8 residents* between layers.  Each
layer's dynamic range is computed IN-CACHE by the ``nc_minmax`` log tree
(§IV-D): only the two integer scalars per image leave the array, the CPU
answers with a fixed-point multiplier and zero point, and the
requantization runs back in-cache.  No CPU-side float min/max touches an
activation tensor in the layer loop; the only offline float ranges are
the static weights'.  BatchNorm is inference-folded into a per-filter
scale (multiplied into the filter before quantization) and a bias (an
integer add on the accumulator).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import backends as _backends
from repro.core import bitserial as bs
from repro.core import nc_layers as nc
from repro.core import quantize as q
from repro.core import schedule as sched
from repro.core import simulator as sim
from repro.core.cache_geometry import CacheGeometry, XEON_E5_35MB
from repro.core.mapper import LayerSpec

__all__ = ["NCLayerReport", "NCForwardReport", "network_specs", "iter_convs",
           "init_params", "prepare_conv_weights", "network_occupancy",
           "observed_occupancy", "activation_sparsity_estimates",
           "prune_wpack", "nc_forward", "RELU_ZERO_FRACTION"]


def conv_activation(op) -> str:
    """The activation a conv op ends in: ``"relu"`` unless it says."""
    return op[6] if len(op) > 6 else "relu"


def _out_size(h: int, r: int, stride: int, pad: str) -> int:
    if pad == "SAME":
        return math.ceil(h / stride)
    return (h - r) // stride + 1


def _branch_lists(name: str, op):
    """``(tag, branches)`` of a ``split`` or ``mixed`` op: its layers are
    named ``{name}_{tag}{i}_{j}``."""
    if op[0] == "split":
        return "s", op[1:]
    return "b", op[1]


# ---------------------------------------------------------------------------
# Spec generation for the mapper/simulator
# ---------------------------------------------------------------------------
def _op_specs(name, block, op, h, c, specs):
    """Append LayerSpecs for one op; return (out_h, out_c)."""
    if op[0] == "conv":
        _, r, s, m, stride, pad = op[:6]
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
    if op[0] in ("split", "mixed"):
        tag, branches = _branch_lists(name, op)
        out_c = 0
        e = h
        for i, sub in enumerate(branches):
            hh, cc = h, c
            for j, sop in enumerate(sub):
                hh, cc = _op_specs(f"{name}_{tag}{i}_{j}", block, sop, hh,
                                   cc, specs)
            out_c += cc
            e = hh
        return e, out_c
    if op[0] == "residual":
        outs = []
        for path in op[1:]:
            hh, cc = h, c
            for suffix, sop in path:
                hh, cc = _op_specs(f"{name}_{suffix}", block, sop, hh, cc,
                                   specs)
            outs.append((hh, cc))
        if outs[0] != outs[1]:
            raise ValueError(f"{name}: body {outs[0]} and shortcut "
                             f"{outs[1]} differ in shape")
        e, m = outs[0]
        specs.append(LayerSpec(name=f"{name}_add", kind="residual", H=e,
                               R=1, S=1, C=0, M=m, E=e, stride=1,
                               block=block))
        return e, m
    raise ValueError(op)


def network_specs(config) -> list[LayerSpec]:
    """The mapper's LayerSpec list of a network description, the global
    average pool and the classifier included."""
    specs: list[LayerSpec] = []
    h, c = config.img, 3
    for name, op in config.stages:
        h, c = _op_specs(name, name, op, h, c, specs)
    # global average pool + FC-as-1x1-conv (§IV-D)
    specs.append(LayerSpec("AvgPool", "avgpool", H=h, R=h, S=h, C=0, M=c, E=1,
                           stride=1, block="AvgPool"))
    specs.append(LayerSpec("FullyConnected", "fc", H=1, R=1, S=1, C=c,
                           M=config.classes, E=1, stride=1,
                           block="FullyConnected"))
    return specs


def iter_convs(config):
    """Yield (name, r, s, c, m) for every conv and the FC in order."""
    for sp in network_specs(config):
        if sp.kind in ("conv", "fc"):
            yield sp.name, sp.R, sp.S, sp.C, sp.M


def _conv_init(key, r, s, c, m, dtype=jnp.float32):
    fan_in = r * s * c
    w = jax.random.normal(key, (r, s, c, m), dtype) * (2.0 / fan_in) ** 0.5
    return {"w": w, "scale": jnp.ones((m,), dtype), "bias": jnp.zeros((m,), dtype)}


def init_params(key: jax.Array, config, dtype=jnp.float32) -> dict:
    """He-normal filters, one key per conv in definition order, with the
    folded BatchNorm at identity (scale 1, bias 0)."""
    params = {}
    convs = list(iter_convs(config))
    keys = jax.random.split(key, len(convs))
    for k, (name, r, s, c, m) in zip(keys, convs):
        params[name] = _conv_init(k, r, s, c, m, dtype)
    return params


# ---------------------------------------------------------------------------
# Reports
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
    integrity: bool = False  # ABFT checksum verification ran
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
# the residual join per image: each operand to the common scale, the
# 32-bit add (ReLU is the MSB mask) and the output requantization
_JOIN_CYCLES = 3 * _REQUANT_PASS_CYCLES + bs.add_cycles(32)


# ---------------------------------------------------------------------------
# Resident weights and occupancy
# ---------------------------------------------------------------------------
def prepare_conv_weights(params: dict, config) -> dict:
    """Offline weight quantization (the paper quantizes weights ahead of
    time — their float ranges are static and never enter the per-layer
    loop).  BN scale folds into the filter; bias is applied as an integer
    add in the requant epilogue.

    ``nc_forward`` calls this once per invocation by default; serving
    engines precompute it once and pass ``wpack=`` so resident filters are
    quantized exactly once per deployment, not once per batch."""
    packed = {}
    for name, _, _, _, _ in iter_convs(config):
        p = params[name]
        wf = np.asarray(p["w"], np.float32) * np.asarray(p["scale"], np.float32)
        w_qp = q.choose_qparams(jnp.float32(wf.min()), jnp.float32(wf.max()))
        wq = nc._quantize_np(wf, w_qp).astype(np.uint8)
        packed[name] = (wq, w_qp, np.asarray(p["bias"], np.float32))
    return packed


# Value sparsity: occupancy metadata for the sparsity-aware scheduler.
# Filter occupancy is DETECTED from the quantized weights (deterministic —
# it earns exact skipped-pass credits); activation sparsity is an ESTIMATE
# threaded from the network structure (a ReLU output's zeros are exact
# zeros in the uint8 resident format) and stays advisory: it sizes the
# EIE-style zero-operand word elision and the reports, never a cycle
# credit.
RELU_ZERO_FRACTION = 0.5  # prior for post-ReLU zeros (symmetric preactivation)


def _op_act_est(name, op, p_in, est):
    """Walk one op: record each conv's INPUT sparsity estimate, return the
    output estimate.  Pool zeros survive only when a whole window is zero
    (non-negative resident activations), so pools raise p to the window
    population; branch concats average their branches (an estimate — the
    channel weighting is not worth modeling); a linear conv has no exact
    zeros, and a residual join ends in ReLU."""
    if op[0] == "conv":
        est[name] = p_in
        return RELU_ZERO_FRACTION if conv_activation(op) == "relu" else 0.0
    if op[0] in ("maxpool", "avgpool"):
        _, r, stride, pad = op
        return float(p_in) ** (r * r)
    if op[0] in ("split", "mixed"):
        tag, branches = _branch_lists(name, op)
        outs = []
        for i, sub in enumerate(branches):
            p = p_in
            for j, sop in enumerate(sub):
                p = _op_act_est(f"{name}_{tag}{i}_{j}", sop, p, est)
            outs.append(p)
        return sum(outs) / len(outs)
    if op[0] == "residual":
        for path in op[1:]:
            p = p_in
            for suffix, sop in path:
                p = _op_act_est(f"{name}_{suffix}", sop, p, est)
        return RELU_ZERO_FRACTION
    raise ValueError(op)


def activation_sparsity_estimates(config) -> dict:
    """Activation-sparsity estimates along the network: for every conv/fc
    layer, the estimated fraction of exactly-zero INPUT activations (what
    the host engine's zero-operand word skipping can elide).  The input
    image is dense (0.0); the FC input comes through the global average
    pool, so it is effectively dense again."""
    est: dict[str, float] = {}
    p = 0.0  # raw image pixels
    for name, op in config.stages:
        p = _op_act_est(name, op, p, est)
    est["FullyConnected"] = 0.0  # global avg of non-negative values
    return est


def network_occupancy(wpack: dict, config) -> dict:
    """Per-layer :class:`~repro.core.schedule.LayerOccupancy` from the
    quantized resident weights (:func:`prepare_conv_weights` output):
    zero-filter/dead-plane detection via the pack-time scan, with the
    activation estimates threaded in.  Feed the result to
    ``plan_network(..., occupancy=...)`` to plan the pruned pass list."""
    est = activation_sparsity_estimates(config)
    occ = {}
    for name, r, s, c, m in iter_convs(config):
        wq, w_qp, _ = wpack[name]
        rows = np.asarray(wq, np.int64).reshape(r * s * c, m).T
        occ[name] = sched.LayerOccupancy.from_filter_rows(
            rows, w_qp.bits, int(w_qp.zero_point),
            activation_sparsity=est.get(name, 0.0))
    return occ


def observed_occupancy(wpack: dict, config, report: NCForwardReport) -> dict:
    """Measured per-layer occupancy from a completed forward pass (the
    warmup re-planning): the filter side re-runs the deterministic
    pack-time scan exactly like :func:`network_occupancy`, but the
    activation side is OBSERVED, not estimated — each conv's input
    sparsity comes from the engine's zero-operand lane counts and its
    ``live_outputs`` from the measured non-zero-point output bytes, so the
    §IV-D requant pass count shrinks to what the warmup batch actually
    produced.  The estimate remains the prior for any layer the report
    did not cover."""
    est = activation_sparsity_estimates(config)
    by_name = {l.name: l for l in report.layers}
    occ = {}
    for name, r, s, c, m in iter_convs(config):
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


# ---------------------------------------------------------------------------
# The executor
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _Exec:
    """What every op of one forward shares: the resident filters, the
    layers' specs and plans, the model constants, the backend, and the
    records it appends."""

    wpack: dict
    specs: dict
    plans: dict
    geom: CacheGeometry
    const: sim.SimConstants
    engine: str
    records: list
    concat_requant_cycles: int = 0


def _fixed_point(acc_b: np.ndarray, real_multiplier: float) -> np.ndarray:
    """In-cache fixed-point multiply of one image's integers (§IV-D:
    integer multiply + round-shift, bit-exact with the shifter).  Host
    int64 arithmetic — the jnp path truncates to int32 without
    ``jax_enable_x64`` and the 31-bit mantissa product needs 63 bits."""
    mult, shift = q.fixed_point_multiplier(jnp.float32(real_multiplier))
    mult, shift = int(mult), int(shift)
    return (acc_b.astype(np.int64) * mult + (1 << (shift - 1))) >> shift


def _requant_image(acc_b: np.ndarray, real_multiplier: float,
                   zero_point: int) -> np.ndarray:
    """Requantize one image's int32 staging to uint8: the fixed-point
    multiply, the zero point, the clip."""
    rounded = _fixed_point(acc_b, real_multiplier)
    return np.clip(rounded + zero_point, 0, 255).astype(np.uint8)


def _requant_out(acc: np.ndarray, scales) -> tuple[np.ndarray, list, int]:
    """The §IV-D requantization of a batch of int32 results ``acc`` whose
    image ``b`` is in units of ``scales[b]``: the in-cache min/max tree,
    the CPU-side scalar step (two integers in, multiplier and zero point
    out), then the fixed-point requant.  Returns ``(uint8, qparams,
    min/max cycles)``."""
    B = acc.shape[0]
    mn, mx, c_mm = nc.nc_minmax(acc.reshape(B, -1), bits=32, signed=True)
    yq = np.empty(acc.shape, np.uint8)
    out_qps = []
    for b in range(B):
        qp = q.choose_qparams(jnp.float32(mn[b] * scales[b]),
                              jnp.float32(mx[b] * scales[b]))
        yq[b] = _requant_image(acc[b], scales[b] / float(qp.scale),
                               int(qp.zero_point))
        out_qps.append(qp)
    return yq, out_qps, int(c_mm)


def _nc_run_conv(name, actq, act_qps, op, ex: _Exec):
    _, r, s, m_, stride, pad = op[:6]
    relu = conv_activation(op) == "relu"
    spec, plan, geom = ex.specs[name], ex.plans[name], ex.geom
    wq, w_qp, bias = ex.wpack[name]
    acc, cycles, stats = nc.nc_conv2d(
        actq, wq, act_qps, w_qp, stride, padding=pad, geom=geom,
        layer_spec=spec, plan=plan, engine=ex.engine, return_stats=True)
    with TraceAnnotation("nc.conv.epilogue"):
        acc = np.asarray(acc, np.int64)  # [B, E, F, M] int32 staging
        B = acc.shape[0]
        # §IV-D epilogue, all in-cache: integer bias add (BN-folded), the
        # MSB-masked ReLU where the op has one, the min/max log tree, then
        # fixed-point requant.  Only two integer scalars per image leave
        # the array; a linear output's signed range gives its uint8 codes
        # a non-zero zero point.
        sxw = np.array([np.float32(qp.scale) * np.float32(w_qp.scale)
                        for qp in act_qps], np.float64)
        bias_q = np.round(bias[None, :] / sxw[:, None]).astype(np.int64)
        acc = acc + bias_q[:, None, None, :]
        if relu:
            acc = np.maximum(acc, 0)
        yq, out_qps, c_mm = _requant_out(acc, sxw)
        cycles += c_mm
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
        modeled = sim.modeled_layer_cycles(eff_plan, geom, ex.const)
        ex.records.append(NCLayerReport(
            name=name, kind="conv", out_shape=tuple(yq.shape),
            emulated_cycles=int(cycles),
            modeled_cycles=(modeled["total_cycles"]
                            + stats.reexec_passes
                            * modeled["reexec_pass_cycles"]),
            serial_passes=modeled["serial_passes"],
            modeled_s=modeled["total_s"],
            lanes=stats.lanes, zero_operand_lanes=stats.zero_operand_lanes,
            batch=B, minmax_cycles=c_mm,
            filter_loads=stats.filter_loads,
            skipped_passes=modeled["skipped_passes"],
            zero_filters=stats.zero_filters, overlap=stats.overlap,
            integrity=stats.integrity, reexec_passes=stats.reexec_passes,
            faults_detected=stats.faults_detected,
            quarantined_slices=stats.quarantined_slices,
            live_output_bytes=live_out))
    return yq, out_qps


def _nc_run_pool(name, actq, act_qps, op, ex: _Exec):
    kind, r, stride, pad = op
    pool = nc.nc_maxpool2d if kind == "maxpool" else nc.nc_avgpool2d
    compiled = _backends.get_backend(ex.engine).compiled_pools
    with TraceAnnotation("nc.pool", path="compiled" if compiled else "walk"):
        out_q, cycles = pool(actq, r, stride, padding=pad, engine=ex.engine)
        out_q = np.asarray(out_q, np.uint8)
    with TraceAnnotation("nc.accounting"):
        # never skip
        modeled = sim.modeled_layer_cycles(ex.specs[name], ex.geom, ex.const)
        ex.records.append(NCLayerReport(
            name=name, kind=kind, out_shape=tuple(out_q.shape),
            emulated_cycles=int(cycles),
            modeled_cycles=modeled["total_cycles"],
            serial_passes=modeled["serial_passes"],
            modeled_s=modeled["total_s"], batch=out_q.shape[0]))
    # pooling is order/affine-transparent: quantization passes through
    return out_q, act_qps


@functools.partial(jax.profiler.annotate_function, name="nc.concat")
def _nc_concat(outs, ex: _Exec):
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
    ex.concat_requant_cycles += B * len(outs) * _REQUANT_PASS_CYCLES
    return np.concatenate(pieces, axis=-1), cat_qps


def _nc_residual(name, body, shortcut, ex: _Exec):
    """The residual join, all in-cache, per image: both uint8 operands
    requantize to the finer of their two scales (the §IV-D fixed-point
    multiply; the finer operand's multiplier is 1 and exact), add in 32
    bits, ReLU, then the min/max tree and the requantization to uint8 that
    a conv epilogue runs."""
    (aq, a_qps), (bq, b_qps) = body, shortcut
    if aq.shape != bq.shape:
        raise ValueError(f"{name}: body {aq.shape} and shortcut {bq.shape} "
                         f"differ in shape")
    B = aq.shape[0]
    with TraceAnnotation("nc.residual", layer=name):
        acc = np.empty(aq.shape, np.int64)
        scales = []
        for b in range(B):
            s_c = min(float(a_qps[b].scale), float(b_qps[b].scale))
            acc[b] = sum(
                _fixed_point(x[b].astype(np.int64) - int(qp[b].zero_point),
                             float(qp[b].scale) / s_c)
                for x, qp in ((aq, a_qps), (bq, b_qps)))
            scales.append(s_c)
        acc = np.maximum(acc, 0)
        yq, out_qps, c_mm = _requant_out(acc, scales)
    with TraceAnnotation("nc.accounting"):
        modeled = sim.modeled_layer_cycles(ex.plans[name], ex.geom, ex.const)
        ex.records.append(NCLayerReport(
            name=name, kind="residual", out_shape=tuple(yq.shape),
            emulated_cycles=B * _JOIN_CYCLES + c_mm,
            modeled_cycles=modeled["total_cycles"],
            serial_passes=modeled["serial_passes"],
            modeled_s=modeled["total_s"], batch=B, minmax_cycles=c_mm,
            live_output_bytes=max(
                int((yq[b] != int(out_qps[b].zero_point)).sum())
                for b in range(B))))
    return yq, out_qps


def _nc_apply_op(actq, act_qps, name, op, ex: _Exec):
    if op[0] == "conv":
        with TraceAnnotation("nc.layer", layer=name):
            return _nc_run_conv(name, actq, act_qps, op, ex)
    if op[0] in ("maxpool", "avgpool"):
        with TraceAnnotation("nc.layer", layer=name):
            return _nc_run_pool(name, actq, act_qps, op, ex)
    if op[0] in ("split", "mixed"):
        tag, branches = _branch_lists(name, op)
        outs = []
        for i, sub in enumerate(branches):
            yq, qps = actq, act_qps
            for j, sop in enumerate(sub):
                yq, qps = _nc_apply_op(yq, qps, f"{name}_{tag}{i}_{j}", sop,
                                       ex)
            outs.append((yq, qps))
        return _nc_concat(outs, ex)
    if op[0] == "residual":
        outs = []
        for path in op[1:]:
            yq, qps = actq, act_qps
            for suffix, sop in path:
                yq, qps = _nc_apply_op(yq, qps, f"{name}_{suffix}", sop, ex)
            outs.append((yq, qps))
        join = f"{name}_add"
        with TraceAnnotation("nc.layer", layer=join):
            return _nc_residual(join, outs[0], outs[1], ex)
    raise ValueError(op)


def _nc_stage_gen(x4, config, ex: _Exec, out: dict):
    """Generator over the network's serial stages (§IV-E layer order): one
    yield per stage of the description and one for the final pool + FC.

    This is the hook for cross-layer streaming: ``nc_forward`` drains one
    generator straight through for a normal run, while ``stream_chunk``
    advances several chunk generators in a skewed wavefront (chunk i at
    stage t while chunk i+1 runs stage t-1 — layer L of one image set
    computes while the next set's layer L-1 loads).  ``out["logits"]``
    holds the float logits after exhaustion."""
    B = x4.shape[0]
    # §IV-D input quantization: images arrive as uint8 pixels — a static
    # [0, 1] range, no min/max ever computed on an activation tensor.
    actq = np.clip(np.round(x4 * np.float32(255.0)), 0, 255).astype(np.uint8)
    act_qps = [q.QuantParams(scale=np.float32(1.0 / 255.0), zero_point=0)] * B
    for name, op in config.stages:
        actq, act_qps = _nc_apply_op(actq, act_qps, name, op, ex)
        yield name
    # global average pool through the array, then FC as a 1x1 conv
    h = actq.shape[1]
    with TraceAnnotation("nc.layer", layer="AvgPool"):
        actq, act_qps = _nc_run_pool("AvgPool", actq, act_qps,
                                     ("avgpool", h, 1, "VALID"), ex)
    actq = actq.reshape(B, -1)
    wq, w_qp, fc_bias = ex.wpack["FullyConnected"]
    spec = ex.specs["FullyConnected"]
    plan = ex.plans["FullyConnected"]
    with TraceAnnotation("nc.layer", layer="FullyConnected"):
        acc, cycles, stats = nc.nc_fc(actq, wq[0, 0], act_qps, w_qp,
                                      geom=ex.geom, layer_spec=spec,
                                      plan=plan, engine=ex.engine,
                                      return_stats=True)
        with TraceAnnotation("nc.conv.epilogue"):
            sxw = np.array([np.float32(qp.scale) * np.float32(w_qp.scale)
                            for qp in act_qps], np.float32)
            logits = (np.asarray(acc, np.float32) * sxw[:, None]
                      + fc_bias[None, :].astype(np.float32))
        with TraceAnnotation("nc.accounting"):
            eff_plan = stats.plan if stats.plan is not None else plan
            modeled = sim.modeled_layer_cycles(eff_plan, ex.geom, ex.const)
            ex.records.append(NCLayerReport(
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
    out["logits"] = logits
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
def nc_forward(params: dict, x: jax.Array, config,
               geom: CacheGeometry = XEON_E5_35MB,
               const: sim.SimConstants = sim.SimConstants(),
               engine: str | None = None,
               schedule: sched.NetworkSchedule | None = None,
               wpack: dict | None = None,
               stream_chunk: int | None = None):
    """Quantized forward pass of the network ``config`` describes, through
    the bit-serial emulation.

    x: [H, W, 3] or batched [B, H, W, 3] float32 in [0, 1].  Every conv,
    pool and the FC run on the packed word engine, tiled by the layer's
    :class:`~repro.core.schedule.SlicePlan` with the batch folded into the
    packed lane axis (one MAC+reduce serves a whole batch tile, filters
    packed once per layer per batch — §VI-C residency); concatenations
    and residual joins requantize in-cache between them.

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
    ``schedule`` (a :class:`NetworkSchedule`) is the only carrier of the
    layers' decisions — pruned pass lists, double buffering, checking,
    filter store and backend pin — built by
    :func:`~repro.core.schedule.plan_network` (the serving path plans once
    per batch size); the forward only reads it, and the SAME object prices
    the run via ``simulator.simulate_network(schedule)``.  By default the
    dense ``plan_network(specs, geom, batch=B)`` is planned here.  A
    schedule built with ``occupancy=network_occupancy(wpack, config)``
    drops zero-filter passes from the executed pass list and credits them
    in the modeled cycles; ``overlap=True`` double-buffers every layer the
    legality rule grants (§IV-E); ``integrity=True`` verifies every pass
    against ABFT checksums, with bounded re-execution and, under an active
    ``core.faults`` scope, stuck-slice quarantine; ``compressed=True``
    keeps every filter store CSR per bit plane.  Each of them leaves the
    logits BYTE-IDENTICAL to the dense serial run on the same weights.
    ``wpack`` accepts the output of :func:`prepare_conv_weights` so
    resident filters quantize once per deployment instead of once per
    call.

    ``stream_chunk=N`` additionally streams the batch through the network
    in chunks of ``N`` images advanced in a skewed wavefront — layer L of
    chunk i computes while chunk i+1 runs layer L-1 (cross-layer §VI-C
    streaming).  Each chunk runs a chunk-sized schedule planned with the
    decisions of ``schedule`` (its overlap, integrity, compression,
    backend pin and per-layer occupancy).  Logits stay byte-identical
    (quantization is per-image), but each chunk packs its own filter
    grids (``filter_loads`` in the report sums to the chunk count), so it
    is an experiment flag, not the serving default.

    Returns ``(logits [B?, classes], NCForwardReport)`` — the report pairs
    each layer's emulated arithmetic cycles (min/max tree included) with
    the analytic model's serialized-pass cycles and modeled wall time.

    Profiler spans (recorded only while a profiler session runs): the
    call is one ``nc.forward``; each conv, pool, residual join and the FC
    one ``nc.layer`` with a ``layer`` stat naming it; inside them the
    host stages ``nc.conv.epilogue`` (bias, ReLU, min/max tree, requant),
    ``nc.pool`` (with the ``path`` stat: ``compiled`` on a device
    backend, ``walk`` on ``host``), ``nc.residual`` (the join, with the
    ``layer`` stat) and
    ``nc.accounting`` (modeled cycles and the report), and ``nc.concat``
    at each branch concatenation.  docs/SERVING.md lists every span.
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
    specs_list = network_specs(config)
    specs = {s.name: s for s in specs_list}
    if wpack is None:
        wpack = prepare_conv_weights(params, config)
    if schedule is None:
        schedule = sched.plan_network(specs_list, geom, batch=B)

    def executor(sc):
        return _Exec(wpack, specs, {p.spec.name: p for p in sc.layers}, geom,
                     const, engine, [])

    if stream_chunk is not None and stream_chunk < B:
        # cross-layer streaming: chunk generators advanced in a skewed
        # wavefront — chunk i runs stage t while chunk i+1 runs stage t-1
        chunks = [x4[i:i + stream_chunk] for i in range(0, B, stream_chunk)]
        occ = {p.spec.name: p.occupancy for p in schedule.layers
               if p.occupancy is not None}
        runs = [(executor(sched.plan_network(
            specs_list, geom, batch=xc.shape[0], occupancy=occ,
            overlap=schedule.overlap, integrity=schedule.integrity,
            compressed=schedule.compressed, backend=schedule.backend)), {})
            for xc in chunks]
        waiting = [_nc_stage_gen(xc, config, ex, out)
                   for xc, (ex, out) in zip(chunks, runs)]
        active: list = []
        while waiting or active:
            if waiting:
                active.append(waiting.pop(0))  # next chunk enters, 1 behind
            for g in list(active):
                try:
                    next(g)
                except StopIteration:
                    active.remove(g)
        logits = np.concatenate([out["logits"] for _, out in runs], axis=0)
        report = NCForwardReport(
            config.name,
            tuple(_merge_chunk_records([ex.records for ex, _ in runs], B)),
            batch=B,
            concat_requant_cycles=sum(ex.concat_requant_cycles
                                      for ex, _ in runs))
        return jnp.asarray(logits if batched else logits[0]), report

    ex, out = executor(schedule), {}
    for _ in _nc_stage_gen(x4, config, ex, out):
        pass
    report = NCForwardReport(config.name, tuple(ex.records), batch=B,
                             concat_requant_cycles=ex.concat_requant_cycles)
    return jnp.asarray(out["logits"] if batched
                       else out["logits"][0]), report
