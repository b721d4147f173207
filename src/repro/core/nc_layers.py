"""Functional execution of DNN layers through the bit-serial engine.

This is the *correctness* counterpart of core/simulator.py (which models
time/energy): each layer is computed element-for-element the way the cache
would — uint8 operands, bit-plane transposed layout, tag-predicated MACs,
in-array log-tree channel reduction, fixed-point requantization — and is
validated against jnp oracles in tests/test_nc_layers.py.

Packed-resident, tiled pipeline
-------------------------------
The engine's :class:`~repro.core.bitserial.PackedPlanes` word format is the
resident representation end to end: operands are packed straight into
row-aligned word space (``pack_values(..., row_align=True)``), the MAC and
the §III-D log-tree reduction run on words, and only the final per-row sums
are decoded — no per-lane plane tensor is ever materialized.

Work is tiled over **(image, output pixel) rows x filters** the way the
slice scheduler plans it (core/schedule.py, fed by the mapper's
serialized passes): a tile's lane count is bounded by the cache geometry
(``geom.compute_slots`` bit lines), so peak host memory follows the
modeled hardware instead of B*E*F*M*K.  Within a tile, the
packed *window* rows are packed once and broadcast across every filter at
word granularity (and the packed filter rows across every pixel) — the
word-level analogue of filter replication across arrays (§IV-B).  The
planner consults ``mapper.check_wordline_budget`` and refuses layers
whose per-bit-line working set cannot fit the modeled array.

Batch dimension (§VI-C): every layer accepts a leading batch axis
(``[B, H, W, C]``); the batch folds into the packed lane axis, so one
MAC+reduce serves rows from several images of a batch tile while the
filters stay packed once per layer per batch — the residency the
scheduler accounts as ``filter_bytes`` loaded once.  Quantization may be
per-image: ``x_qp`` accepts a sequence of per-image
:class:`~repro.core.quantize.QuantParams` (the integer MAC is shared
across the batch; only the affine zero-point correction and the padding
constant vary per image), and already-quantized *integer* inputs skip the
quantize step entirely (the §IV-D resident-uint8 pipeline).

Layer cycle counts are Python ints and are *unchanged* by tiling or
packing: each (image, pixel, filter) lane group still reports the same
``per_dot_cycles`` (mul + accumulate + log-tree), so total modeled cycles
are bit-identical to the untiled formulation — the emulation got faster,
the modeled hardware did not.  ``engine="jit"`` routes tiles through the
bucketed compiled engine (see core/bitserial.py) for sweep workloads.

:func:`nc_minmax` is the §IV-D in-cache dynamic-range reduction: a
bit-serial log tree of subtract + tag-masked copies over packed lanes —
only the two scalars per image ever leave the cache.

The TPU-fast path lives in repro/kernels.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from repro.core import backends as _backends
from repro.core import bitserial as bs
from repro.core import faults
from repro.core import quantize as q
from repro.core import schedule as sched
from repro.core.cache_geometry import CacheGeometry, XEON_E5_35MB
from repro.core.mapper import LayerSpec

__all__ = [
    "nc_dot",
    "nc_conv2d",
    "nc_maxpool2d",
    "nc_avgpool2d",
    "nc_minmax",
    "nc_relu_requant",
    "nc_fc",
    "ConvStats",
]


@dataclasses.dataclass(frozen=True)
class ConvStats:
    """Per-layer emulation accounting notes (cycles stay formula-exact for
    the passes that RUN; sparse plans drop zero-filter passes and their
    §III charges with them)."""

    lanes: int  # B*E*F*M*K MAC lanes
    zero_operand_lanes: int  # lanes a tag latch could predicate off (EIE-style)
    tiles: int
    tile_pixels: int  # (image, pixel) rows per tile
    tile_filters: int
    serial_passes: int  # mapper's modeled pass count for the layer (per image)
    engine_words_total: int  # host-engine word columns seen by the multiplier
    engine_words_skipped: int  # word columns elided (all-zero operand)
    batch: int = 1  # images folded into the lane axis this call
    filter_loads: int = 1  # times the filter word grid was packed (§VI-C: 1/batch)
    zero_filters: int = 0  # all-zero filters the sparse plan pruned
    skipped_passes: int = 0  # serialized passes the plan dropped (per image)
    overlap: bool = False  # §IV-E double buffering ran (prefetch + deferred store)
    # PR 7 integrity/fault path (all zero when integrity is off and no
    # fault environment is active — the unchecked path never touches them)
    integrity: bool = False  # ABFT checksum verification ran per pass
    verify_passes: int = 0  # checksum verifications charged (attempts incl.)
    reexec_passes: int = 0  # tile passes re-executed after detected faults
    faults_detected: int = 0  # verification mismatches caught
    integrity_cycles: int = 0  # §III cycles charged for checksum columns
    reexec_cycles: int = 0  # §III cycles charged for pass re-executions
    quarantined_slices: tuple = ()  # slices lost to repeated failures
    # PR 8 compressed residency (all zero/False when the plan is
    # uncompressed — the dense store runs bit for bit)
    compressed: bool = False  # filters lived CSR-per-bit-plane resident
    csr_payload_bytes: int = 0  # measured packed-word bytes of the store
    csr_index_bytes: int = 0  # measured per-plane live-column index bytes
    # the plan actually executed — differs from the caller's only after a
    # quarantine re-plan (excluded from equality: plans carry the spec)
    plan: object = dataclasses.field(default=None, compare=False, repr=False)


def nc_dot(x_q, w_q, acc_bits: int = 24, n_bits: int = 8):
    """Quantized dot products, one per bit-line group.

    x_q: [..., K] uint8 inputs, w_q: [..., K] uint8 filters (same shape).
    Each of the K lanes performs one ``n_bits`` MAC into a ``acc_bits``-bit
    partial sum, then the lanes reduce via the in-array log tree.  Returns
    (int values [...], cycles) — bit-exact with the integer dot product.

    Packed-resident: operands go straight to row-aligned words and the
    MAC feeds the reducer without leaving packed space.
    """
    x_q = np.asarray(x_q)
    w_q = np.asarray(w_q)
    K = x_q.shape[-1]
    P, wpr, r = bs._row_layout(K)
    xw = bs.pack_values(x_q, n_bits, row_align=True).words
    ww = bs.pack_values(w_q, n_bits, row_align=True).words
    if r == 1:
        xw = xw.reshape(n_bits, -1, wpr)
        ww = ww.reshape(n_bits, -1, wpr)
    vals, cycles = bs.packed_dot_words(xw, ww, K=K, acc_bits=acc_bits)
    n_rows = int(np.prod(x_q.shape[:-1])) if x_q.ndim > 1 else 1
    vals = np.asarray(vals).reshape(-1)[:n_rows]
    return vals.reshape(x_q.shape[:-1]), cycles


def _quantize_np(x, qp: q.QuantParams) -> np.ndarray:
    """Host mirror of core.quantize.quantize (float32 divide +
    round-half-even + clip — bit-identical to the jnp path)."""
    scale = np.float32(qp.scale)
    zp = int(qp.zero_point)
    vals = np.round(np.asarray(x, np.float32) / scale) + zp
    return np.clip(vals, qp.qmin, qp.qmax).astype(np.int64)


def _as_qp_list(qp, B: int) -> list[q.QuantParams]:
    """Normalize a QuantParams-or-per-image-sequence to a length-B list."""
    if isinstance(qp, q.QuantParams):
        return [qp] * B
    qps = list(qp)
    if len(qps) != B:
        raise ValueError(f"got {len(qps)} per-image QuantParams for batch {B}")
    if any(p.bits != qps[0].bits for p in qps):
        raise ValueError("per-image QuantParams must share a bit width")
    return qps


def _quantize_images(x4: np.ndarray, qps: list[q.QuantParams]) -> np.ndarray:
    """Per-image quantize of ``[B, H, W, C]`` — each image uses its own
    scale/zero-point, bit-identical to :func:`_quantize_np` per image."""
    if np.issubdtype(x4.dtype, np.integer):
        return x4.astype(np.int64)  # resident path: already quantized
    scales = np.array([np.float32(p.scale) for p in qps], np.float32)
    zps = np.array([int(p.zero_point) for p in qps], np.int64)
    vals = (np.round(x4.astype(np.float32) / scales[:, None, None, None])
            + zps[:, None, None, None])
    return np.clip(vals, qps[0].qmin, qps[0].qmax).astype(np.int64)


def _same_pad(h: int, r: int, stride: int) -> tuple[int, int]:
    """TF/lax SAME convention: total pad so out = ceil(h/stride); extra
    padding goes after (bottom/right)."""
    out = -(-h // stride)
    total = max((out - 1) * stride + r - h, 0)
    return total // 2, total - total // 2


def _extract_windows(x: np.ndarray, R: int, S: int, stride: int):
    """[H, W, C] -> ([E, F, R*S*C] window tensor, E, F) (VALID padding)."""
    H, W, C = x.shape
    E = (H - R) // stride + 1
    F = (W - S) // stride + 1
    rows = np.arange(E)[:, None] * stride + np.arange(R)[None, :]  # (E, R)
    cols = np.arange(F)[:, None] * stride + np.arange(S)[None, :]  # (F, S)
    win = x[rows][:, :, cols]  # (E, R, F, S, C)
    return win.transpose(0, 2, 1, 3, 4).reshape(E, F, R * S * C), E, F


def _extract_windows_batch(x4: np.ndarray, R: int, S: int, stride: int):
    """[B, H, W, C] -> ([B, E, F, R*S*C] window tensor, E, F)."""
    B, H, W, C = x4.shape
    E = (H - R) // stride + 1
    F = (W - S) // stride + 1
    rows = np.arange(E)[:, None] * stride + np.arange(R)[None, :]  # (E, R)
    cols = np.arange(F)[:, None] * stride + np.arange(S)[None, :]  # (F, S)
    win = x4[:, rows][:, :, :, cols]  # (B, E, R, F, S, C)
    return (win.transpose(0, 1, 3, 2, 4, 5).reshape(B, E, F, R * S * C),
            E, F)


def _pack_x_rows(rows: np.ndarray, n_bits: int) -> np.ndarray:
    """Window rows (T, K) -> broadcastable word grid (n, 1, ...) shared by
    every filter in the tile (the packed-plane reuse across filters)."""
    K = rows.shape[-1]
    P, wpr, r = bs._row_layout(K)
    w = bs.pack_values(rows, n_bits, row_align=True).words
    if r == 1:
        return w.reshape(n_bits, 1, rows.shape[0], wpr)
    return w.reshape(n_bits, 1, -1)  # (n, 1, ceil(T/r)) — rows share words


def _pack_w_rows(rows: np.ndarray, n_bits: int) -> np.ndarray:
    """Filter rows (M, K) -> broadcastable word grid (n, M, 1[, wpr]).

    For P < 32 each word of the dot grid holds 32/P *pixel* rows of one
    filter, so the filter's P-bit pattern is replicated across the word."""
    K = rows.shape[-1]
    P, wpr, r = bs._row_layout(K)
    if r == 1:
        w = bs.pack_values(rows, n_bits, row_align=True).words
        return w.reshape(n_bits, rows.shape[0], 1, wpr)
    rep = sum(1 << (j * P) for j in range(r))
    ks = np.arange(K, dtype=np.uint64)
    out = np.empty((n_bits, rows.shape[0]), np.uint64)
    rows = rows.astype(np.uint64)
    for p in range(n_bits):
        rowval = (((rows >> np.uint64(p)) & 1) << ks).sum(axis=1)
        out[p] = rowval * rep
    return out.astype(np.uint32)[:, :, None]


def _call_groups(sizes: list[int], cap: int | None) -> list[list[int]]:
    """Consecutive tile indices grouped so each group's summed word-grid
    size stays within ``cap`` (one group when unbounded; a tile larger
    than ``cap`` alone is a group of its own)."""
    groups: list[list[int]] = []
    total = 0
    for i, size in enumerate(sizes):
        if not groups or (cap is not None and total + size > cap):
            groups.append([])
            total = 0
        groups[-1].append(i)
        total += size
    return groups


def nc_conv2d(
    x: jax.Array,
    w: jax.Array,
    x_qp: q.QuantParams | Sequence[q.QuantParams],
    w_qp: q.QuantParams,
    stride: int = 1,
    *,
    padding: str = "VALID",
    tile_pixels: int | None = None,
    tile_filters: int | None = None,
    geom: CacheGeometry = XEON_E5_35MB,
    layer_spec: LayerSpec | None = None,
    plan: sched.SlicePlan | None = None,
    engine: str | None = None,
    return_stats: bool = False,
):
    """Quantized conv through the array model (packed-resident + tiled).

    x: [H, W, C] or [B, H, W, C] float, w: [R, S, C, M] float.  Both are
    quantized (zero-point affine, ``qp.bits`` planes), the cross terms of
    (x-zx)(w-zw) are handled exactly as the integer expansion, and the
    result is returned as int32 — what the reserved-way staging would hold
    before requantization.  Integer-dtype inputs are treated as *already
    quantized* (the resident-uint8 pipeline) and skip the quantize step;
    ``x_qp`` may be a per-image sequence for batched inputs.
    ``padding="SAME"`` pads with the (per-image) quantized zero point
    (exact under the affine identity).

    Every (image, output pixel, filter) triple is a lane group.  Work is
    tiled over (image, pixel) rows and filters so a tile's bit lines fit
    the cache geometry (peak memory is bounded by ``geom.compute_slots``,
    not B*E*F*M*K); the batch folds into the row axis, and the packed
    window rows of a row tile are packed once and broadcast across every
    filter, while the filter word grid packs ONCE per layer per batch
    (§VI-C residency).  Cycle accounting is unchanged by tiling or
    batching: each lane group reports the same ``per_dot_cycles`` as the
    untiled single-image formulation.

    ``plan`` (a :class:`~repro.core.schedule.SlicePlan`) is the only
    carrier of the layer's decisions — its tile sizes, pruned pass list,
    double buffering, checking, filter store and backend pin — built by
    :func:`~repro.core.schedule.plan_layer`; this function only reads it.
    ``plan=None`` plans the dense default ``plan_layer(spec, geom,
    batch=B)``.  ``tile_pixels`` / ``tile_filters`` re-plan the tile
    sizes and keep every other decision of the given plan (its
    occupancy, overlap, integrity, compression, backend pin and
    quarantined slices).

    ``engine`` names a registered backend (``core/backends.py``:
    ``host``, ``jit``, ``pallas``, ...); ``None`` resolves by
    the standing precedence explicit ``engine=`` > the plan's
    ``backend`` field (``plan_layer(..., backend=...)``) > the
    ``NC_BACKEND`` environment variable > ``pallas`` on a TPU > host.
    An explicit engine that
    contradicts a backend-carrying plan raises (the plan already
    decided).  ``engine="jit"`` runs tiles through the bucketed compiled
    engine (tiles are padded to a uniform shape so one executable serves
    the whole layer); ``return_stats=True`` appends a :class:`ConvStats`
    with the EIE-style zero-operand skip counts.

    Sparsity-aware execution: a plan carrying a
    :class:`~repro.core.schedule.LayerOccupancy` executes the PRUNED pass
    list — only live filter columns run through the packed engine, while
    the outputs of all-zero filters are filled from the exact affine
    identity ``zw * sum(x)`` (bit-identical to computing them; the cycle
    charge follows the executed lanes).  Build the occupancy from the
    quantized filter rows with
    :meth:`~repro.core.schedule.LayerOccupancy.from_filter_rows` and pass
    it to ``plan_layer``.  The plan's occupancy is validated against the
    actual weights (a filter it marks zero must BE zero — under-claiming
    sparsity is allowed, over-claiming raises).  Dense plans (no
    occupancy) behave exactly as before.

    §IV-E double buffering (a plan that granted ``overlap``):
    the engine runs the plan's explicit (load, compute) stage split —
    while tile k's MAC+reduce is in flight (the bucketed-jit dispatch is
    asynchronous), the host packs tile k+1's filter columns and window
    rows (the load stage), and tile k-1's finished result is retired; the
    device->host copy is deferred by exactly one tile (depth-1 pipeline,
    matching the single prefetch buffer the reserved I/O way has headroom
    for).  Results are byte-identical to the serial path — the flag only
    reorders WHEN packing and copies happen.

    Integrity + fault path (a plan that set ``integrity``, and/or an
    active ``faults.inject`` scope): each tile pass runs
    checked — ABFT checksum columns (``bitserial.abft_checksums``) are
    verified against the pass's MAC+reduce output; a mismatch triggers
    bounded re-execution (clean operands are re-packed from the resident
    caches, which faults never mutate), repeated failure quarantines the
    pass's slice and re-plans through ``schedule.plan_layer`` over the
    survivors, and an unrecoverable pass raises
    ``faults.IntegrityError``.  Verification and re-execution charge §III
    cycles (one extra lane group per row + filter per verify; the full
    tile per re-execution).  The checked path executes tiles serially and
    stores immediately — outputs stay byte-identical to the unchecked
    path on clean passes, and with integrity off and no fault scope the
    original unchecked loop runs bit for bit.

    Compressed filter residency (a plan that set ``compressed``): the
    layer's resident filter store is the CSR-per-bit-
    plane :class:`~repro.core.bitserial.CompressedPlanes` — live columns
    of live planes only — and each tile's filter slice is reconstructed
    from it before the packed MAC+reduce.  Dead columns/planes come back
    as zero words (the multiply's identity), so outputs are BYTE-
    IDENTICAL to dense execution at every pruning level
    (tests/test_sparsity.py's differential sweep).

    Layer-granular dispatch: on the unchecked path, a backend that
    declares ``layer_calls`` (``pallas``) runs one device program for
    the layer's whole pass list.  The load stage is unchanged (each row
    tile and filter tile packs once, as above); the packed rows are
    gathered along the row axis and the packed filter columns along the
    filter axis, and whole tiles split into further calls only where an
    operand grid would pass the backend's ``max_lane_words``.  The int32
    sums do not depend on how passes are grouped, so values, cycles and
    :class:`ConvStats` (``tiles`` still counts the plan's passes) are
    those of the per-tile loop, which ``host``, ``jit`` and the checked
    path keep.

    Profiler spans (``jax.profiler.TraceAnnotation``, recorded only while
    a profiler session runs; docs/SERVING.md lists them all):
    ``nc.conv.im2col`` (quantize, pad, window extraction, lane casts,
    occupancy validation), ``nc.conv.pack`` (the per-layer filter pack
    and each miss of the per-tile window and filter caches),
    ``nc.conv.store`` (each device call's values into the output),
    ``nc.conv.epilogue`` (pruned-filter fill, zero-point correction, the
    result array) and ``nc.accounting`` (the :class:`ConvStats` counts).
    """
    with TraceAnnotation("nc.conv.im2col"):
        xin = np.asarray(x)
        batched = xin.ndim == 4
        x4 = xin if batched else xin[None]
        B = x4.shape[0]
        x_qps = _as_qp_list(x_qp, B)
        wq = (np.asarray(w, np.int64)
              if np.issubdtype(np.asarray(w).dtype, np.integer)
              else _quantize_np(np.asarray(w), w_qp))
        xq = _quantize_images(x4, x_qps)
        R, S, Cw, M = wq.shape
        assert xq.shape[3] == Cw
        zxs = np.array([int(p.zero_point) for p in x_qps], np.int64)
        if padding == "SAME":
            ph = _same_pad(xq.shape[1], R, stride)
            pw = _same_pad(xq.shape[2], S, stride)
            padded = np.empty((B, xq.shape[1] + sum(ph),
                               xq.shape[2] + sum(pw), Cw), np.int64)
            padded[:] = zxs[:, None, None, None]  # per-image zero point
            padded[:, ph[0]:ph[0] + xq.shape[1],
                   pw[0]:pw[0] + xq.shape[2]] = xq
            xq = padded
        elif padding != "VALID":
            raise ValueError(
                f"padding must be VALID or SAME, got {padding!r}")
        H = xq.shape[1]
        win, E, F = _extract_windows_batch(xq, R, S, stride)  # (B, E, F, K)
        K = R * S * Cw
        n_bits = max(x_qps[0].bits, w_qp.bits)
        acc_bits = 32
        rows_total = B * E * F
        lane_dtype = np.uint8 if n_bits <= 8 else np.uint32
        win_flat = win.reshape(rows_total, K).astype(lane_dtype)
        w_rows = wq.reshape(K, M).T.astype(lane_dtype)

    # scheduler contract: the plan carries the mapper layout (word-line
    # budget already enforced), the geometry-bounded tile sizes and the
    # value-sparsity occupancy (the pruned pass list executed below).
    spec = layer_spec or LayerSpec(
        name="nc_conv2d", kind="conv", H=H, R=R, S=S, C=Cw, M=M, E=E,
        stride=stride)
    zw_int = int(w_qp.zero_point)
    if (engine is not None and plan is not None
            and plan.backend not in (None, engine)):
        raise ValueError("pick the backend through the plan "
                         "(plan_layer(..., backend=...)); engine= "
                         "contradicting a backend-carrying plan is "
                         "ambiguous")
    if plan is None or tile_pixels is not None or tile_filters is not None:
        # tile overrides re-plan the tile sizes only: every other decision
        # of the given plan carries over
        kept = {} if plan is None else dict(
            occupancy=plan.occupancy, overlap=plan.overlap,
            integrity=plan.integrity, compressed=plan.compressed,
            backend=plan.backend,
            quarantined_slices=plan.quarantined_slices)
        plan = sched.plan_layer(spec, geom, batch=B, tile_pixels=tile_pixels,
                                tile_filters=tile_filters, **kept)
    # backend selection is pure configuration: explicit engine= > the
    # plan's pin > NC_BACKEND > pallas on a TPU > host (contradictions
    # raised above)
    engine = _backends.resolve_backend(engine, plan.backend)
    tile_rows = max(1, min(plan.tile_rows, rows_total))
    tile_filters = max(1, min(plan.tile_filters, M))

    # sparse plans prune all-zero filters out of the engine's filter axis;
    # an over-claiming occupancy (marking a live filter zero) would corrupt
    # results, so it is validated against the actual quantized weights here
    with TraceAnnotation("nc.conv.im2col"):
        occ = plan.occupancy
        if occ is not None and occ.zero_filters:
            if occ.total_filters != M:
                raise ValueError(f"{spec.name}: occupancy covers "
                                 f"{occ.total_filters} filters, layer has {M}")
            zero_idx = np.asarray(occ.zero_filters, np.int64)
            not_zero = ~(w_rows[zero_idx] == zw_int).all(axis=1)
            if not_zero.any():
                raise ValueError(
                    f"{spec.name}: occupancy marks filters "
                    f"{zero_idx[not_zero].tolist()} as zero but their weights "
                    f"are live (stale plan?)")
            zero_mask = np.zeros(M, bool)
            zero_mask[zero_idx] = True
            live_idx = np.flatnonzero(~zero_mask)
        else:
            zero_mask = live_idx = None

    w_rows_live = w_rows if live_idx is None else w_rows[live_idx]
    M_live = w_rows_live.shape[0]
    overlap_exec = bool(plan.overlap)
    compressed_exec = bool(plan.compressed)
    # filters packed once per layer per batch; tiles slice the word grid.
    # Under §IV-E double buffering the pack is deferred to the per-tile
    # load stage instead (each tile's columns still pack exactly once).
    # Compressed plans (PR 8) keep the CSR-per-bit-plane store resident
    # instead of the dense grid; tiles reconstruct their column slice.
    ww_all = cw_all = None
    if M_live and not overlap_exec:
        with TraceAnnotation("nc.conv.pack"):
            grid = _pack_w_rows(w_rows_live, w_qp.bits)
            if compressed_exec:
                cw_all = bs.CompressedPlanes.compress(grid)
            else:
                ww_all = grid
            del grid
    csr_bytes = [0, 0]  # measured (payload, index) bytes of the CSR store
    if cw_all is not None:
        csr_bytes = [cw_all.payload_bytes, cw_all.index_bytes]

    skip0_words = bs.SKIP_STATS.words_total
    skip0_skipped = bs.SKIP_STATS.words_skipped
    per_dot = bs.dot_cycles(K, n_bits, acc_bits)
    out = np.empty((rows_total, M), np.int64)
    n_tiles = 0
    # jit engine: pad every tile (ragged tails included) to the layer's
    # bucket_words sizes so one compiled executable serves the whole layer
    # (and any other layer landing on the same bucket)
    bt = bs.bucket_words(tile_rows) if engine == "jit" else tile_rows
    bf = bs.bucket_words(tile_filters) if engine == "jit" else None
    p_tiles = ([(p0, min(p0 + tile_rows, rows_total))
                for p0 in range(0, rows_total, tile_rows)] if M_live else [])
    m_tiles = [(m0, min(m0 + tile_filters, M_live))
               for m0 in range(0, M_live, tile_filters)]
    w_cache: dict[int, np.ndarray] = {}
    x_cache: dict[int, np.ndarray] = {}

    def _filter_tile(mi: int) -> np.ndarray:
        """Load stage: one pass's packed filter columns (§VI-C: each
        tile's columns pack exactly once per layer per batch)."""
        ww = w_cache.get(mi)
        if ww is None:
            with TraceAnnotation("nc.conv.pack"):  # misses only
                m0, m1 = m_tiles[mi]
                if cw_all is not None:
                    ww = cw_all.dense_columns(m0, m1)
                elif ww_all is not None:
                    ww = ww_all[:, m0:m1]
                else:
                    ww = _pack_w_rows(w_rows_live[m0:m1], w_qp.bits)
                    if compressed_exec:
                        # §IV-E overlap defers packing per tile: the
                        # tile's columns still live CSR-compressed and
                        # reconstruct byte-identically before the MAC
                        cp = bs.CompressedPlanes.compress(ww)
                        csr_bytes[0] += cp.payload_bytes
                        csr_bytes[1] += cp.index_bytes
                        ww = cp.dense()
                if engine == "jit" and m1 - m0 < bf:
                    pad = (((0, 0), (0, bf - (m1 - m0)))
                           + ((0, 0),) * (ww.ndim - 2))
                    ww = np.pad(ww, pad)
                w_cache[mi] = ww
        return ww

    def _x_tile(pi: int) -> np.ndarray:
        xw = x_cache.get(pi)
        if xw is None:
            with TraceAnnotation("nc.conv.pack"):  # misses only
                p0, p1 = p_tiles[pi]
                rows = win_flat[p0:p1]
                if engine == "jit" and rows.shape[0] < bt:
                    rows = np.pad(rows, ((0, bt - rows.shape[0]), (0, 0)))
                xw = _pack_x_rows(rows, x_qps[0].bits)
                x_cache[pi] = xw
        return xw

    def _store(vals, rows: tuple[int, int], cols: tuple[int, int]) -> None:
        """Write one call's block, live filters ``cols`` x rows ``rows``."""
        with TraceAnnotation("nc.conv.store"):
            p0, p1 = rows
            m0, m1 = cols
            v = np.asarray(vals)  # (Mt, T[, expanded rows]); blocks on jit
            sel = slice(m0, m1) if live_idx is None else live_idx[m0:m1]
            out[p0:p1, sel] = v[: m1 - m0, : p1 - p0].T

    order = [(pi, mi) for pi in range(len(p_tiles))
             for mi in range(len(m_tiles))]
    # PR 7 checked path: active fault scope and/or an integrity plan runs
    # every tile serially through verify/retry/quarantine; otherwise the
    # unchecked loop below runs bit for bit (standing off-switch idiom)
    fs = faults.active()
    integrity_on = bool(plan.integrity)
    checked = integrity_on or fs is not None
    backend = _backends.get_backend(engine)
    # rows sharing words (K <= 16) cannot be gathered along the row axis
    layer_calls = backend.layer_calls and bs._row_layout(K)[2] == 1
    eff_plan = plan
    verify_passes = reexec_passes = faults_detected = 0
    integrity_cycles = reexec_cycles = 0
    if checked:
        P_lay, _, r_lay = bs._row_layout(K)
        cs_refs: dict = {}
        lanes_f: dict = {}
        lanes_a: dict = {}

        def _refs(pi: int, mi: int):
            """Clean ABFT references for tile (pi, mi), encoded once from
            the resident operands (the load-time checksum columns)."""
            got = cs_refs.get((pi, mi))
            if got is None:
                p0, p1 = p_tiles[pi]
                m0, m1 = m_tiles[mi]
                got = cs_refs[(pi, mi)] = bs.abft_checksums(
                    win_flat[p0:p1], w_rows_live[m0:m1])
            return got

        def _live_lanes_filter(pi: int) -> np.ndarray:
            """Lanes where a filter-side fault provably changes output:
            the window rows riding bit slot 0 (the injected replica) have
            a nonzero lane sum there."""
            got = lanes_f.get(pi)
            if got is None:
                p0, p1 = p_tiles[pi]
                sums = win_flat[p0:p1][0::r_lay].sum(axis=0, dtype=np.int64)
                got = lanes_f[pi] = np.flatnonzero(sums > 0)
            return got

        def _live_lanes_act(mi: int) -> np.ndarray:
            """Lanes where an activation-side fault provably changes
            output: some live filter is nonzero there."""
            got = lanes_a.get(mi)
            if got is None:
                m0, m1 = m_tiles[mi]
                sums = w_rows_live[m0:m1].sum(axis=0, dtype=np.int64)
                got = lanes_a[mi] = np.flatnonzero(sums > 0)
            return got

        max_retries = fs.profile.max_retries if fs is not None else 1
        for t, (pi, mi) in enumerate(order):
            for stale in [k for k in x_cache if k < pi]:
                del x_cache[stale]
            p0, p1 = p_tiles[pi]
            m0, m1 = m_tiles[mi]
            attempts = 0       # retry budget (refreshed by a quarantine)
            execs = 0          # total executions of this tile
            quarantine_rounds = 0
            while True:
                execs += 1
                xw = _x_tile(pi)
                ww = _filter_tile(mi)
                corrupted = False
                if fs is not None:
                    fs.maybe_stall(spec.name, t)
                    ww2 = fs.corrupt_filter_words(
                        ww, spec.name, t, lanes=_live_lanes_filter(pi),
                        filters=m1 - m0, P=P_lay, r=r_lay)
                    xw2 = fs.corrupt_act_words(
                        xw, spec.name, t, lanes=_live_lanes_act(mi),
                        rows=p1 - p0, P=P_lay, r=r_lay)
                    corrupted = ww2 is not ww or xw2 is not xw
                    xw, ww = xw2, ww2
                vals, _ = bs.packed_dot_words(
                    xw, ww, K=K, acc_bits=acc_bits, engine=engine)
                v2 = np.asarray(vals)[: m1 - m0, : p1 - p0]
                if fs is not None:
                    v3 = fs.corrupt_values(v2, spec.name, t,
                                           filters=m1 - m0, rows=p1 - p0)
                    corrupted = corrupted or v3 is not v2
                    v2 = v3
                    if corrupted:
                        fs.note_corrupt_attempt()
                if execs == 1:
                    n_tiles += 1
                else:
                    reexec_passes += 1
                    reexec_cycles += per_dot * (p1 - p0) * (m1 - m0)
                    if fs is not None:
                        fs.note_reexecution()
                if not integrity_on:
                    break  # faults without checking: corruption flows through
                verify_passes += 1
                integrity_cycles += per_dot * ((p1 - p0) + (m1 - m0))
                ref_col, ref_row = _refs(pi, mi)
                if ((v2.sum(axis=0, dtype=np.int64) == ref_col).all()
                        and (v2.sum(axis=1, dtype=np.int64) == ref_row).all()):
                    break
                faults_detected += 1
                if fs is not None:
                    fs.note_detected()
                attempts += 1
                if attempts <= max_retries:
                    continue
                # retry budget exhausted — only a persistent (stuck-at)
                # fault survives clean re-execution, so quarantine the
                # pass's slice, re-plan over the survivors (the pass ->
                # slice map shifts off the dead slice) and grant one
                # fresh budget; unrecoverable passes raise
                sid = fs.slice_for(spec.name, t) if fs is not None else None
                can_quarantine = (
                    fs is not None and sid is not None
                    and sid not in fs.quarantined
                    and len(fs.quarantined) < geom.n_slices - 1
                    and quarantine_rounds < geom.n_slices)
                if not can_quarantine:
                    raise faults.IntegrityError(spec.name, t, attempts)
                fs.quarantine(sid)
                quarantine_rounds += 1
                eff_plan = sched.plan_layer(
                    spec, geom, batch=B,
                    tile_pixels=tile_rows, tile_filters=tile_filters,
                    occupancy=plan.occupancy, overlap=plan.overlap,
                    integrity=True,
                    quarantined_slices=tuple(sorted(fs.quarantined)),
                    compressed=plan.compressed)
                attempts = 0
            _store(v2, p_tiles[pi], m_tiles[mi])
    elif layer_calls:
        # one device call serves many plan passes: the load stage still
        # packs each row tile and filter tile once, then the packed
        # windows are gathered along the row axis and the packed filter
        # columns along the filter axis; whole tiles split into more
        # calls only where a grid would pass the backend's cap
        cap = backend.max_lane_words
        xs = [_x_tile(pi) for pi in range(len(p_tiles))]
        ws = [_filter_tile(mi) for mi in range(len(m_tiles))]
        w_calls = [(np.concatenate([ws[i] for i in mg], axis=1),
                    (m_tiles[mg[0]][0], m_tiles[mg[-1]][1]), len(mg))
                   for mg in _call_groups([a.size for a in ws], cap)]
        for pg in _call_groups([a.size for a in xs], cap):
            xw = np.concatenate([xs[i] for i in pg], axis=2)
            rows = (p_tiles[pg[0]][0], p_tiles[pg[-1]][1])
            for ww, cols, n_m in w_calls:
                vals, _ = bs.packed_dot_words(
                    xw, ww, K=K, acc_bits=acc_bits, engine=engine,
                    passes=len(pg) * n_m)
                _store(vals, rows, cols)
        n_tiles = len(p_tiles) * len(m_tiles)
    else:
        pending = None  # §IV-E double buffer: one dispatched tile in flight
        for t, (pi, mi) in enumerate(order):
            for stale in [k for k in x_cache if k < pi]:
                del x_cache[stale]  # row tiles behind the pipeline are done
            vals, _ = bs.packed_dot_words(
                _x_tile(pi), _filter_tile(mi), K=K, acc_bits=acc_bits,
                engine=engine, materialize=not overlap_exec)
            n_tiles += 1
            if not overlap_exec:
                _store(vals, p_tiles[pi], m_tiles[mi])
                continue
            # tile t's MAC+reduce is in flight (asynchronous dispatch): run
            # tile t+1's load stage NOW — pack the next pass's filter columns
            # and window rows while t computes — then retire tile t-1, whose
            # result the device finished before starting t
            if t + 1 < len(order):
                npi, nmi = order[t + 1]
                _filter_tile(nmi)
                _x_tile(npi)
            if pending is not None:
                _store(*pending)
            pending = (vals, p_tiles[pi], m_tiles[mi])
        if pending is not None:
            _store(*pending)
    total_cycles = per_dot * rows_total * M_live  # one dot per live (b,e,f,m)
    # PR 7: checksum verifications + re-executed tiles charge the same §III
    # formulas as the real work — an additive term, zero when unchecked
    total_cycles += integrity_cycles + reexec_cycles

    with TraceAnnotation("nc.conv.epilogue"):
        if zero_mask is not None:
            # pruned passes: an all-zero filter's dot is the affine
            # constant zw * sum_k(x_k) — exact, no engine lanes clocked
            row_sums = win_flat.sum(axis=1, dtype=np.int64)
            out[:, zero_mask] = zw_int * row_sums[:, None]
        # affine-zero-point correction (done by the accumulating requant
        # step in-cache; exact integer identity — zero points are per image)
        sx = win.sum(axis=-1)  # (B, E, F)
        sw = wq.sum(axis=(0, 1, 2))  # (M,)
        zx = zxs[:, None, None, None]
        acc = (
            out.reshape(B, E, F, M)
            - int(w_qp.zero_point) * sx[..., None]
            - zx * sw[None, None, None, :]
            + K * zx * int(w_qp.zero_point)
        )
        result = jnp.asarray(acc if batched else acc[0], jnp.int32)
    if not return_stats:
        return result, total_cycles
    with TraceAnnotation("nc.accounting"):
        # separable zero-operand count:
        # sum_k (#zero-free windows_k) * (#zero-free w_k)
        cx = (win_flat != 0).sum(axis=0).astype(np.int64)  # (K,)
        cw = (w_rows != 0).sum(axis=0).astype(np.int64)  # (K,)
        live = int((cx * cw).sum())
        stats = ConvStats(
            lanes=rows_total * M * K,
            zero_operand_lanes=rows_total * M * K - live,
            tiles=n_tiles,
            tile_pixels=tile_rows,
            tile_filters=tile_filters,
            serial_passes=eff_plan.serial_passes,
            engine_words_total=bs.SKIP_STATS.words_total - skip0_words,
            engine_words_skipped=bs.SKIP_STATS.words_skipped - skip0_skipped,
            batch=B,
            filter_loads=1,
            zero_filters=M - M_live,
            skipped_passes=eff_plan.skipped_passes,
            overlap=overlap_exec and not checked,  # checked path runs serially
            integrity=integrity_on,
            verify_passes=verify_passes,
            reexec_passes=reexec_passes,
            faults_detected=faults_detected,
            integrity_cycles=integrity_cycles,
            reexec_cycles=reexec_cycles,
            quarantined_slices=eff_plan.quarantined_slices,
            compressed=compressed_exec,
            csr_payload_bytes=csr_bytes[0],
            csr_index_bytes=csr_bytes[1],
            plan=eff_plan,
        )
    return result, total_cycles, stats


def _pool_cycles(kind: str, B: int, E: int, F: int, window: int):
    """Emulated cycles of a ``kind`` ("max" or "avg") pool with B x E x F
    output lane groups: the closed forms the bit-serial walk asserts step
    by step (one subtract + masked copy per max step; the widening log
    tree plus one 8-bit divide per average)."""
    w2 = window * window
    if kind == "max":
        return (w2 - 1) * (bs.add_cycles(8) + 8 + 1) * B * E * F
    return int(B * E * F * (bs.reduce_cycles(w2, 8) + bs.div_cycles(8)))


@functools.partial(jax.jit,
                   static_argnames=("kind", "window", "stride", "padding"))
def _pool_device(x, *, kind: str, window: int, stride: int, padding: str):
    """One compiled reduce-window over a ``[B, H, W, C]`` uint8 batch,
    byte-identical to the bit-serial walk: max pads with 0 (the uint8
    minimum); the average divides the int32 window sum by the
    pad-excluded population, rounded."""
    _, H, W, _ = x.shape
    pads = ((0, 0), (0, 0), (0, 0), (0, 0))
    if padding == "SAME":
        pads = ((0, 0), _same_pad(H, window, stride),
                _same_pad(W, window, stride), (0, 0))
    dims, strides = (1, window, window, 1), (1, stride, stride, 1)
    op = lax.max if kind == "max" else lax.add
    out = lax.reduce_window(x.astype(jnp.int32), np.int32(0), op, dims,
                            strides, pads)
    if kind == "avg":
        counts = lax.reduce_window(jnp.ones((1, H, W, 1), jnp.int32),
                                   np.int32(0), lax.add, dims, strides, pads)
        # (sum + count // 2) // count: an int32 divide by an array costs
        # the TPU compiler about a minute per shape, so the float32
        # quotient is taken and corrected by one step to the exact floor
        num = out + counts // 2
        quo = jnp.floor(num.astype(jnp.float32)
                        / counts.astype(jnp.float32)).astype(jnp.int32)
        quo = quo - (quo * counts > num) + ((quo + 1) * counts <= num)
        out = jnp.clip(quo, 0, 255)
    return out.astype(jnp.uint8)


def _pool_compiled(kind: str, x_q, window: int, stride: int, padding: str):
    """The pool as one device call: the uint8 activation goes to the
    device, :func:`_pool_device` reduces it, the cycles come from
    :func:`_pool_cycles`."""
    x = jnp.asarray(x_q, jnp.uint8)
    batched = x.ndim == 4
    out = _pool_device(x if batched else x[None], kind=kind, window=window,
                       stride=stride, padding=padding)
    B, E, F, _ = out.shape
    return (out if batched else out[0]), _pool_cycles(kind, B, E, F, window)


def nc_maxpool2d(x_q: jax.Array, window: int, stride: int,
                 padding: str = "VALID", engine: str | None = None):
    """uint8 max pooling via subtract + MSB-masked copies (§IV-D).

    Accepts ``[H, W, C]`` or ``[B, H, W, C]``; all B x E x F x C output
    lanes advance in lockstep through the window^2 - 1 sequential max
    steps (cycle count stays per-pixel, as the per-pixel formulation
    reported it).  On a backend with ``compiled_pools`` (``engine``) the
    values come from one compiled reduce-window call and the cycles from
    the walk's closed form; without an engine, or on ``host``, the
    bit-serial walk runs."""
    if engine is not None and _backends.get_backend(engine).compiled_pools:
        return _pool_compiled("max", x_q, window, stride, padding)
    xin = np.asarray(x_q, np.int64)
    batched = xin.ndim == 4
    xq = xin if batched else xin[None]
    if padding == "SAME":
        ph = _same_pad(xq.shape[1], window, stride)
        pw = _same_pad(xq.shape[2], window, stride)
        xq = np.pad(xq, ((0, 0), ph, pw, (0, 0)))  # uint8 min
    win, E, F = _extract_windows_batch(xq, window, window, stride)
    B, C = xq.shape[0], xq.shape[3]
    win = win.reshape(B, E, F, window * window, C)
    cur = bs.pack_values(win[:, :, :, 0].astype(np.uint32), 8)
    cycles = 0
    for t in range(1, window * window):
        nxt = bs.pack_values(win[:, :, :, t].astype(np.uint32), 8)
        cur, c = bs.bitserial_max(cur, nxt)
        cur = cur[:8]
        cycles += c * B * E * F
    out = bs.unpack_values(cur)  # (B, E, F, C)
    return jnp.asarray(out if batched else out[0], jnp.uint8), cycles


def nc_avgpool2d(x_q: jax.Array, window: int, stride: int,
                 padding: str = "VALID", engine: str | None = None):
    """uint8 average pooling: in-array window-sum via the §III-D log tree,
    then the §III-C bit-serial divide (rounded; SAME padding divides by the
    pad-excluded window population, matching the float reference — exact
    under the affine identity only for zero_point == 0, which holds for
    every post-ReLU activation in the §IV-D pipeline).

    Accepts ``[H, W, C]`` or ``[B, H, W, C]``.  Cycles per output lane
    group: the widening sum tree over the window plus one 8-bit divide.
    ``engine`` selects the compiled call as in :func:`nc_maxpool2d`."""
    if engine is not None and _backends.get_backend(engine).compiled_pools:
        return _pool_compiled("avg", x_q, window, stride, padding)
    xin = np.asarray(x_q, np.int64)
    batched = xin.ndim == 4
    xq = xin if batched else xin[None]
    B, H, W, C = xq.shape
    ones = np.ones((H, W, 1), np.int64)
    if padding == "SAME":
        ph = _same_pad(H, window, stride)
        pw = _same_pad(W, window, stride)
        xq = np.pad(xq, ((0, 0), ph, pw, (0, 0)))
        ones = np.pad(ones, (ph, pw, (0, 0)))
    win, E, F = _extract_windows_batch(xq, window, window, stride)
    w2 = window * window
    # reduce axis last: (B, E, F, C, W2) rows of the window population
    rows = win.reshape(B, E, F, w2, C).transpose(0, 1, 2, 4, 3)
    pp = bs.pack_values(rows.astype(np.uint32), 8, row_align=True)
    red, c_red = bs.bitserial_reduce(pp)
    sums = bs.unpack_values(red)[..., 0]  # (B, E, F, C)
    counts, _, _ = _extract_windows(ones, window, window, stride)
    counts = counts.reshape(E, F, w2, 1).sum(axis=2)  # (E, F, 1)
    out = (sums + counts // 2) // counts  # rounded integer divide
    cycles = int(B * E * F * (c_red + bs.div_cycles(8)))
    out = np.clip(out, 0, 255)
    return jnp.asarray(out if batched else out[0], jnp.uint8), cycles


def nc_minmax(x_q, bits: int = 32, signed: bool = False):
    """§IV-D in-cache dynamic range: min AND max of quantized values via a
    bit-serial log tree (subtract + tag-masked copy per halving step), run
    entirely in packed word space — only the two scalars per row leave the
    cache, exactly the "two numbers sent to the CPU" of the paper's
    quantization pipeline.

    ``x_q``: integer array whose LAST axis is reduced; leading axes (e.g.
    the image batch) are independent rows advancing in lockstep.  Rows are
    pre-padded to the next power of two with copies of their first lane so
    padding never pollutes the min.  ``signed`` treats values as
    ``bits``-wide two's complement (the int32 accumulator case): the sign
    plane is biased on the way in and the scalars un-biased on the way out
    (one extra cycle each way — an XOR pass on a single plane).

    Returns ``(mins, maxs, cycles)`` — arrays shaped like the leading
    axes — with ``cycles == bitserial.minmax_cycles(K, bits)``
    (+2 when ``signed``); all rows share the one lockstep tree.
    """
    x = np.asarray(x_q)
    lead = x.shape[:-1]
    K = x.shape[-1] if x.ndim else 1
    rows = x.reshape(-1, K).astype(np.int64)
    bias = (1 << (bits - 1)) if signed else 0
    u = ((rows + bias) & ((1 << bits) - 1)).astype(np.uint64)
    P = 1 << max(0, (K - 1).bit_length())
    padded = np.empty((u.shape[0], P), np.uint64)
    padded[:, :K] = u
    padded[:, K:] = u[:, :1]  # neutral pad: a copy of a real lane
    pp = bs.pack_values(padded, bits, row_align=True)
    (mn_pp, mx_pp), cycles = bs.bitserial_minmax(pp)
    mn = bs.unpack_values(mn_pp).reshape(-1) - bias
    mx = bs.unpack_values(mx_pp).reshape(-1) - bias
    if signed:
        cycles += 2  # sign-plane bias in + un-bias out
    return mn.reshape(lead), mx.reshape(lead), cycles


def nc_relu_requant(
    acc: jax.Array, real_multiplier: float, out_zp: int = 0
) -> jax.Array:
    """ReLU on the int32 accumulator then fixed-point requant to uint8 —
    the in-cache epilogue of every conv layer."""
    acc = jnp.maximum(acc, 0)  # MSB-masked zero write
    m, s = q.fixed_point_multiplier(jnp.float32(real_multiplier))
    return q.requantize_fixedpoint(acc, m, s, zero_point=out_zp).astype(jnp.uint8)


def nc_fc(x: jax.Array, w: jax.Array,
          x_qp: q.QuantParams | Sequence[q.QuantParams],
          w_qp: q.QuantParams, **conv_kwargs):
    """FC as a 1x1 conv over a 1x1 'image' (§IV-D).

    ``x``: [K] or batched [B, K] (each row one image's feature vector —
    the batch folds into the conv's row axis); ``plan``, ``engine`` and the
    tiling keywords pass through to :func:`nc_conv2d`."""
    xa = np.asarray(x)
    w4 = np.asarray(w)[None, None, :, :]
    if xa.ndim == 2:  # batched: [B, K] -> [B, 1, 1, K] image batch
        res = nc_conv2d(xa[:, None, None, :], w4, x_qp, w_qp, **conv_kwargs)
        if len(res) == 3:
            out, cycles, stats = res
            return out[:, 0, 0], cycles, stats
        out, cycles = res
        return out[:, 0, 0], cycles
    res = nc_conv2d(xa[None, None, :], w4, x_qp, w_qp, **conv_kwargs)
    if len(res) == 3:
        out, cycles, stats = res
        return out[0, 0], cycles, stats
    out, cycles = res
    return out[0, 0], cycles
