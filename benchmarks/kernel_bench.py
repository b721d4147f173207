"""Kernel benches: fused W8A8 and bit-serial GEMM vs fp32 XLA dot, plus the
word-packed emulation engine.

CPU wall-times are informational (TPU is the target); the structural
result is the plane-count scaling of the bit-serial kernel — the paper's
precision-proportional-latency property (Stripes-style) — measured as
HLO FLOPs of the lowered kernel, which *is* hardware-portable.  The
``emulation/*`` section times the packed bit-plane engine
(core/bitserial.py + core/nc_layers.py): 32 lanes per uint32 word, one
bitwise op per 32 lanes.

Besides the printed CSV rows, every result is appended to the module-level
``RECORDS`` list ({op, shape, us_per_call, derived}) so benchmarks/run.py
can dump a machine-readable ``BENCH_kernels.json`` perf baseline.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, timed
from repro.core.quantize import choose_qparams_symmetric, quantize, quantize_per_channel
from repro.distributed.hlo_analysis import xla_cost_analysis
from repro.kernels import ops as K

RECORDS: list[dict] = []

# op name -> zero-arg callable returning a fresh us_per_call measurement.
# benchmarks/run.py re-times flagged regressions through this registry
# (median of 3) before recording them, so the known kernel/f32_dot
# host-load flap (SPEEDUP_NOTES["host_noise"]) stops producing phantom
# notes.regressions entries.  Only the subsecond kernel/* ops register —
# re-timing a multi-second emulation record would double the bench wall.
RETIMERS: dict[str, object] = {}


def _rec(name: str, us: float, shape: str, derived: str = "") -> str:
    RECORDS.append({"op": name, "shape": shape, "us_per_call": round(us, 2),
                    "derived": derived})
    return row(name, us, derived or shape)


def _timed_rec(name: str, call, iters: int, shape: str,
               derived: str = "") -> str:
    """Time ``call``, record it, and register a retimer for it."""
    _, us = timed(call, iters=iters)
    RETIMERS[name] = lambda: timed(call, iters=iters)[1]
    return _rec(name, us, shape, derived)


def _emulation_rows():
    """Wall-time the packed bit-plane engine on emulation-suite shapes."""
    from repro.core import bitserial as bs
    from repro.core import nc_layers as nc
    from repro.core import quantize as q

    out = []
    rng = np.random.default_rng(0)

    # element-wise MAC over 4096 packed lanes (128 uint32 words / plane)
    a = rng.integers(0, 256, size=(4096,), dtype=np.uint32)
    b = rng.integers(0, 256, size=(4096,), dtype=np.uint32)
    pa, pb = bs.bitplane_pack(a, 8), bs.bitplane_pack(b, 8)
    acc = np.zeros((24, 4096), np.uint8)
    _, us = timed(lambda: bs.bitserial_mac(acc, pa, pb), iters=15)
    out.append(_rec("emulation/mac8_4096lanes", us, "4096 lanes x 8b MAC",
                    "packed words: 128 uint32/plane"))

    # log-tree reduction of 4096-lane rows of 24-bit partial sums.  The
    # micro-op is BATCHED (64 rows, one lockstep tree call) and the operand
    # packs row-aligned outside the timed body: a single cold row is pure
    # per-call python overhead (it times the interpreter, not the engine —
    # the old B=1 record cost the same wall time as these 64 rows and kept
    # flagging phantom ~1.4x regressions), while per-row time of the
    # batched call is the number the layer pipeline actually sees.
    rows64 = rng.integers(0, 1 << 16, size=(64, 4096), dtype=np.uint32)
    pp64 = bs.pack_values(rows64, 24, row_align=True)
    _, us = timed(lambda: bs.bitserial_reduce(pp64), iters=15)
    out.append(_rec("emulation/reduce_64x4096lanes", us, "64 rows x 4096, 24b",
                    f"{us / 64:.1f} us/row; "
                    f"{bs.reduce_cycles(4096, 24)} modeled cycles/row"))

    # §IV-D in-cache min/max over an int32 accumulator tensor
    acc = rng.integers(-(1 << 24), 1 << 24, size=(16384,)).astype(np.int64)
    _, us = timed(lambda: nc.nc_minmax(acc, bits=32, signed=True), iters=15)
    out.append(_rec("emulation/nc_minmax_16klanes", us, "16384 -> 2 scalars",
                    f"{bs.minmax_cycles(16384, 32) + 2} modeled cycles"))

    # full conv layer through the array model (all pixels/filters in lockstep)
    x = rng.normal(size=(14, 14, 8)).astype(np.float32)
    w = rng.normal(size=(3, 3, 8, 16)).astype(np.float32) * 0.5
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    w_qp = q.choose_qparams(jnp.float32(w.min()), jnp.float32(w.max()))
    _, us = timed(lambda: nc.nc_conv2d(jnp.asarray(x), jnp.asarray(w),
                                       x_qp, w_qp), iters=5)
    out.append(_rec("emulation/nc_conv2d", us, "14x14x8 * 3x3x8x16",
                    "12x12x16 outputs, one packed MAC+reduce"))

    # the same conv with a 4-image batch folded into the packed lane axis,
    # through the engine nc_forward defaults to at batch >= 2: the bucketed
    # jit kernel (timed() warms once, so the bucket compile amortizes away
    # exactly as it does across a serving run's batches)
    xb = rng.normal(size=(4, 14, 14, 8)).astype(np.float32)
    _, us_b = timed(lambda: nc.nc_conv2d(xb, jnp.asarray(w),
                                         [x_qp] * 4, w_qp, engine="jit"),
                    iters=5)
    out.append(_rec("emulation/nc_conv2d_batch4", us_b, "4x 14x14x8 * 3x3x8x16",
                    f"batch in lane axis, bucketed-jit engine; "
                    f"{us_b / 4:.0f} us/img vs {us:.0f} single host"))

    # max pooling via subtract + tag-masked copies (sub-ms op: extra iters
    # so the min actually rejects this host's CPU-steal spikes)
    xq = rng.integers(0, 256, size=(28, 28, 8), dtype=np.uint8)
    _, us = timed(lambda: nc.nc_maxpool2d(jnp.asarray(xq), 2, 2), iters=15)
    out.append(_rec("emulation/nc_maxpool2d", us, "28x28x8 w2 s2",
                    "14x14x8 lanes in lockstep"))

    # end-to-end: reduced Inception v3 stem through the emulation (tiled,
    # packed-resident; per-layer cycles reported by nc_forward)
    import jax as _jax
    from repro.models import inception
    cfg = inception.reduced_config(img=63, width_div=8, classes=8, stages=())
    params = inception.init_params(_jax.random.PRNGKey(0), config=cfg)
    img = _jax.random.uniform(_jax.random.PRNGKey(1), (63, 63, 3), jnp.float32)
    (_, report), us = timed(
        lambda: inception.nc_forward(params, img, config=cfg), iters=1)
    out.append(_rec("emulation/inception_stem", us, "63px /8 widths stem",
                    f"{len(report.layers)} layers, "
                    f"{report.total_emulated_cycles} emulated cycles"))
    out.extend(_sparsity_rows())
    out.extend(_overlap_rows())
    out.extend(_compressed_rows())
    return out


def _sparsity_rows():
    """Dense-vs-sparse record pair: reduced_config at batch 4 with a fixed
    50% filter pruning, executed dense and through the sparse schedule
    (pruned pass list).  GATE: sparse wall time above dense fails the run
    — the pruned pass list must actually be cheaper, not just modeled so.
    Both runs are timed back to back in this process, so the shared-host
    noise in SPEEDUP_NOTES["host_noise"] largely cancels; logits are also
    asserted byte-identical, making this a correctness gate too."""
    import time

    import jax as _jax
    from repro.core import schedule as nc_sched
    from repro.models import inception

    cfg = inception.reduced_config()
    params = inception.init_params(_jax.random.PRNGKey(0), config=cfg)
    wpack = inception.prune_wpack(
        inception.prepare_conv_weights(params, cfg), 0.5)
    xb = np.asarray(_jax.random.uniform(
        _jax.random.PRNGKey(1), (4, cfg.img, cfg.img, 3), jnp.float32))
    specs = inception.specs(cfg)

    # interleaved min-of-3 (first pass also warms the bucketed-jit engine
    # caches): the host_noise drift hits dense and sparse alike, and the
    # min rejects CPU-steal spikes the way timed() does for every other
    # record — the gate must not flap on a loaded container
    wall_d = wall_s = float("inf")
    logits_d = logits_s = None
    for _ in range(3):
        t0 = time.perf_counter()
        logits_d, rep_d = inception.nc_forward(params, xb, config=cfg,
                                               wpack=wpack)
        wall_d = min(wall_d, time.perf_counter() - t0)
        t0 = time.perf_counter()
        logits_s, rep_s = inception.nc_forward(
            params, xb, config=cfg, wpack=wpack,
            schedule=nc_sched.plan_network(
                specs, batch=4,
                occupancy=inception.network_occupancy(wpack, cfg)))
        wall_s = min(wall_s, time.perf_counter() - t0)
    if not np.array_equal(np.asarray(logits_d), np.asarray(logits_s)):
        raise RuntimeError("sparsity gate: sparse nc_forward logits diverge "
                           "from dense on the same pruned weights")
    if wall_s > wall_d:
        raise RuntimeError(
            f"sparsity gate: sparse wall time {wall_s * 1e3:.0f} ms exceeds "
            f"dense {wall_d * 1e3:.0f} ms on the fixed 50% pruning")
    zero_filters = sum(l.zero_filters for l in rep_s.layers)
    out = [
        _rec("emulation/nc_forward_b4_pruned50_dense", wall_d * 1e6,
             f"{cfg.img}px /4 widths, batch 4, 50% filters zero",
             f"{wall_d / 4 * 1e3:.0f} ms/img; engine runs every filter"),
        _rec("emulation/nc_forward_b4_pruned50_sparse", wall_s * 1e6,
             f"{cfg.img}px /4 widths, batch 4, 50% filters zero",
             f"{wall_s / 4 * 1e3:.0f} ms/img; {zero_filters} filters pruned "
             f"from the pass list, {wall_d / wall_s:.2f}x vs dense"),
    ]
    return out


def _kernel_rows():
    """The subsecond ``kernel/*`` subset (every op registers a retimer).

    This is also the whole of ``python -m benchmarks.run --quick``: fast
    enough for a CI pre-gate, diffed against the same baseline."""
    out = []
    k1, k2 = jax.random.split(jax.random.key(0))
    M, Kdim, N = 256, 512, 256
    x = jax.random.normal(k1, (M, Kdim), jnp.float32)
    w = jax.random.normal(k2, (Kdim, N), jnp.float32) * 0.2
    qp = choose_qparams_symmetric(jnp.max(jnp.abs(x)))
    xq = quantize(x, qp)

    f32 = jax.jit(lambda a, b: a @ b)
    out.append(_timed_rec("kernel/f32_dot",
                          lambda: jax.block_until_ready(f32(x, w)), 15,
                          f"{M}x{Kdim}x{N}"))

    wq, ws = quantize_per_channel(w)
    q8 = jax.jit(lambda a, b: K.quant_matmul(a, b, qp.scale, ws.reshape(-1)))
    out.append(_timed_rec("kernel/w8a8_fused",
                          lambda: jax.block_until_ready(q8(xq, wq)), 15,
                          f"{M}x{Kdim}x{N}", "int8 MXU path (xla ref on cpu)"))

    base_flops = None
    for bits in (8, 4, 2, 1):
        wqb, wsb = quantize_per_channel(w, bits=bits)
        planes = K.pack_weights(wqb.astype(jnp.int32), bits)  # byte-packed
        fn = jax.jit(lambda a, p, bits=bits, wsb=wsb: K.bitserial_matmul(
            a, p, qp.scale, wsb.reshape(-1), n_bits=bits))
        flops = xla_cost_analysis(fn.lower(xq, planes).compile()).get("flops", 0)
        if bits == 8:
            base_flops = flops or 1
        out.append(_timed_rec(
            f"kernel/bitserial_{bits}b",
            lambda fn=fn, planes=planes: jax.block_until_ready(fn(xq, planes)),
            9, f"{M}x{Kdim}x{N}",
            f"{bits} planes byte-packed; HLO flops "
            f"{flops/base_flops:.2f}x of 8b"))

    # W4A4: byte-packing extended to the activations (2 elements/byte,
    # 2 half-K MXU passes per plane) — flops still plane-proportional
    from repro.kernels import ref as kref
    x4 = jax.random.randint(k1, (M, Kdim), -8, 8, jnp.int8)
    w4, ws4 = quantize_per_channel(w, bits=4)
    xp4 = kref.pack_activation_nibbles(x4)
    wp4 = K.pack_weights(w4.astype(jnp.int32), 4)
    fn4 = jax.jit(lambda a, p: K.bitserial_matmul_a4(
        a, p, qp.scale, ws4.reshape(-1), k=Kdim))
    flops4 = xla_cost_analysis(fn4.lower(xp4, wp4).compile()).get("flops", 0)
    out.append(_timed_rec("kernel/bitserial_w4a4_packed_act",
                          lambda: jax.block_until_ready(fn4(xp4, wp4)), 9,
                          f"{M}x{Kdim}x{N}",
                          f"2 elems/byte activations; HLO flops "
                          f"{flops4/base_flops:.2f}x of 8b"))
    return out


def _overlap_rows():
    """Serial-vs-overlapped record pair: a batch-4 reduced config executed
    through the PR 3/4 serial plan and through the double-buffered plan
    (``plan_network(..., overlap=True)``: pass k+1's packed filter columns
    prefetch while pass k's MAC+reduce runs).  The workload is the stem at
    ``width_div=2`` on a ``scaled(4)`` geometry — at the full 35 MB array
    every reduced-config layer is single-pass and the §IV-E legality rule
    correctly denies overlap everywhere (nothing to hide), so the measured
    pair runs where multi-pass layers carry ~3/4 of the modeled time and
    the double buffer actually executes.  GATE: overlapped wall time must
    stay within :func:`benchmarks.common.overlap_wall_slack` of serial —
    no-loss where a second core gives the prefetch real concurrency,
    parity-within-noise on a single-core container (total work is
    conserved there; the model's floor for the measured win is zero
    either way, since overlap only re-times the copies, never the
    computed values); logits are asserted byte-identical, making this a
    correctness gate too.  A third record runs the 50%-pruned sparse
    schedule WITH overlap (pruning drops passes first, overlap hides the
    survivors' loads), gated locally against its own sparse-serial
    timing.  Interleaved min-of-3 as in :func:`_sparsity_rows` so host
    noise cancels."""
    import time

    import jax as _jax
    from benchmarks.common import overlap_wall_slack
    from repro.core import schedule as nc_sched
    from repro.core.cache_geometry import XEON_E5_35MB
    from repro.models import inception

    cfg = inception.reduced_config(width_div=2, stages=())
    geom = XEON_E5_35MB.scaled(4)
    params = inception.init_params(_jax.random.PRNGKey(0), config=cfg)
    wpack = inception.prepare_conv_weights(params, cfg)
    xb = np.asarray(_jax.random.uniform(
        _jax.random.PRNGKey(1), (4, cfg.img, cfg.img, 3), jnp.float32))
    specs = inception.specs(cfg)

    def plan(wp=None, **decisions):
        """The batch-4 schedule with the weights ``wp``'s occupancy."""
        occ = None if wp is None else inception.network_occupancy(wp, cfg)
        return nc_sched.plan_network(specs, geom, batch=4, occupancy=occ,
                                     **decisions)

    wall_s = wall_o = float("inf")
    logits_srl = logits_ov = None
    rep_o = None
    for _ in range(3):
        t0 = time.perf_counter()
        logits_srl, _ = inception.nc_forward(params, xb, config=cfg,
                                             geom=geom, wpack=wpack)
        wall_s = min(wall_s, time.perf_counter() - t0)
        t0 = time.perf_counter()
        logits_ov, rep_o = inception.nc_forward(params, xb, config=cfg,
                                                geom=geom, wpack=wpack,
                                                schedule=plan(overlap=True))
        wall_o = min(wall_o, time.perf_counter() - t0)
    if not np.array_equal(np.asarray(logits_srl), np.asarray(logits_ov)):
        raise RuntimeError("overlap gate: overlapped nc_forward logits "
                           "diverge from serial on the same weights")
    slack = overlap_wall_slack()
    if wall_o > slack * wall_s:
        raise RuntimeError(
            f"overlap gate: overlapped wall time {wall_o * 1e3:.0f} ms "
            f"exceeds {slack:.2f}x serial {wall_s * 1e3:.0f} ms at batch "
            f"4 — the double buffer must be free, not a cost")
    n_ov = sum(1 for l in rep_o.layers if l.overlap)
    if n_ov == 0:
        raise RuntimeError("overlap gate: no layer executed double-buffered "
                           "— the record pair would be measuring noise")
    shape = f"{cfg.img}px /2 widths stem, batch 4, 1/4-scale array"
    out = [
        _rec("emulation/nc_forward_b4_serial", wall_s * 1e6, shape,
             f"{wall_s / 4 * 1e3:.0f} ms/img; load-then-compute per pass"),
        _rec("emulation/nc_forward_b4_overlap", wall_o * 1e6, shape,
             f"{wall_o / 4 * 1e3:.0f} ms/img; {n_ov} layers prefetch "
             f"filters under MAC+reduce, {wall_s / wall_o:.2f}x vs serial"),
    ]

    # pruning x overlap: the sparse schedule's surviving passes still
    # double-buffer; gate against sparse-serial so the comparison point
    # shares the pruned pass list
    wp = inception.prune_wpack(wpack, 0.5)
    wall_ps = wall_po = float("inf")
    logits_ps = logits_po = None
    for _ in range(3):
        t0 = time.perf_counter()
        logits_ps, _ = inception.nc_forward(params, xb, config=cfg,
                                            geom=geom, wpack=wp,
                                            schedule=plan(wp))
        wall_ps = min(wall_ps, time.perf_counter() - t0)
        t0 = time.perf_counter()
        logits_po, _ = inception.nc_forward(params, xb, config=cfg,
                                            geom=geom, wpack=wp,
                                            schedule=plan(wp, overlap=True))
        wall_po = min(wall_po, time.perf_counter() - t0)
    if not np.array_equal(np.asarray(logits_ps), np.asarray(logits_po)):
        raise RuntimeError("overlap gate: sparse+overlap logits diverge "
                           "from sparse-serial on the same pruned weights")
    if wall_po > slack * wall_ps:
        raise RuntimeError(
            f"overlap gate: sparse+overlap wall time {wall_po * 1e3:.0f} ms "
            f"exceeds {slack:.2f}x sparse-serial {wall_ps * 1e3:.0f} ms "
            f"at batch 4")
    out.append(_rec(
        "emulation/nc_forward_b4_pruned50_overlap", wall_po * 1e6,
        f"{shape}, 50% pruned",
        f"{wall_po / 4 * 1e3:.0f} ms/img; skipped passes first, loads "
        f"hidden second, {wall_ps / wall_po:.2f}x vs sparse-serial"))
    return out


def _compressed_rows():
    """Compressed-vs-dense record pair (PR 8): reduced_config at batch
    4 with the fixed 50% filter pruning, executed from the dense filter
    store (every filter runs) and from the CSR bit-plane store through
    the compressed sparse schedule.  GATES, any failure raises like the
    sparsity/overlap gates: (1) the compressed schedule must keep no more
    than 0.55x the dense schedule's ``filter_bytes_loaded`` resident —
    the modeled §IV-A residency win the simulator credits exactly; (2)
    compressed wall time must not regress past dense; (3) logits must be
    byte-identical (decompression scatters live columns into zero words,
    the multiply identity).  Interleaved min-of-3 as in
    :func:`_sparsity_rows` so shared-host noise cancels."""
    import time

    import jax as _jax
    from repro.core import schedule as nc_sched
    from repro.core.cache_geometry import XEON_E5_35MB
    from repro.models import inception

    cfg = inception.reduced_config()
    params = inception.init_params(_jax.random.PRNGKey(0), config=cfg)
    wpack = inception.prune_wpack(
        inception.prepare_conv_weights(params, cfg), 0.5)
    xb = np.asarray(_jax.random.uniform(
        _jax.random.PRNGKey(1), (4, cfg.img, cfg.img, 3), jnp.float32))

    # modeled residency gate first — deterministic, no timing noise
    specs = inception.inception_v3_specs(cfg)
    occ = inception.network_occupancy(wpack, cfg)
    dense_plan = nc_sched.plan_network(specs, XEON_E5_35MB, batch=4)
    comp_plan = nc_sched.plan_network(specs, XEON_E5_35MB, batch=4,
                                      occupancy=occ, compressed=True)
    fbl_ratio = comp_plan.filter_bytes_loaded / dense_plan.filter_bytes_loaded
    if fbl_ratio > 0.55:
        raise RuntimeError(
            f"compression gate: compressed schedule keeps {fbl_ratio:.3f}x "
            f"the dense filter bytes resident at 50% pruning — must be "
            f"<= 0.55x")

    wall_d = wall_c = float("inf")
    logits_d = logits_c = None
    for _ in range(3):
        t0 = time.perf_counter()
        logits_d, _ = inception.nc_forward(params, xb, config=cfg,
                                           wpack=wpack)
        wall_d = min(wall_d, time.perf_counter() - t0)
        t0 = time.perf_counter()
        logits_c, rep_c = inception.nc_forward(
            params, xb, config=cfg, wpack=wpack,
            schedule=nc_sched.plan_network(
                specs, XEON_E5_35MB, batch=4,
                occupancy=inception.network_occupancy(wpack, cfg),
                compressed=True))
        wall_c = min(wall_c, time.perf_counter() - t0)
    if not np.array_equal(np.asarray(logits_d), np.asarray(logits_c)):
        raise RuntimeError("compression gate: CSR-store nc_forward logits "
                           "diverge from the dense store on the same "
                           "pruned weights")
    if wall_c > wall_d:
        raise RuntimeError(
            f"compression gate: compressed wall time {wall_c * 1e3:.0f} ms "
            f"exceeds dense {wall_d * 1e3:.0f} ms on the fixed 50% pruning")
    shape = f"{cfg.img}px /4 widths, batch 4, 50% filters zero"
    return [
        _rec("emulation/nc_forward_b4_pruned50_densestore", wall_d * 1e6,
             shape, f"{wall_d / 4 * 1e3:.0f} ms/img; full dense residency "
             f"({dense_plan.filter_bytes_loaded} filter bytes)"),
        _rec("emulation/nc_forward_b4_pruned50_csr", wall_c * 1e6, shape,
             f"{wall_c / 4 * 1e3:.0f} ms/img; CSR bit-plane store, "
             f"{fbl_ratio:.3f}x dense residency (credit "
             f"{comp_plan.residency_credit_bytes} B/batch), "
             f"{wall_d / wall_c:.2f}x vs dense"),
    ]


def _compressed_smoke_rows():
    """``--quick`` compressed smoke (PR 8): a small half-pruned conv
    executed from the CSR bit-plane store — GATE: byte-identical to the
    dense store.  Subsecond, registers a retimer like the kernel rows."""
    from repro.core import nc_layers as nc
    from repro.core import quantize as q
    from repro.core import schedule as nc_sched
    from repro.core.mapper import LayerSpec

    rng = np.random.default_rng(0)
    wq = rng.integers(0, 256, size=(3, 3, 4, 16)).astype(np.uint8)
    wq[..., 8:] = 7  # half the filters at the zero point
    w_qp = q.QuantParams(scale=np.float32(0.05), zero_point=7)
    x = rng.uniform(-1, 1, (2, 10, 10, 4)).astype(np.float32)
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    spec = LayerSpec(name="nc_conv2d", kind="conv", H=12, R=3, S=3, C=4,
                     M=16, E=10)

    def csr_conv():
        """The conv under its detected occupancy and the CSR store."""
        occ = nc_sched.LayerOccupancy.from_filter_rows(
            wq.reshape(-1, 16).T, w_qp.bits, 7)
        plan = nc_sched.plan_layer(spec, batch=2, occupancy=occ,
                                   compressed=True)
        return nc.nc_conv2d(x, wq, [x_qp] * 2, w_qp, padding="SAME",
                            plan=plan)

    dense, _ = nc.nc_conv2d(x, wq, [x_qp] * 2, w_qp, padding="SAME")
    comp, _ = csr_conv()
    if not np.array_equal(np.asarray(comp), np.asarray(dense)):
        raise RuntimeError("compression smoke gate: CSR-store conv diverges "
                           "from the dense store")
    return [_timed_rec(
        "emulation/csr_conv_smoke", csr_conv, 5,
        "2x 10x10x4 * 3x3x4x16, 50% pruned",
        "CSR bit-plane store, byte-identical to dense")]


def _backend_rows():
    """Backend-registry record pair (PR 10): the batch-4 pruned-50
    reduced forward executed through the ``host`` and ``jit`` backends of
    core/backends.py — the same workload twice, differing ONLY in the
    ``engine=`` name.  GATES, raising like the sparsity/overlap gates:
    logits must be byte-identical across backends (the conformance
    contract of tests/test_backends.py at network scale) and both the
    emulated and modeled cycle totals must be bit-identical (backends
    re-time execution, never the model).  A third subsecond record times
    the ``pallas`` adapter on one packed dot (gated on byte-identity to
    host), so the interpret path's wall cost is tracked in
    the same baseline.  Interleaved min-of-2 so shared-host noise
    cancels; per-backend wall times are EXPECTED to differ — that is the
    point of the records — only values and cycles are gated."""
    import time

    import jax as _jax
    from repro.core import backends as nc_backends
    from repro.core import bitserial as bs
    from repro.core import nc_layers as nc
    from repro.core import schedule as nc_sched
    from repro.models import inception

    cfg = inception.reduced_config()
    params = inception.init_params(_jax.random.PRNGKey(0), config=cfg)
    wpack = inception.prune_wpack(
        inception.prepare_conv_weights(params, cfg), 0.5)
    xb = np.asarray(_jax.random.uniform(
        _jax.random.PRNGKey(1), (4, cfg.img, cfg.img, 3), jnp.float32))

    walls = {"host": float("inf"), "jit": float("inf")}
    logits: dict = {}
    reports: dict = {}
    for _ in range(2):
        for name in ("host", "jit"):
            t0 = time.perf_counter()
            logits[name], reports[name] = inception.nc_forward(
                params, xb, config=cfg, wpack=wpack, engine=name,
                schedule=nc_sched.plan_network(
                    inception.specs(cfg), batch=4,
                    occupancy=inception.network_occupancy(wpack, cfg)))
            walls[name] = min(walls[name], time.perf_counter() - t0)
    if not np.array_equal(np.asarray(logits["host"]),
                          np.asarray(logits["jit"])):
        raise RuntimeError("backend gate: jit-backend nc_forward logits "
                           "diverge from the host backend on the same "
                           "pruned weights")
    for field in ("total_emulated_cycles", "total_modeled_cycles"):
        if getattr(reports["host"], field) != getattr(reports["jit"], field):
            raise RuntimeError(
                f"backend gate: {field} differs across backends — backends "
                f"must re-time execution, never the cycle model")
    shape = f"{cfg.img}px /4 widths, batch 4, 50% filters zero"
    out = [
        _rec(f"backend/{name}/nc_forward_b4_pruned50", walls[name] * 1e6,
             shape,
             f"{walls[name] / 4 * 1e3:.0f} ms/img via the {name} backend; "
             f"logits and cycles gated identical across backends")
        for name in ("host", "jit")
    ]

    # interpret-mode adapter: one packed dot, byte-identity gated, timed
    # so the Pallas path's wall cost rides the same regression baseline
    rng = np.random.default_rng(0)
    xw = nc._pack_x_rows(
        rng.integers(0, 256, size=(13, 144)).astype(np.uint32), 8)
    ww = nc._pack_w_rows(
        rng.integers(0, 256, size=(8, 144)).astype(np.uint32), 8)
    ref, _ = bs.packed_dot_words(xw, ww, K=144, acc_bits=32, engine="host")
    nc_backends.dispatch_stats_clear()
    vals, _ = bs.packed_dot_words(xw, ww, K=144, acc_bits=32,
                                  engine="pallas")
    if not np.array_equal(np.asarray(vals), np.asarray(ref)):
        raise RuntimeError("backend gate: pallas packed dot "
                           "diverges from the host backend")
    if nc_backends.dispatch_stats()["pallas"]["native"] != 1:
        raise RuntimeError("backend gate: pallas delegated the "
                           "in-envelope dot to host — the record would "
                           "time the wrong body")
    out.append(_timed_rec(
        "backend/pallas/dot",
        lambda: bs.packed_dot_words(xw, ww, K=144, acc_bits=32,
                                    engine="pallas"), 3,
        "13x144 . 8x144 word grids",
        "interpret-mode Pallas GEMM, byte-identical to host"))
    return out


# checksum verification may not cost more than this multiple of the
# unchecked conv wall/cycles on the _fault_rows workload — the recorded
# bound the fault gate enforces (the modeled overhead is one extra lane
# group riding every pass: a few percent, so 1.5x leaves only noise room)
INTEGRITY_OVERHEAD_BOUND = 1.5


def _fault_rows():
    """Fault-sweep smoke gate (PR 7), quick enough for ``--quick``.

    A small conv runs once unchecked and once integrity-checked with no
    faults — GATE: logits byte-identical (verification never perturbs the
    data path) and both cycle and wall overhead under
    :data:`INTEGRITY_OVERHEAD_BOUND`.  Then every covered fault class
    (``faults.COVERED_CLASSES``) injects at rate 1 under integrity —
    GATE: every corrupted pass is detected (zero silent corruption) and
    the recovered logits are byte-identical to clean.  Any gate failure
    raises, failing the bench run like the sparsity/overlap gates."""
    import time

    from repro.core import faults, nc_layers as nc
    from repro.core import quantize as q
    from repro.core import schedule as nc_sched
    from repro.core.cache_geometry import XEON_E5_35MB
    from repro.core.mapper import LayerSpec

    rng = np.random.default_rng(0)
    geom = XEON_E5_35MB
    x = rng.uniform(-1, 1, (2, 10, 10, 4)).astype(np.float32)
    w = rng.uniform(-1, 1, (3, 3, 4, 16)).astype(np.float32)
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    w_qp = q.choose_qparams(jnp.float32(w.min()), jnp.float32(w.max()))

    checked = nc_sched.plan_layer(
        LayerSpec(name="nc_conv2d", kind="conv", H=12, R=3, S=3, C=4, M=16,
                  E=10), geom, batch=2, integrity=True)

    def conv(**kw):
        t0 = time.perf_counter()
        res = nc.nc_conv2d(x, w, [x_qp] * 2, w_qp, stride=1, padding="SAME",
                           geom=geom, **kw)
        return res, time.perf_counter() - t0

    (out0, cyc0), wall0 = conv()
    (out1, cyc1, st1), wall1 = conv(plan=checked, return_stats=True)
    if not np.array_equal(np.asarray(out0), np.asarray(out1)):
        raise RuntimeError("fault gate: integrity-checked conv logits "
                           "diverge from unchecked on clean execution")
    cyc_ratio = cyc1 / cyc0
    if cyc_ratio > INTEGRITY_OVERHEAD_BOUND:
        raise RuntimeError(
            f"fault gate: checksum cycle overhead {cyc_ratio:.2f}x exceeds "
            f"the {INTEGRITY_OVERHEAD_BOUND}x bound")
    out = [
        _rec("faults/conv_unchecked", wall0 * 1e6, "2x 10x10x4 * 3x3x4x16",
             f"{cyc0} emulated cycles"),
        _rec("faults/conv_integrity", wall1 * 1e6, "2x 10x10x4 * 3x3x4x16",
             f"{cyc1} emulated cycles, {cyc_ratio:.3f}x unchecked "
             f"(bound {INTEGRITY_OVERHEAD_BOUND}x)"),
    ]

    t0 = time.perf_counter()
    detected_total = 0
    for cls in faults.COVERED_CLASSES:
        if cls == "stuck":
            probe = faults.FaultState(
                faults.FaultProfile(n_slices=geom.n_slices))
            sid = probe.slice_for("nc_conv2d", 0)
            prof = faults.FaultProfile(seed=5, stuck_slices=(sid,),
                                       n_slices=geom.n_slices)
        else:
            kw = {"filter_flip": dict(filter_flip_rate=1.0),
                  "act_flip": dict(act_flip_rate=1.0),
                  "compute": dict(compute_rate=1.0)}[cls]
            prof = faults.FaultProfile(seed=5, n_slices=geom.n_slices, **kw)
        with faults.inject(prof) as fs:
            (outf, _, stf), _ = conv(plan=checked, return_stats=True)
        if fs.corrupt_attempts == 0:
            raise RuntimeError(f"fault gate: class {cls!r} injected nothing "
                               f"at rate 1 — the sweep is not covering it")
        if fs.detected != fs.corrupt_attempts:
            raise RuntimeError(
                f"fault gate: class {cls!r} had {fs.corrupt_attempts} "
                f"corrupt passes but only {fs.detected} detected — "
                f"silent corruption")
        if not np.array_equal(np.asarray(out0), np.asarray(outf)):
            raise RuntimeError(f"fault gate: class {cls!r} recovered logits "
                               f"diverge from clean")
        detected_total += fs.detected
    wall_sweep = time.perf_counter() - t0
    out.append(_rec(
        "faults/covered_class_sweep", wall_sweep * 1e6,
        f"{len(faults.COVERED_CLASSES)} classes x rate 1",
        f"{detected_total} faults detected, 0 silent, logits clean"))
    return out


def run():
    RECORDS.clear()
    RETIMERS.clear()
    out = _kernel_rows()
    out.extend(_emulation_rows())
    out.extend(_fault_rows())
    out.extend(_compressed_smoke_rows())
    out.extend(_backend_rows())
    return out


def run_quick():
    """``kernel/*`` + fault-gate + compressed-smoke + cross-backend
    records; ``benchmarks.run --quick``."""
    RECORDS.clear()
    RETIMERS.clear()
    out = _kernel_rows()
    out.extend(_fault_rows())
    out.extend(_compressed_smoke_rows())
    out.extend(_backend_rows())
    return out
