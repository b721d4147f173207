"""Packed-resident tiled layer pipeline: tiled-vs-untiled bit-exactness,
SAME padding, pools, wordline-budget enforcement, the bucketed jit engine,
batched-vs-single bit-exactness (batch folded into the packed lane axis),
the §IV-D in-cache nc_minmax reduction, and the end-to-end (slow-marked)
reduced Inception v3 forward through the emulation."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bitserial as bs
from repro.core import nc_layers as nc
from repro.core import quantize as q
from repro.core.cache_geometry import CacheGeometry, XEON_E5_35MB
from repro.core.mapper import LayerSpec
from repro.models import inception

jax.config.update("jax_enable_x64", True)


def _conv_case(rng, H, C, R, M, n_bits):
    x = rng.normal(size=(H, H, C)).astype(np.float32)
    w = rng.normal(size=(R, R, C, M)).astype(np.float32) * 0.5
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()),
                            bits=n_bits)
    w_qp = q.choose_qparams(jnp.float32(w.min()), jnp.float32(w.max()),
                            bits=n_bits)
    return jnp.asarray(x), jnp.asarray(w), x_qp, w_qp


# ---------------------------------------------------------------------------
# Tentpole: tiled + packed-resident conv is bit-exact vs the untiled oracle
# across strides, tile sizes (incl. non-dividing), and plane counts.
# ---------------------------------------------------------------------------
@given(
    stride=st.sampled_from([1, 2]),
    n_bits=st.sampled_from([4, 6, 8]),
    tile_pixels=st.sampled_from([1, 3, 5, 49, 1000]),
    tile_filters=st.sampled_from([1, 2, 5, 1000]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=8, deadline=None)
def test_tiled_conv_bit_exact_vs_untiled(stride, n_bits, tile_pixels,
                                         tile_filters, seed):
    rng = np.random.default_rng(seed)
    x, w, x_qp, w_qp = _conv_case(rng, H=8, C=3, R=3, M=5, n_bits=n_bits)
    ref, cyc_ref = nc.nc_conv2d(x, w, x_qp, w_qp, stride)
    out, cyc = nc.nc_conv2d(x, w, x_qp, w_qp, stride,
                            tile_pixels=tile_pixels,
                            tile_filters=tile_filters)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert cyc == cyc_ref  # tiling must not change modeled cycles


@given(
    k=st.sampled_from([4, 9, 31, 40]),
    tile_filters=st.sampled_from([1, 3, 1000]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=6, deadline=None)
def test_tiled_fc_bit_exact(k, tile_filters, seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(k,)).astype(np.float32)
    w = rng.normal(size=(k, 7)).astype(np.float32)
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    w_qp = q.choose_qparams(jnp.float32(w.min()), jnp.float32(w.max()))
    ref, cyc_ref = nc.nc_fc(jnp.asarray(x), jnp.asarray(w), x_qp, w_qp)
    out, cyc = nc.nc_fc(jnp.asarray(x), jnp.asarray(w), x_qp, w_qp,
                        tile_filters=tile_filters)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert cyc == cyc_ref


def test_conv_cycles_match_formula():
    """Per-dot cycles are the unchanged §III composition (mul + acc-add +
    log-tree), independent of tiling/packing."""
    rng = np.random.default_rng(0)
    x, w, x_qp, w_qp = _conv_case(rng, H=6, C=2, R=3, M=4, n_bits=8)
    _, cyc = nc.nc_conv2d(x, w, x_qp, w_qp, tile_pixels=3, tile_filters=2)
    K = 3 * 3 * 2
    per_dot = bs.mul_cycles(8) + bs.add_cycles(32) + bs.reduce_cycles(K, 32)
    assert cyc == per_dot * 4 * 4 * 4  # E*F*M dots


def test_conv_same_padding_exact():
    rng = np.random.default_rng(3)
    xq = rng.integers(0, 256, size=(8, 8, 3), dtype=np.uint8)
    wq = rng.integers(0, 256, size=(3, 3, 3, 4), dtype=np.uint8)
    qp0 = q.QuantParams(scale=1.0, zero_point=0)
    for stride in (1, 2):
        acc, _ = nc.nc_conv2d(jnp.asarray(xq, jnp.float32),
                              jnp.asarray(wq, jnp.float32), qp0, qp0, stride,
                              padding="SAME")
        ref = jax.lax.conv_general_dilated(
            jnp.asarray(xq, jnp.int64)[None], jnp.asarray(wq, jnp.int64),
            (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))[0]
        np.testing.assert_array_equal(np.asarray(acc),
                                      np.asarray(ref, np.int32))


def test_conv_same_padding_nonzero_zp():
    """SAME padding uses the quantized zero point, so the affine identity
    stays exact: dequantized padding contributes exactly zero."""
    rng = np.random.default_rng(4)
    x = rng.normal(size=(6, 6, 2)).astype(np.float32) + 1.5  # nonzero zp
    w = rng.normal(size=(3, 3, 2, 3)).astype(np.float32) * 0.5
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    w_qp = q.choose_qparams(jnp.float32(w.min()), jnp.float32(w.max()))
    assert int(x_qp.zero_point) != 0
    acc, _ = nc.nc_conv2d(jnp.asarray(x), jnp.asarray(w), x_qp, w_qp,
                          padding="SAME")
    got = np.asarray(acc, np.float64) * float(x_qp.scale) * float(w_qp.scale)
    ref = jax.lax.conv_general_dilated(
        jnp.asarray(x)[None], jnp.asarray(w), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))[0]
    assert np.abs(got - np.asarray(ref)).max() < 0.5


# ---------------------------------------------------------------------------
# Pools
# ---------------------------------------------------------------------------
def test_maxpool_same():
    rng = np.random.default_rng(5)
    xq = rng.integers(0, 256, size=(9, 9, 4), dtype=np.uint8)
    got, _ = nc.nc_maxpool2d(jnp.asarray(xq), 3, 2, padding="SAME")
    ref = jax.lax.reduce_window(
        jnp.asarray(xq, jnp.int32), jnp.int32(0), jax.lax.max,
        (3, 3, 1), (2, 2, 1), "SAME")
    np.testing.assert_array_equal(np.asarray(got, np.int32), np.asarray(ref))


@pytest.mark.parametrize("pad", ["VALID", "SAME"])
def test_avgpool_matches_float(pad):
    rng = np.random.default_rng(6)
    xq = rng.integers(0, 256, size=(9, 9, 4), dtype=np.uint8)
    got, cyc = nc.nc_avgpool2d(jnp.asarray(xq), 3, 1, padding=pad)
    ones = jax.lax.reduce_window(jnp.ones((9, 9, 4), jnp.float32), 0.0,
                                 jax.lax.add, (3, 3, 1), (1, 1, 1), pad)
    s = jax.lax.reduce_window(jnp.asarray(xq, jnp.float32), 0.0, jax.lax.add,
                              (3, 3, 1), (1, 1, 1), pad)
    ref = np.asarray(s / ones)
    assert np.abs(np.asarray(got, np.float64) - ref).max() <= 0.51
    assert cyc > 0


# ---------------------------------------------------------------------------
# Batched-vs-single bit-exactness: the batch folds into the packed lane
# axis, quantization is per-image, outputs must match N independent runs.
# ---------------------------------------------------------------------------
@given(
    stride=st.sampled_from([1, 2]),
    padding=st.sampled_from(["VALID", "SAME"]),
    batch=st.sampled_from([2, 3, 5]),
    tile_pixels=st.sampled_from([None, 7, 1000]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=8, deadline=None)
def test_batched_conv_bit_exact_vs_singles(stride, padding, batch,
                                           tile_pixels, seed):
    rng = np.random.default_rng(seed)
    # per-image data AND per-image quantization ranges
    xs = [rng.normal(size=(8, 8, 3)).astype(np.float32) * s
          for s in rng.uniform(0.3, 3.0, batch)]
    w = rng.normal(size=(3, 3, 3, 5)).astype(np.float32) * 0.5
    w_qp = q.choose_qparams(jnp.float32(w.min()), jnp.float32(w.max()))
    qps = [q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
           for x in xs]
    out, cyc = nc.nc_conv2d(np.stack(xs), jnp.asarray(w), qps, w_qp, stride,
                            padding=padding, tile_pixels=tile_pixels)
    singles = [nc.nc_conv2d(jnp.asarray(x), jnp.asarray(w), qp, w_qp, stride,
                            padding=padding) for x, qp in zip(xs, qps)]
    for b in range(batch):
        np.testing.assert_array_equal(np.asarray(out[b]),
                                      np.asarray(singles[b][0]))
    # cycles are per lane group — batching never discounts the §III charge
    assert cyc == sum(s[1] for s in singles)


def test_batched_conv_non_dividing_batch_tile():
    """A tile_pixels that does not divide E*F forces tiles spanning image
    boundaries AND ragged tails — results must stay bit-identical."""
    rng = np.random.default_rng(11)
    xs = [rng.normal(size=(7, 7, 2)).astype(np.float32) for _ in range(3)]
    w = rng.normal(size=(3, 3, 2, 4)).astype(np.float32)
    w_qp = q.choose_qparams(jnp.float32(w.min()), jnp.float32(w.max()))
    qps = [q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
           for x in xs]
    # E*F = 9 per image (SAME stride 2 -> 4x4=16); rows total 3*16, tile 7
    out, _ = nc.nc_conv2d(np.stack(xs), jnp.asarray(w), qps, w_qp, 2,
                          padding="SAME", tile_pixels=7, tile_filters=3)
    for b in range(3):
        ref, _ = nc.nc_conv2d(jnp.asarray(xs[b]), jnp.asarray(w), qps[b],
                              w_qp, 2, padding="SAME")
        np.testing.assert_array_equal(np.asarray(out[b]), np.asarray(ref))


def test_batched_pools_and_fc_bit_exact():
    rng = np.random.default_rng(12)
    xq = rng.integers(0, 256, size=(3, 9, 9, 4), dtype=np.uint8)
    for pad in ("VALID", "SAME"):
        mb, cm = nc.nc_maxpool2d(xq, 3, 2, padding=pad)
        ab, ca = nc.nc_avgpool2d(xq, 3, 1, padding=pad)
        for b in range(3):
            m1, c1 = nc.nc_maxpool2d(xq[b], 3, 2, padding=pad)
            a1, c2 = nc.nc_avgpool2d(xq[b], 3, 1, padding=pad)
            np.testing.assert_array_equal(np.asarray(mb[b]), np.asarray(m1))
            np.testing.assert_array_equal(np.asarray(ab[b]), np.asarray(a1))
        assert cm == 3 * c1 and ca == 3 * c2
    xs = rng.normal(size=(3, 23)).astype(np.float32)
    w = rng.normal(size=(23, 6)).astype(np.float32)
    w_qp = q.choose_qparams(jnp.float32(w.min()), jnp.float32(w.max()))
    qps = [q.choose_qparams(jnp.float32(v.min()), jnp.float32(v.max()))
           for v in xs]
    ob, _ = nc.nc_fc(xs, w, qps, w_qp)
    for b in range(3):
        o1, _ = nc.nc_fc(xs[b], w, qps[b], w_qp)
        np.testing.assert_array_equal(np.asarray(ob[b]), np.asarray(o1))


def test_batched_conv_prequantized_resident_inputs():
    """Integer inputs skip the quantize step (the resident-uint8 path)."""
    rng = np.random.default_rng(13)
    xq = rng.integers(0, 256, size=(2, 6, 6, 2), dtype=np.uint8)
    wq = rng.integers(0, 256, size=(3, 3, 2, 3), dtype=np.uint8)
    qp0 = q.QuantParams(scale=1.0, zero_point=0)
    acc, _ = nc.nc_conv2d(xq, wq, [qp0, qp0], qp0)
    ref = jax.lax.conv_general_dilated(
        jnp.asarray(xq, jnp.int64), jnp.asarray(wq, jnp.int64), (1, 1),
        "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(ref, np.int32))


# ---------------------------------------------------------------------------
# §IV-D in-cache min/max (nc_minmax): exact vs np.min/np.max, log-tree cycles
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 2, 3, 5, 16, 31, 32, 33, 100, 257, 1024])
def test_nc_minmax_matches_numpy_int8(k):
    rng = np.random.default_rng(k)
    v = rng.integers(-128, 128, size=(k,)).astype(np.int8)
    mn, mx, cyc = nc.nc_minmax(v, bits=8, signed=True)
    assert int(mn) == int(v.min()) and int(mx) == int(v.max())
    assert cyc == bs.minmax_cycles(k, 8) + 2  # +2: sign-plane bias in/out


@pytest.mark.parametrize("k", [1, 7, 64, 500])
def test_nc_minmax_batched_rows_and_int32(k):
    rng = np.random.default_rng(k)
    v = rng.integers(-2**31, 2**31, size=(5, k), dtype=np.int64)
    mn, mx, cyc = nc.nc_minmax(v, bits=32, signed=True)
    np.testing.assert_array_equal(mn, v.min(axis=1))
    np.testing.assert_array_equal(mx, v.max(axis=1))
    # all rows advance in lockstep: one tree's worth of cycles
    assert cyc == bs.minmax_cycles(k, 32) + 2


def test_nc_minmax_unsigned_and_formula():
    rng = np.random.default_rng(99)
    v = rng.integers(0, 256, size=(40,)).astype(np.uint8)
    mn, mx, cyc = nc.nc_minmax(v, bits=8)
    assert int(mn) == int(v.min()) and int(mx) == int(v.max())
    # the closed form: ceil(log2 k) steps of subtract + masked copy + tag
    steps = int(np.ceil(np.log2(40)))
    assert cyc == steps * (bs.add_cycles(8) + 9 + 1) == bs.minmax_cycles(40, 8)


def test_bitserial_minmax_packed_roundtrip():
    """Packed-in/packed-out: the op stays below the value-plane API."""
    rng = np.random.default_rng(3)
    rows = rng.integers(0, 1 << 12, size=(6, 32)).astype(np.uint64)
    pp = bs.pack_values(rows, 12, row_align=True)
    (mn, mx), cyc = bs.bitserial_minmax(pp)
    assert isinstance(mn, bs.PackedPlanes) and isinstance(mx, bs.PackedPlanes)
    np.testing.assert_array_equal(
        bs.unpack_values(mx).reshape(-1), rows.max(axis=1))
    np.testing.assert_array_equal(
        bs.unpack_values(mn).reshape(-1), rows.min(axis=1))
    assert cyc == bs.minmax_cycles(32, 12)


# ---------------------------------------------------------------------------
# Mapper wordline-budget enforcement (satellite: clear error with the spec)
# ---------------------------------------------------------------------------
def test_conv_tiler_raises_on_wordline_budget():
    rng = np.random.default_rng(7)
    x, w, x_qp, w_qp = _conv_case(rng, H=6, C=2, R=3, M=4, n_bits=8)
    tiny = dataclasses.replace(XEON_E5_35MB, array_rows=120, name="tiny-rows")
    with pytest.raises(ValueError, match="word-line budget"):
        nc.nc_conv2d(x, w, x_qp, w_qp, geom=tiny,
                     layer_spec=LayerSpec(name="offending_conv", kind="conv",
                                          H=6, R=3, S=3, C=2, M=4, E=4))
    try:
        nc.nc_conv2d(x, w, x_qp, w_qp, geom=tiny,
                     layer_spec=LayerSpec(name="offending_conv", kind="conv",
                                          H=6, R=3, S=3, C=2, M=4, E=4))
    except ValueError as e:
        assert "offending_conv" in str(e)  # the spec rides in the error


# ---------------------------------------------------------------------------
# Bucketed jit engine: parity + compilation reuse across tiles
# ---------------------------------------------------------------------------
def test_jit_engine_parity_and_cache_reuse():
    rng = np.random.default_rng(8)
    x, w, x_qp, w_qp = _conv_case(rng, H=8, C=3, R=3, M=5, n_bits=8)
    ref, _ = nc.nc_conv2d(x, w, x_qp, w_qp)
    bs.engine_cache_clear()
    out, _ = nc.nc_conv2d(x, w, x_qp, w_qp, tile_pixels=7, tile_filters=2,
                          engine="jit")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    info = bs.engine_cache_info()
    # one engine entry for the layer's (planes, acc, K) bucket, and the
    # ragged tail tiles were padded onto the same compiled shape
    assert info["entries"] == 1
    if info["compiled"]:  # executable count is best-effort (private JAX API)
        assert info["compiled"] <= 2  # full tile shape (+ at most one variant)
    # a second layer with the same K/planes reuses the same entry
    out2, _ = nc.nc_conv2d(x, w, x_qp, w_qp, tile_pixels=9, tile_filters=5,
                           engine="jit")
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(ref))
    assert bs.engine_cache_info()["entries"] == 1


# ---------------------------------------------------------------------------
# EIE-style zero-operand skipping: accounting only, never results
# ---------------------------------------------------------------------------
def test_zero_operand_stats_and_exactness():
    rng = np.random.default_rng(9)
    x = rng.normal(size=(6, 6, 2)).astype(np.float32)
    w = np.zeros((3, 3, 2, 4), np.float32)
    w[0, 0, 0, 0] = 1.0  # a single live weight: almost every lane skippable
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    w_qp = q.QuantParams(scale=1.0, zero_point=0)
    out, cyc, stats = nc.nc_conv2d(jnp.asarray(x), jnp.asarray(w), x_qp, w_qp,
                                   return_stats=True)
    # the accumulator holds the affine-corrected integer conv: (xq - zx) * w
    xq_centered = nc._quantize_np(x, x_qp) - int(x_qp.zero_point)
    ref = jax.lax.conv_general_dilated(
        jnp.asarray(xq_centered, jnp.int64)[None],
        jnp.asarray(w, jnp.int64), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))[0]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref, np.int32))
    K = 3 * 3 * 2
    assert stats.lanes == 4 * 4 * 4 * K
    # only one of the K weight positions is live, in one of the 4 filters
    live_windows = int((nc._quantize_np(x, x_qp)[:4, :4, 0] != 0).sum())
    assert stats.zero_operand_lanes == stats.lanes - live_windows
    assert stats.engine_words_skipped > 0
    # cycles never change: the SRAM clocks every bit-slice
    _, cyc_dense = nc.nc_conv2d(jnp.asarray(x), jnp.asarray(np.ones_like(w)),
                                x_qp, w_qp)
    assert cyc == cyc_dense


# ---------------------------------------------------------------------------
# End-to-end: reduced Inception v3 through the emulation (slow-marked; the
# tier-1 run skips these — benchmarks/run.py's gate exercises `-m slow`)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_forward():
    cfg = inception.reduced_config(img=47, width_div=8, classes=8,
                                   stages=("a",))
    key = jax.random.PRNGKey(0)
    params = inception.init_params(key, config=cfg)
    x = jax.random.uniform(key, (47, 47, 3), jnp.float32)
    logits, report = inception.nc_forward(params, x, config=cfg)
    return cfg, params, x, logits, report


@pytest.mark.slow
def test_nc_forward_runs_and_reports(tiny_forward):
    cfg, params, x, logits, report = tiny_forward
    assert logits.shape == (8,)
    assert bool(jnp.all(jnp.isfinite(logits)))
    specs = inception.inception_v3_specs(cfg)
    assert len(report.layers) == len(specs)  # one report row per layer
    assert report.total_emulated_cycles > 0
    assert report.total_modeled_cycles > 0
    assert report.total_modeled_s > 0
    for l in report.layers:
        assert l.emulated_cycles >= 0 and l.serial_passes >= 1
    # §IV-D: every conv layer's dynamic range came from the in-cache tree
    for l in report.layers:
        if l.kind == "conv":
            assert l.minmax_cycles > 0
    text = report.summary()
    assert "TOTAL" in text and "modeled latency" in text


@pytest.mark.slow
def test_nc_forward_tracks_float_model(tiny_forward):
    cfg, params, x, logits, report = tiny_forward
    ref = inception.apply(params, x[None], quant=True, config=cfg)[0]
    corr = np.corrcoef(np.asarray(ref), np.asarray(logits))[0, 1]
    assert corr > 0.95, corr


@pytest.mark.slow
def test_nc_forward_batched_bit_identical(tiny_forward):
    """nc_forward(batch=N) rows == N independent single-image runs, bit for
    bit (per-image in-cache quantization makes batching invisible)."""
    cfg, params, _, _, _ = tiny_forward
    xb = jax.random.uniform(jax.random.PRNGKey(7), (3, 47, 47, 3),
                            jnp.float32)
    lb, rb = inception.nc_forward(params, xb, config=cfg)
    assert rb.batch == 3
    for b in range(3):
        ls, _ = inception.nc_forward(params, xb[b], config=cfg)
        np.testing.assert_array_equal(np.asarray(lb[b]), np.asarray(ls))


@pytest.mark.slow
def test_nc_forward_batch4_acceptance():
    """The PR acceptance run: reduced_config() at batch=4, end to end.

    - in-cache nc_minmax quantization on every conv (no CPU float min/max
      in the layer loop),
    - filters packed once per layer per batch (§VI-C residency),
    - per-image wall time lower than the batch=1 path,
    - simulate_network consuming the SAME NetworkSchedule reports filter
      bytes loaded once per layer per batch."""
    import time

    from repro.core import schedule as sched
    from repro.core import simulator as sim

    cfg = inception.reduced_config()
    params = inception.init_params(jax.random.PRNGKey(0), config=cfg)
    xb = jax.random.uniform(jax.random.PRNGKey(1), (4, cfg.img, cfg.img, 3),
                            jnp.float32)
    specs = inception.inception_v3_specs(cfg)
    schedule = sched.plan_network(specs, XEON_E5_35MB, batch=4)

    # two runs each, min taken: the first batched run also warms the
    # bucketed-jit engine cache, which is the steady state a serving run
    # amortizes to (and what "per-image wall time" means under load noise)
    wall1, wall4 = float("inf"), float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        l1, r1 = inception.nc_forward(params, xb[0], config=cfg)
        wall1 = min(wall1, time.perf_counter() - t0)
        t0 = time.perf_counter()
        l4, r4 = inception.nc_forward(params, xb, config=cfg,
                                      schedule=schedule)
        wall4 = min(wall4, time.perf_counter() - t0)

    assert l4.shape == (4, cfg.classes)
    assert bool(jnp.all(jnp.isfinite(l4)))
    np.testing.assert_array_equal(np.asarray(l4[0]), np.asarray(l1))
    # §IV-D: dynamic ranges from the in-cache tree, filters resident
    for l in r4.layers:
        if l.kind in ("conv", "fc"):
            assert l.filter_loads == 1  # packed once per layer per batch
        if l.kind == "conv":
            assert l.minmax_cycles > 0
    # batching amortizes: per-image wall time beats the single-image path
    assert wall4 / 4 < wall1, (wall4 / 4, wall1)
    # the same plan object prices the run: filter bytes once per layer
    # per batch, independent of the batch size
    res = sim.simulate_network(schedule)
    assert res.schedule is schedule
    assert res.filter_bytes_loaded == sum(s.filter_bytes for s in specs)
    assert res.filter_bytes_loaded == sim.simulate_network(
        specs).filter_bytes_loaded


@pytest.mark.slow
def test_nc_serving_engine_batches_requests():
    """Serving routes admitted request batches through the schedule: the
    per-request logits equal standalone single-image runs bit for bit."""
    from repro.launch.serve import NCRequest, NCServingEngine

    cfg = inception.reduced_config(img=47, width_div=8, classes=8,
                                   stages=("a",))
    params = inception.init_params(jax.random.PRNGKey(0), config=cfg)
    eng = NCServingEngine(params, cfg, max_batch=2)
    rng = np.random.default_rng(0)
    imgs = rng.random((5, 47, 47, 3)).astype(np.float32)
    for r in range(5):
        eng.submit(NCRequest(rid=r, image=imgs[r]))
    done = eng.run()
    assert len(done) == 5 and all(r.done for r in done)
    assert eng.steps == 3  # 2 + 2 + 1: ragged final batch
    assert eng.schedule.batch == 2  # planned once for the admission size
    for r in done:
        ref, _ = inception.nc_forward(params, imgs[r.rid], config=cfg)
        np.testing.assert_array_equal(r.logits, np.asarray(ref))


def test_reduced_config_specs_map():
    from repro.core.mapper import map_network
    cfg = inception.reduced_config()
    specs = inception.inception_v3_specs(cfg)
    assert specs[-1].M == cfg.classes
    mapped = map_network(specs)  # must fit the budget
    assert len(mapped) == len(specs)
    kinds = {s.kind for s in specs}
    assert kinds == {"conv", "maxpool", "avgpool", "fc"}
    # every mixed stage type survives the reduction
    names = {s.block for s in specs}
    for b in ("Mixed_5b", "Mixed_6a", "Mixed_6b", "Mixed_7a", "Mixed_7b"):
        assert b in names


def test_full_config_unchanged():
    """The FULL config must still reproduce the paper's Table-I network."""
    assert inception.FULL.img == 299
    assert inception.FULL.classes == 1001
    specs_default = inception.inception_v3_specs()
    specs_full = inception.inception_v3_specs(inception.FULL)
    assert specs_default == specs_full
