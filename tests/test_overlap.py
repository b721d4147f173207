"""§IV-E double-buffered slice pipeline, locked down three ways.

* **Pricing property sweep** — for any geometry/batch, the overlapped
  schedule's ``batch_time_s`` equals the serial schedule's minus EXACTLY
  the hidden-load credit (``NetworkResult.hidden_s``), per-layer credits
  are bounded by both the hideable load and one image's MAC+reduce, and
  ``total_cycles`` never moves (overlap re-times copies, not compute).
* **Engine differential** — overlap-granted plans through ``nc_conv2d``
  and ``nc_forward`` (including ``stream_chunk`` cross-layer streaming
  and the sparse x overlap composition) return BYTE-IDENTICAL outputs to
  the serial plans on the same weights.
* **Legality** — single-pass layers, pools, and fully pruned layers are
  denied overlap.  Overlap is a plan decision, like sparsity: the
  executors take no ``overlap`` keyword, only the plan or schedule.

The measured wall-time side (serial vs overlapped batch-4 pair) lives in
``benchmarks/kernel_bench.py`` + ``benchmarks/sched_breakdown.py``,
gated by ``benchmarks.common.overlap_wall_slack``.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import nc_layers as nc
from repro.core import quantize as q
from repro.core import schedule as sched
from repro.core.cache_geometry import XEON_E5_35MB
from repro.core.mapper import LayerSpec, pass_filter_bytes
from repro.core.simulator import (batch_time_s, modeled_layer_cycles,
                                  simulate_network)
from repro.models import inception

GEOM = XEON_E5_35MB
GEOM_1SLICE = XEON_E5_35MB.scaled(1)
GEOM_4SLICE = XEON_E5_35MB.scaled(4)


@pytest.fixture(scope="module")
def reduced_specs():
    return inception.inception_v3_specs(inception.reduced_config())


# ---------------------------------------------------------------------------
# Pricing: credit exactness, bounds, cycle invariance
# ---------------------------------------------------------------------------
def test_overlap_credit_exact_for_any_batch(reduced_specs):
    """Acceptance: serial minus overlapped == hidden credit, to float
    precision, for every batch size — the identity that lets the serving
    LatencyModel calibrate against overlapped plans with no changes."""
    for geom in (GEOM_1SLICE, GEOM_4SLICE):
        serial = sched.plan_network(reduced_specs, geom, batch=4)
        over = sched.plan_network(reduced_specs, geom, batch=4, overlap=True)
        assert over.overlapped_layers > 0
        rs, ro = simulate_network(serial), simulate_network(over)
        assert rs.hidden_s == 0.0
        assert ro.hidden_s > 0.0
        assert math.isclose(ro.hidden_s, sum(l.hidden_s for l in ro.layers),
                            rel_tol=1e-12)
        for n in (1, 2, 4, 16, 64):
            assert math.isclose(batch_time_s(rs, n) - batch_time_s(ro, n),
                                ro.hidden_s, rel_tol=1e-9)
        assert math.isclose(ro.overlapped_latency_s,
                            ro.latency_s - ro.hidden_s, rel_tol=1e-12)


def test_overlap_layer_credit_bounds(reduced_specs):
    """Per layer: the credit is exactly min(hideable load, one image's
    MAC+reduce), never negative, never more than the filter load — and
    the serial-priced totals (seconds AND cycles) don't move at all."""
    serial = sched.plan_network(reduced_specs, GEOM_1SLICE, batch=4)
    over = sched.plan_network(reduced_specs, GEOM_1SLICE, batch=4,
                              overlap=True)
    rs, ro = simulate_network(serial), simulate_network(over)
    for ls, lo, ps, po in zip(rs.layers, ro.layers, serial.layers,
                              over.layers):
        assert lo.total_s == ls.total_s  # serial pricing untouched
        assert modeled_layer_cycles(po, GEOM_1SLICE)["total_cycles"] == \
            modeled_layer_cycles(ps, GEOM_1SLICE)["total_cycles"]
        if not lo.overlap:
            assert lo.hidden_s == 0.0 and lo.prologue_s == 0.0
            continue
        assert 0.0 < lo.prologue_s <= lo.filter_s
        assert math.isclose(
            lo.hidden_s,
            min(max(lo.filter_s - lo.prologue_s, 0.0),
                lo.mac_s + lo.reduce_s), rel_tol=1e-12)
        assert lo.hidden_s <= lo.filter_s


def test_overlap_off_bit_identical_to_serial(reduced_specs):
    """overlap=False is the PR 3/4 schedule, field for field, and the
    simulator's numbers don't move a bit."""
    for batch in (1, 4):
        dense = sched.plan_network(reduced_specs, GEOM, batch=batch)
        off = sched.plan_network(reduced_specs, GEOM, batch=batch,
                                 overlap=False)
        assert off == dense
    r = simulate_network(sched.plan_network(reduced_specs, GEOM))
    assert r.hidden_s == 0.0
    assert r.overlapped_latency_s == r.latency_s


def test_pruning_overlap_composition(reduced_specs):
    """Sparsity first, overlap second: the sparse+overlapped schedule
    keeps the sparse plan's skip credit bit-for-bit and its own hidden
    credit stays exact against the sparse-serial schedule — composition
    never over-credits."""
    occ = sched.prune_occupancy(reduced_specs, 0.5)
    ss = sched.plan_network(reduced_specs, GEOM_1SLICE, batch=4,
                            occupancy=occ)
    so = sched.plan_network(reduced_specs, GEOM_1SLICE, batch=4,
                            occupancy=occ, overlap=True)
    assert so.skipped_passes == ss.skipped_passes
    assert 0 < so.overlapped_layers
    rs, ro = simulate_network(ss), simulate_network(so)
    assert ro.hidden_s > 0.0
    for n in (1, 4, 64):
        assert math.isclose(batch_time_s(rs, n) - batch_time_s(ro, n),
                            ro.hidden_s, rel_tol=1e-9)
    # a layer pruned down to <=1 executed pass has nothing to buffer
    for p in so.layers:
        if p.is_compute and p.executed_passes <= 1:
            assert not p.overlap


# ---------------------------------------------------------------------------
# pass_stages: the explicit (load, compute) split
# ---------------------------------------------------------------------------
def test_pass_stages_invariants(reduced_specs):
    for overlap in (False, True):
        net = sched.plan_network(reduced_specs, GEOM_1SLICE, batch=4,
                                 overlap=overlap)
        for p in net.layers:
            stages = p.pass_stages()
            if not p.is_compute:
                assert stages == ()
                continue
            assert len(stages) == p.executed_passes
            assert sum(s.load_bytes for s in stages) == p.filter_bytes
            for k, s in enumerate(stages):
                assert s.index == k
                assert s.load_bytes <= p.filter_bytes_per_pass
                # stage 0 is the prologue: never overlapped
                assert s.overlapped == (p.overlap and k > 0)
            if p.overlap:
                assert p.filter_bytes_per_pass == pass_filter_bytes(
                    p.filter_bytes, p.executed_passes)


def test_overlap_legality_denials(reduced_specs):
    spec = LayerSpec(name="t", kind="conv", H=18, R=3, S=3, C=8, M=64, E=16)
    # multi-pass at 1 slice: granted
    assert sched.plan_layer(spec, GEOM_1SLICE, overlap=True).overlap
    # same layer single-pass at the paper geometry: denied (nothing to
    # prefetch under — every filter column is already streaming for pass 0)
    full = sched.plan_layer(spec, GEOM, overlap=True)
    assert full.executed_passes == 1 and not full.overlap
    # pools carry no filters: denied, no stages
    pool = next(s for s in reduced_specs if s.kind not in ("conv", "fc"))
    pp = sched.plan_layer(pool, GEOM_1SLICE, overlap=True)
    assert not pp.overlap and pp.pass_stages() == ()
    # fully pruned: zero executed passes, nothing to double-buffer
    dead = sched.plan_layer(spec, GEOM_1SLICE, overlap=True,
                            occupancy=sched.LayerOccupancy(
                                spec.M, tuple(range(spec.M))))
    assert dead.executed_passes == 0 and not dead.overlap


# ---------------------------------------------------------------------------
# Engine differential: byte identity
# ---------------------------------------------------------------------------
def test_nc_conv2d_overlap_byte_identical():
    """The prefetch + deferred-store execution path returns the same
    bytes as the serial path on a genuinely multi-pass plan."""
    rng = np.random.default_rng(11)
    spec = LayerSpec(name="t", kind="conv", H=18, R=3, S=3, C=8, M=64, E=16)
    wq = rng.integers(0, 256, size=(3, 3, 8, 64)).astype(np.uint8)
    w_qp = q.QuantParams(scale=np.float32(0.05), zero_point=7)
    x = rng.normal(size=(18, 18, 8)).astype(np.float32)
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    serial = sched.plan_layer(spec, GEOM_1SLICE)
    over = sched.plan_layer(spec, GEOM_1SLICE, overlap=True)
    assert over.overlap and over.executed_passes > 1
    ref, cyc_s = nc.nc_conv2d(x, wq, x_qp, w_qp, plan=serial)
    out, cyc_o, stats = nc.nc_conv2d(x, wq, x_qp, w_qp, plan=over,
                                     return_stats=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert cyc_o == cyc_s  # modeled cycles are overlap-invariant
    assert stats.overlap
    assert stats.filter_loads == 1


def test_stream_chunk_runs_the_schedules_decisions(monkeypatch):
    """``stream_chunk`` plans each chunk with the given schedule's own
    decisions.  A batch of 2 streamed one image per chunk under a pruned,
    overlapped, compressed schedule returns the whole-batch run's logits
    byte for byte, and every layer's cycles: the modeled ones, and the
    emulated ones but for the §IV-D min/max tree, which runs once per
    chunk; each chunk's schedule carries the occupancy, overlap and
    compression of the given one."""
    cfg = inception.reduced_config(img=39, width_div=8, classes=8,
                                   stages=("a",))
    params = inception.init_params(jax.random.PRNGKey(0), config=cfg)
    # an eighth of the filters pruned: the stem keeps 2 passes at 1 slice,
    # so double buffering is granted there
    wpack = inception.prune_wpack(
        inception.prepare_conv_weights(params, cfg), 0.125)
    xb = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(2), (2, 39, 39, 3), jnp.float32))
    schedule = sched.plan_network(
        inception.inception_v3_specs(cfg), GEOM_1SLICE, batch=2,
        occupancy=inception.network_occupancy(wpack, cfg), overlap=True,
        compressed=True)
    run = dict(config=cfg, geom=GEOM_1SLICE, wpack=wpack, schedule=schedule)
    whole, rw = inception.nc_forward(params, xb, **run)
    chunk_schedules = []
    plan_network = sched.plan_network

    def spy(*a, **kw):
        chunk_schedules.append(plan_network(*a, **kw))
        return chunk_schedules[-1]

    monkeypatch.setattr(sched, "plan_network", spy)
    chunked, rc = inception.nc_forward(params, xb, stream_chunk=1, **run)
    assert np.asarray(chunked).tobytes() == np.asarray(whole).tobytes()
    assert rc.concat_requant_cycles == rw.concat_requant_cycles
    for lc, lw in zip(rc.layers, rw.layers, strict=True):
        assert (lc.name, lc.emulated_cycles - lc.minmax_cycles,
                lc.modeled_cycles, lc.serial_passes, lc.skipped_passes,
                lc.zero_filters, lc.overlap) == \
            (lw.name, lw.emulated_cycles - lw.minmax_cycles,
             lw.modeled_cycles, lw.serial_passes, lw.skipped_passes,
             lw.zero_filters, lw.overlap)
    assert sum(l.zero_filters for l in rc.layers) > 0
    assert sum(1 for l in rc.layers if l.overlap) > 0
    assert [sc.batch for sc in chunk_schedules] == [1, 1]
    for sc in chunk_schedules:
        assert (sc.overlap, sc.integrity, sc.compressed, sc.backend) == \
            (True, False, True, None)
        for pc, pw in zip(sc.layers, schedule.layers, strict=True):
            assert (pc.occupancy, pc.overlap, pc.compressed) == \
                (pw.occupancy, pw.overlap, pw.compressed)


def test_nc_forward_overlap_and_stream_chunk_byte_identical():
    """End to end on the miniature network at 1 slice (3 layers genuinely
    double-buffered): overlap and cross-layer streaming both return the
    serial logits byte for byte, and cycles don't move."""
    cfg = inception.reduced_config(img=39, width_div=8, classes=8,
                                   stages=("a",))
    params = inception.init_params(jax.random.PRNGKey(0), config=cfg)
    xb = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(1), (2, 39, 39, 3), jnp.float32))
    specs = inception.inception_v3_specs(cfg)
    over = sched.plan_network(specs, GEOM_1SLICE, batch=2, overlap=True)
    ref, rd = inception.nc_forward(params, xb, config=cfg, geom=GEOM_1SLICE)
    out, ro = inception.nc_forward(params, xb, config=cfg, geom=GEOM_1SLICE,
                                   schedule=over)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert sum(1 for l in ro.layers if l.overlap) > 0
    assert ro.total_emulated_cycles == rd.total_emulated_cycles
    chunked, _ = inception.nc_forward(params, xb, config=cfg,
                                      geom=GEOM_1SLICE, schedule=over,
                                      stream_chunk=1)
    np.testing.assert_array_equal(np.asarray(chunked), np.asarray(ref))
    # sparse x overlap composition: still byte-identical to sparse-serial
    wpack = inception.prune_wpack(
        inception.prepare_conv_weights(params, cfg), 0.5)
    occ = inception.network_occupancy(wpack, cfg)
    sref, _ = inception.nc_forward(
        params, xb, config=cfg, geom=GEOM_1SLICE, wpack=wpack,
        schedule=sched.plan_network(specs, GEOM_1SLICE, batch=2,
                                    occupancy=occ))
    sout, rso = inception.nc_forward(
        params, xb, config=cfg, geom=GEOM_1SLICE, wpack=wpack,
        schedule=sched.plan_network(specs, GEOM_1SLICE, batch=2,
                                    occupancy=occ, overlap=True))
    np.testing.assert_array_equal(np.asarray(sout), np.asarray(sref))
    assert sum(l.zero_filters for l in rso.layers) > 0
