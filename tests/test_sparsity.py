"""Sparsity-aware slice scheduling, locked down by a differential harness.

Three layers of defense:

* **Differential property sweep** — random per-filter prunings (0%, 25%,
  75%, 100% zero filters) and per-plane prunings through ``nc_conv2d`` /
  ``nc_fc``: the sparse run (pruned pass list) must return BYTE-IDENTICAL
  outputs to the dense run on the same weights, across SAME/VALID padding,
  stride 2, batch 1 and 4, and non-dividing tiles.
* **Schedule invariants** — skipped-pass credits monotone in sparsity,
  zero-sparsity plans structurally equal to dense plans,
  ``stream_batch_limit`` pruning-independent, and the simulator's
  dense-vs-sparse delta equal to the skip credit TO THE CYCLE.
* **Golden cycle-model regression** — ``tests/golden/modeled_cycles.json``
  freezes ``simulate_network``'s per-layer modeled cycles for
  ``reduced_config`` (dense, a fixed 50% pruning, and the §IV-E
  overlapped plan's per-layer hidden-load credits, on the paper
  geometry and a 1-slice scale-down where passes actually serialize).
  Any cycle-model drift fails tier-1; regenerate deliberately with
  ``REGEN_GOLDEN=1 pytest tests/test_sparsity.py``.
"""
import dataclasses
import json
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bitserial as bs
from repro.core import nc_layers as nc
from repro.core import quantize as q
from repro.core import schedule as sched
from repro.core.cache_geometry import XEON_E5_35MB
from repro.core.mapper import LayerSpec, serial_passes_for
from repro.core.simulator import modeled_layer_cycles, simulate_network
from repro.models import inception
from nc_plans import conv_plan, fc_plan, occupancy

GEOM = XEON_E5_35MB
GEOM_1SLICE = XEON_E5_35MB.scaled(1)
GOLDEN = pathlib.Path(__file__).parent / "golden" / "modeled_cycles.json"
PRUNE_FRACTIONS = (0.0, 0.25, 0.75, 1.0)


def _pruned_case(rng, M=8, C=3, R=3, frac=0.5, zp=7):
    """Integer (already-quantized) weights with round(M*frac) random
    filters set to the zero point — dequantized exactly zero."""
    wq = rng.integers(0, 256, size=(R, R, C, M)).astype(np.uint8)
    k = int(round(M * frac))
    idx = rng.choice(M, size=k, replace=False)
    wq[..., idx] = zp
    w_qp = q.QuantParams(scale=np.float32(0.05), zero_point=zp)
    return wq, w_qp, np.sort(idx)


# ---------------------------------------------------------------------------
# Differential property sweep: sparse == dense, byte for byte
# ---------------------------------------------------------------------------
@given(
    frac=st.sampled_from(PRUNE_FRACTIONS),
    stride=st.sampled_from([1, 2]),
    padding=st.sampled_from(["VALID", "SAME"]),
    batch=st.sampled_from([1, 4]),
    tile_pixels=st.sampled_from([None, 7]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=10, deadline=None)
def test_sparse_conv_bit_exact_vs_dense(frac, stride, padding, batch,
                                        tile_pixels, seed):
    rng = np.random.default_rng(seed)
    wq, w_qp, idx = _pruned_case(rng, frac=frac)
    shape = (batch, 8, 8, 3) if batch > 1 else (8, 8, 3)
    x = rng.normal(size=shape).astype(np.float32)
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    qps = [x_qp] * batch if batch > 1 else x_qp
    dense, cyc_d = nc.nc_conv2d(x, wq, qps, w_qp, stride, padding=padding,
                                tile_pixels=tile_pixels)
    sparse, cyc_s, stats = nc.nc_conv2d(
        x, wq, qps, w_qp, stride, padding=padding,
        plan=conv_plan(x, wq, stride, padding=padding,
                       tile_pixels=tile_pixels,
                       occupancy=occupancy(wq, w_qp)),
        return_stats=True)
    np.testing.assert_array_equal(np.asarray(sparse), np.asarray(dense))
    assert stats.zero_filters == len(idx)
    # the engine charges §III cycles only for the executed (live) lanes
    assert cyc_s <= cyc_d
    if frac == 0.0:
        assert cyc_s == cyc_d
    if frac == 1.0:
        assert cyc_s == 0


@given(
    frac=st.sampled_from(PRUNE_FRACTIONS),
    k=st.sampled_from([9, 23, 40]),
    tile_filters=st.sampled_from([None, 3]),
    batch=st.sampled_from([1, 4]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=8, deadline=None)
def test_sparse_fc_bit_exact_vs_dense(frac, k, tile_filters, batch, seed):
    rng = np.random.default_rng(seed)
    M, zp = 8, 3
    wq = rng.integers(0, 256, size=(k, M)).astype(np.uint8)
    idx = rng.choice(M, size=int(round(M * frac)), replace=False)
    wq[:, idx] = zp
    w_qp = q.QuantParams(scale=np.float32(0.1), zero_point=zp)
    shape = (batch, k) if batch > 1 else (k,)
    x = rng.normal(size=shape).astype(np.float32)
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    qps = [x_qp] * batch if batch > 1 else x_qp
    dense, cyc_d = nc.nc_fc(x, wq, qps, w_qp, tile_filters=tile_filters)
    sparse, cyc_s = nc.nc_fc(x, wq, qps, w_qp, plan=fc_plan(
        x, wq, tile_filters=tile_filters, occupancy=occupancy(wq, w_qp)))
    np.testing.assert_array_equal(np.asarray(sparse), np.asarray(dense))
    assert cyc_s <= cyc_d


@given(
    planes=st.sampled_from([(0,), (3,), (0, 1), (2, 5, 7), (7,)]),
    stride=st.sampled_from([1, 2]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=6, deadline=None)
def test_per_plane_pruning_elides_dead_planes_bit_exact(planes, stride, seed):
    """Per-plane pruning: weights whose live bits sit in a few planes.  The
    host multiply elides the dead shifted-add steps (an all-zero tag word
    is an identity) — results must be bit-identical with elision disabled,
    and the elision must show in SKIP_STATS."""
    rng = np.random.default_rng(seed)
    keep = 0
    for p in planes:
        keep |= 1 << p
    wq = (rng.integers(0, 256, size=(3, 3, 2, 4)) & keep).astype(np.uint8)
    x = rng.normal(size=(7, 7, 2)).astype(np.float32)
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    w_qp = q.QuantParams(scale=np.float32(0.05), zero_point=0)
    bs.ZERO_SKIP = False
    ref, cyc_ref = nc.nc_conv2d(x, wq, x_qp, w_qp, stride)
    bs.ZERO_SKIP = True
    bs.SKIP_STATS.reset()
    out, cyc = nc.nc_conv2d(x, wq, x_qp, w_qp, stride)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert cyc == cyc_ref  # modeled cycles never change from elision
    snap = bs.SKIP_STATS.snapshot()
    assert snap["planes_total"] > 0
    # every weight bit outside `planes` is dead in every tile's multiply
    assert snap["planes_skipped"] >= snap["planes_total"] // 8 * (8 - len(planes))


def test_tile_override_keeps_plan_occupancy():
    """Replanning for a caller's tile override must carry the sparse
    plan's occupancy along, not silently fall back to dense."""
    rng = np.random.default_rng(8)
    wq, w_qp, idx = _pruned_case(rng, frac=0.5)
    x = rng.normal(size=(8, 8, 3)).astype(np.float32)
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    spec = LayerSpec(name="nc_conv2d", kind="conv", H=8, R=3, S=3, C=3, M=8,
                     E=6)
    occ = sched.LayerOccupancy.from_filter_rows(
        wq.reshape(-1, 8).T, 8, int(w_qp.zero_point))
    plan = sched.plan_layer(spec, GEOM, occupancy=occ)
    dense, _ = nc.nc_conv2d(x, wq, x_qp, w_qp)
    out, _, stats = nc.nc_conv2d(x, wq, x_qp, w_qp, plan=plan,
                                 tile_filters=2, return_stats=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(dense))
    assert stats.zero_filters == len(idx)


def test_occupancy_detection_matches_weights():
    rng = np.random.default_rng(5)
    wq, w_qp, idx = _pruned_case(rng, M=16, frac=0.5, zp=9)
    rows = wq.reshape(-1, 16).T
    zero_mask, plane_live = bs.filter_occupancy(rows, 8, 9)
    np.testing.assert_array_equal(np.flatnonzero(zero_mask), idx)
    occ = sched.LayerOccupancy.from_filter_rows(rows, 8, 9)
    assert occ.zero_filters == tuple(int(i) for i in idx)
    assert occ.n_live == 16 - len(idx)
    assert 0 <= occ.dead_planes < 8


def test_overclaiming_occupancy_raises_underclaiming_allowed():
    """An occupancy marking a LIVE filter as zero would corrupt results —
    the engine validates against the actual weights.  Marking fewer
    filters than are actually zero is safe (they just run dense)."""
    rng = np.random.default_rng(6)
    wq, w_qp, idx = _pruned_case(rng, M=8, frac=0.5, zp=7)
    x = rng.normal(size=(6, 6, 3)).astype(np.float32)
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    live = [m for m in range(8) if m not in idx]
    with pytest.raises(ValueError, match="stale plan"):
        nc.nc_conv2d(x, wq, x_qp, w_qp, plan=conv_plan(
            x, wq, occupancy=sched.LayerOccupancy(8, (live[0],))))
    dense, _ = nc.nc_conv2d(x, wq, x_qp, w_qp)
    under, _ = nc.nc_conv2d(x, wq, x_qp, w_qp, plan=conv_plan(
        x, wq, occupancy=sched.LayerOccupancy(8, (int(idx[0]),))))
    np.testing.assert_array_equal(np.asarray(under), np.asarray(dense))


# ---------------------------------------------------------------------------
# Schedule invariants
# ---------------------------------------------------------------------------
def _spec(M=64, E=35, C=32, R=3):
    return LayerSpec(name="s", kind="conv", H=E + R - 1, R=R, S=R, C=C, M=M,
                     E=E)


def test_skip_credit_monotone_in_sparsity():
    spec = _spec()
    dense = sched.plan_layer(spec, GEOM_1SLICE, batch=2)
    prev_skipped, prev_bytes = 0, dense.filter_bytes + 1
    for zf in range(spec.M + 1):
        occ = sched.LayerOccupancy(spec.M, tuple(range(zf)))
        p = sched.plan_layer(spec, GEOM_1SLICE, batch=2, occupancy=occ)
        assert p.skipped_passes >= prev_skipped  # monotone credit
        assert 0 <= p.skipped_passes <= dense.serial_passes
        assert p.executed_passes == dense.serial_passes - p.skipped_passes
        assert p.filter_bytes < prev_bytes or zf == 0
        prev_skipped, prev_bytes = p.skipped_passes, p.filter_bytes
    # full pruning skips every pass and loads no filters
    assert p.executed_passes == 0 and p.filter_bytes == 0
    # the sparse pass count follows the mapper's ONE serialization rule
    occ = sched.LayerOccupancy(spec.M, tuple(range(24)))
    p = sched.plan_layer(spec, GEOM_1SLICE, occupancy=occ)
    assert p.executed_passes == serial_passes_for(
        (spec.M - 24) * spec.E * spec.E, p.mapped.parallel_convs)


def test_degenerate_spec_still_maps_to_one_idle_pass():
    """The shared serial_passes_for rule must not regress map_layer's
    handling of zero-work specs (serial=1, utilization=0)."""
    from repro.core.mapper import map_layer
    m = map_layer(LayerSpec(name="d", kind="conv", H=3, R=3, S=3, C=2, M=8,
                            E=0))
    assert m.serial_passes == 1 and m.utilization == 0.0


def test_zero_sparsity_plan_structurally_equal_to_dense():
    """A plan with zero detected sparsity is the PR-3 plan, field for
    field (occupancy metadata aside)."""
    for spec in (_spec(), _spec(M=8, E=4, C=4)):
        for batch in (1, 4):
            dense = sched.plan_layer(spec, GEOM, batch=batch)
            zocc = sched.LayerOccupancy(spec.M, ())
            sparse = sched.plan_layer(spec, GEOM, batch=batch, occupancy=zocc)
            assert sparse.skipped_passes == 0
            assert dataclasses.replace(sparse, occupancy=None) == dense


@pytest.fixture(scope="module")
def reduced_specs():
    return inception.inception_v3_specs(inception.reduced_config())


def test_network_invariants_under_pruning(reduced_specs):
    occ = sched.prune_occupancy(reduced_specs, 0.75)
    dense = sched.plan_network(reduced_specs, GEOM_1SLICE, batch=4)
    sparse = sched.plan_network(reduced_specs, GEOM_1SLICE, batch=4,
                                occupancy=occ)
    # stream_batch_limit depends on activation bytes only — pruning-proof
    assert sparse.stream_batch_limit == dense.stream_batch_limit
    # spill decisions unchanged (outputs stream at full width)
    assert [p.spill_to_dram for p in sparse.layers] == \
        [p.spill_to_dram for p in dense.layers]
    assert sparse.skipped_passes > 0
    assert sparse.filter_bytes_loaded < dense.filter_bytes_loaded
    # monotone at the network level too
    lighter = sched.plan_network(reduced_specs, GEOM_1SLICE, batch=4,
                                 occupancy=sched.prune_occupancy(
                                     reduced_specs, 0.25))
    assert lighter.skipped_passes <= sparse.skipped_passes


def test_modeled_cycles_drop_by_skip_credit_exactly(reduced_specs):
    """Acceptance: sparse modeled cycles == dense - credit, per layer, on
    both the paper geometry and the 1-slice scale-down."""
    occ = sched.prune_occupancy(reduced_specs, 0.5)
    for geom in (GEOM, GEOM_1SLICE):
        dense = sched.plan_network(reduced_specs, geom, batch=1)
        sparse = sched.plan_network(reduced_specs, geom, batch=1,
                                    occupancy=occ)
        for pd, ps in zip(dense.layers, sparse.layers):
            md = modeled_layer_cycles(pd, geom)
            ms = modeled_layer_cycles(ps, geom)
            assert ms["per_pass_cycles"] == md["per_pass_cycles"]
            assert md["total_cycles"] - ms["total_cycles"] == \
                ms["skip_credit_cycles"]
            assert ms["skip_credit_cycles"] == \
                ms["per_pass_cycles"] * ms["skipped_passes"]


def test_simulator_dense_bit_identical_with_sparsity_off(reduced_specs):
    """Sparsity off (no occupancy / zero occupancy) must not move a single
    bit of the dense model's numbers."""
    r_dense = simulate_network(sched.plan_network(reduced_specs, GEOM))
    zocc = {s.name: sched.LayerOccupancy(s.M, ())
            for s in reduced_specs if s.kind in ("conv", "fc")}
    r_zero = simulate_network(sched.plan_network(reduced_specs, GEOM,
                                                 occupancy=zocc))
    assert r_zero.latency_s == r_dense.latency_s
    assert r_zero.energy_j == r_dense.energy_j
    assert r_zero.filter_s == r_dense.filter_s
    for ld, lz in zip(r_dense.layers, r_zero.layers):
        assert (lz.mac_s, lz.reduce_s, lz.quant_s, lz.pool_s) == \
            (ld.mac_s, ld.reduce_s, ld.quant_s, ld.pool_s)


# ---------------------------------------------------------------------------
# Golden cycle-model regression (regenerate with REGEN_GOLDEN=1)
# ---------------------------------------------------------------------------
def _golden_payload():
    cfg = inception.reduced_config()
    specs = inception.inception_v3_specs(cfg)
    occ = sched.prune_occupancy(specs, 0.5)

    def table(schedule, geom):
        out = {}
        for p in schedule.layers:
            m = modeled_layer_cycles(p, geom)
            out[p.spec.name] = {
                "per_pass_cycles": float(m["per_pass_cycles"]),
                "serial_passes": int(m["serial_passes"]),
                "skipped_passes": int(m["skipped_passes"]),
                "total_cycles": float(m["total_cycles"]),
            }
        return out

    def overlap_table(schedule, geom):
        """§IV-E double buffering: freeze which layers are granted the
        overlap and the seconds each hides — total_cycles stays the
        dense table's (overlap re-times copies, never compute)."""
        out = {}
        for p in schedule.layers:
            m = modeled_layer_cycles(p, geom)
            out[p.spec.name] = {
                "overlap": bool(m["overlap"]),
                "hidden_s": float(m["hidden_s"]),
                "overlapped_total_s": float(m["overlapped_total_s"]),
                "total_cycles": float(m["total_cycles"]),
            }
        return out

    payload = {"config": cfg.name, "pruning": 0.5, "geometries": {}}
    for geom in (GEOM, GEOM_1SLICE):
        payload["geometries"][geom.name] = {
            "dense": table(sched.plan_network(specs, geom), geom),
            "pruned": table(
                sched.plan_network(specs, geom, occupancy=occ), geom),
            "overlapped": overlap_table(
                sched.plan_network(specs, geom, overlap=True), geom),
        }
    return payload


def test_golden_modeled_cycles_frozen():
    """Per-layer modeled cycles (dense AND fixed 50% pruning) must match
    tests/golden/modeled_cycles.json bit for bit.  Cycle-model changes are
    allowed only deliberately: rerun with ``REGEN_GOLDEN=1`` and commit
    the refreshed file."""
    payload = _golden_payload()
    if os.environ.get("REGEN_GOLDEN"):
        GOLDEN.parent.mkdir(exist_ok=True)
        GOLDEN.write_text(json.dumps(payload, indent=1, sort_keys=True)
                          + "\n")
    want = json.loads(GOLDEN.read_text())
    assert payload == want, (
        "modeled cycle drift vs tests/golden/modeled_cycles.json — if the "
        "cycle model changed on purpose, regenerate with REGEN_GOLDEN=1")


# ---------------------------------------------------------------------------
# End-to-end acceptance (slow-marked; exercised by benchmarks/run.py's gate)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_nc_forward_sparse_acceptance_batch4():
    """reduced_config + fixed 50% filter pruning at batch 4: sparse
    nc_forward is byte-identical to dense on the same pruned weights,
    modeled cycles drop by the skip credit, and warm wall time per image
    is below the dense run's."""
    import time

    cfg = inception.reduced_config()
    params = inception.init_params(jax.random.PRNGKey(0), config=cfg)
    wpack = inception.prune_wpack(
        inception.prepare_conv_weights(params, cfg), 0.5)
    xb = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(1), (4, cfg.img, cfg.img, 3), jnp.float32))
    specs = inception.inception_v3_specs(cfg)
    occ = inception.network_occupancy(wpack, cfg)
    sparse_schedule = sched.plan_network(specs, GEOM, batch=4, occupancy=occ)

    wall_d = wall_s = float("inf")
    for _ in range(2):  # first pass warms the bucketed-jit caches
        t0 = time.perf_counter()
        ld, rd = inception.nc_forward(params, xb, config=cfg, wpack=wpack)
        wall_d = min(wall_d, time.perf_counter() - t0)
        t0 = time.perf_counter()
        ls, rs = inception.nc_forward(params, xb, config=cfg, wpack=wpack,
                                      schedule=sparse_schedule)
        wall_s = min(wall_s, time.perf_counter() - t0)

    np.testing.assert_array_equal(np.asarray(ls), np.asarray(ld))
    assert rs.total_emulated_cycles < rd.total_emulated_cycles
    # modeled credit: exact per layer (pass counts are 1 at paper scale for
    # this miniature, so assert on the 1-slice geometry where they bite)
    dense1 = sched.plan_network(specs, GEOM_1SLICE, batch=4)
    sparse1 = sched.plan_network(specs, GEOM_1SLICE, batch=4, occupancy=occ)
    assert sparse1.skipped_passes > 0
    for pd, ps in zip(dense1.layers, sparse1.layers):
        md = modeled_layer_cycles(pd, GEOM_1SLICE)
        ms = modeled_layer_cycles(ps, GEOM_1SLICE)
        assert md["total_cycles"] - ms["total_cycles"] == \
            ms["skip_credit_cycles"]
    # every conv pruned half its filters and the engine never ran them
    for l in rs.layers:
        if l.kind in ("conv", "fc"):
            assert l.zero_filters == round(0.5 * l.out_shape[-1])
    # wall time: half the filter columns never enter the packed engine
    assert wall_s < wall_d, (wall_s, wall_d)


@pytest.mark.slow
def test_nc_serving_engine_sparse_bit_exact():
    """A serving deployment of a PRUNED model (half of every conv's output
    channels zeroed in the float weights — they quantize exactly to the
    zero point) plans sparse by default and still answers byte-identically
    to dense standalone runs."""
    from repro.launch.serve import NCRequest, NCServingEngine

    cfg = inception.reduced_config(img=47, width_div=8, classes=8,
                                   stages=("a",))
    params = inception.init_params(jax.random.PRNGKey(0), config=cfg)
    for name, p in params.items():  # prune: last half of output channels
        w = np.array(p["w"], copy=True)
        w[..., w.shape[-1] // 2:] = 0.0
        p["w"] = jnp.asarray(w)
    eng = NCServingEngine(params, cfg, max_batch=2)
    assert eng.occupancy is not None
    assert sum(o.n_zero for o in eng.occupancy.values()) > 0
    rng = np.random.default_rng(0)
    imgs = rng.random((3, 47, 47, 3)).astype(np.float32)
    for r in range(3):
        eng.submit(NCRequest(rid=r, image=imgs[r]))
    done = eng.run()
    assert len(done) == 3
    for r in done:  # dense standalone reference: byte-identical
        ref, _ = inception.nc_forward(params, imgs[r.rid], config=cfg)
        np.testing.assert_array_equal(r.logits, np.asarray(ref))
    # every batch report saw the pruned filters skipped by the engine
    for rep in eng.reports:
        assert sum(l.zero_filters for l in rep.layers) > 0


# ---------------------------------------------------------------------------
# Compressed filter residency (PR 8): CSR bit-plane store + plan flag
# ---------------------------------------------------------------------------
@given(
    frac=st.sampled_from((0.0, 0.25, 0.5, 0.75, 1.0)),
    tail=st.sampled_from([(1,), (1, 3), (2,)]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=10, deadline=None)
def test_compressed_planes_roundtrip(frac, tail, seed):
    """CompressedPlanes pack/unpack is byte-identical to the dense grid,
    whole and per column range, and the footprint shrinks with pruning."""
    rng = np.random.default_rng(seed)
    n, M = 8, 12
    grid = rng.integers(0, 2**32, size=(n, M) + tail, dtype=np.uint32)
    k = int(round(M * frac))
    if k:
        grid[:, rng.choice(M, size=k, replace=False)] = 0
    cp = bs.CompressedPlanes.compress(grid)
    np.testing.assert_array_equal(cp.dense(), grid)
    for m0, m1 in ((0, M), (0, 1), (3, 7), (M - 1, M), (5, 5)):
        np.testing.assert_array_equal(cp.dense_columns(m0, m1),
                                      grid[:, m0:m1])
    assert cp.n_columns == M and cp.tail_shape == tuple(tail)
    assert cp.payload_bytes + cp.index_bytes == cp.nbytes
    if frac >= 0.5:
        assert cp.nbytes < grid.nbytes
    if frac == 1.0:
        assert cp.payload_bytes == 0 and cp.live_planes == 0


@given(
    frac=st.sampled_from((0.0, 0.25, 0.5, 0.75, 1.0)),
    stride=st.sampled_from([1, 2]),
    padding=st.sampled_from(["VALID", "SAME"]),
    batch=st.sampled_from([1, 4]),
    tile_pixels=st.sampled_from([None, 7]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=10, deadline=None)
def test_compressed_conv_bit_exact_vs_dense(frac, stride, padding, batch,
                                            tile_pixels, seed):
    """The differential harness, compressed: executing from the CSR
    bit-plane store must be byte-identical to the dense store at every
    pruning level, across padding/stride/batch/tiling."""
    rng = np.random.default_rng(seed)
    wq, w_qp, _ = _pruned_case(rng, frac=frac)
    shape = (batch, 8, 8, 3) if batch > 1 else (8, 8, 3)
    x = rng.normal(size=shape).astype(np.float32)
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    qps = [x_qp] * batch if batch > 1 else x_qp
    dense, cyc_d = nc.nc_conv2d(x, wq, qps, w_qp, stride, padding=padding,
                                tile_pixels=tile_pixels)
    comp, cyc_c, stats = nc.nc_conv2d(
        x, wq, qps, w_qp, stride, padding=padding,
        plan=conv_plan(x, wq, stride, padding=padding,
                       tile_pixels=tile_pixels,
                       occupancy=occupancy(wq, w_qp), compressed=True),
        return_stats=True)
    np.testing.assert_array_equal(np.asarray(comp), np.asarray(dense))
    assert stats.compressed
    if frac < 1.0:
        assert stats.csr_payload_bytes > 0


def test_compressed_fc_bit_exact_vs_dense():
    rng = np.random.default_rng(7)
    M, zp, k = 8, 3, 23
    wq = rng.integers(0, 256, size=(k, M)).astype(np.uint8)
    wq[:, [1, 4]] = zp
    w_qp = q.QuantParams(scale=np.float32(0.1), zero_point=zp)
    x = rng.normal(size=(4, k)).astype(np.float32)
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    dense, _ = nc.nc_fc(x, wq, [x_qp] * 4, w_qp)
    comp, _, stats = nc.nc_fc(
        x, wq, [x_qp] * 4, w_qp, return_stats=True,
        plan=fc_plan(x, wq, occupancy=occupancy(wq, w_qp), compressed=True))
    np.testing.assert_array_equal(np.asarray(comp), np.asarray(dense))
    assert stats.compressed


def test_compression_off_plan_equal_and_carryover(reduced_specs):
    """compressed=False is the PR 7 plan, field for field; a compressed
    plan round-trips its flag through schedules and carries the exact
    residency bookkeeping."""
    base = sched.plan_network(reduced_specs, GEOM, batch=4)
    off = sched.plan_network(reduced_specs, GEOM, batch=4, compressed=False)
    assert base == off
    comp = sched.plan_network(reduced_specs, GEOM, batch=4, compressed=True)
    assert comp.compressed
    for pb, pc in zip(base.layers, comp.layers):
        if pc.spec.kind in ("conv", "fc") and pb.filter_bytes:
            assert pc.compressed
            assert pc.dense_filter_bytes == pb.filter_bytes
            assert pc.residency_credit_bytes == \
                pb.filter_bytes - pc.filter_bytes
        else:
            assert pc.residency_credit_bytes == 0


def test_residency_credit_exact_per_layer_and_batch(reduced_specs):
    """Acceptance: dense minus compressed modeled time equals the
    residency credit to 1e-12, per layer and per batch (overlap-off plans
    — overlap re-times hidden loads and is gated separately)."""
    from repro.core.simulator import batch_time_s

    for occ in (None, sched.prune_occupancy(reduced_specs, 0.5)):
        dense = sched.plan_network(reduced_specs, GEOM, batch=4,
                                   occupancy=occ)
        comp = sched.plan_network(reduced_specs, GEOM, batch=4,
                                  occupancy=occ, compressed=True)
        rd, rc = simulate_network(dense), simulate_network(comp)
        for ld, lc in zip(rd.layers, rc.layers):
            assert abs((ld.total_s - lc.total_s)
                       - lc.residency_credit_s) < 1e-12
        for n in (1, 2, 4, 8, 16):
            assert abs((batch_time_s(rd, n) - batch_time_s(rc, n))
                       - rc.residency_credit_s) < 1e-12
        assert abs(rc.residency_credit_s
                   - comp.residency_credit_bytes / 10.96e9) < 1e-12


def test_stream_limit_and_spill_monotone_under_compression(reduced_specs):
    """Property sweep (PR 8 satellite): as residency shrinks (pruning
    0 -> 100%, compressed on/off), ``stream_batch_limit`` is monotone
    non-decreasing, never below the uncompressed plan's, and spill
    decisions never move (outputs are pruning- and compression-blind)."""
    fracs = (0.0, 0.25, 0.5, 0.75, 1.0)
    for geom in (GEOM, GEOM_1SLICE):
        dense = sched.plan_network(reduced_specs, geom, batch=4)
        spills = [p.spill_to_dram for p in dense.layers]
        prev = {True: 0, False: 0}
        for frac in fracs:
            occ = sched.prune_occupancy(reduced_specs, frac)
            for compressed in (False, True):
                s = sched.plan_network(reduced_specs, geom, batch=4,
                                       occupancy=occ, compressed=compressed)
                assert [p.spill_to_dram for p in s.layers] == spills
                assert s.stream_batch_limit >= dense.stream_batch_limit
                assert s.stream_batch_limit >= prev[compressed], \
                    (geom.name, frac, compressed)
                prev[compressed] = s.stream_batch_limit
                if not compressed:  # uncompressed: pinned exactly
                    assert s.stream_batch_limit == dense.stream_batch_limit


def test_compressed_residency_ratio_at_half_pruning(reduced_specs):
    """50% filter pruning + compression keeps no more than 0.55x the dense
    filter bytes resident (the kernel_bench gate's modeled side)."""
    occ = sched.prune_occupancy(reduced_specs, 0.5)
    dense = sched.plan_network(reduced_specs, GEOM, batch=4)
    comp = sched.plan_network(reduced_specs, GEOM, batch=4, occupancy=occ,
                              compressed=True)
    assert comp.filter_bytes_loaded <= 0.55 * dense.filter_bytes_loaded


@pytest.mark.slow
def test_nc_serving_engine_compressed_ragged_bit_exact():
    """Compressed serving with a ragged tail (3 requests, max_batch=2):
    every request's logits byte-identical to the dense standalone
    forward, and the engine's schedules all carry the compressed flag."""
    from repro.launch.serve import NCRequest, NCServingEngine

    cfg = inception.reduced_config(img=47, width_div=8, classes=8,
                                   stages=("a",))
    params = inception.init_params(jax.random.PRNGKey(0), config=cfg)
    eng = NCServingEngine(params, cfg, max_batch=2, compressed=True)
    assert eng.schedule.compressed
    rng = np.random.default_rng(0)
    imgs = rng.random((3, 47, 47, 3)).astype(np.float32)
    for r in range(3):
        eng.submit(NCRequest(rid=r, image=imgs[r]))
    done = eng.run()
    assert len(done) == 3 and not eng.failed
    assert sorted(eng._schedules) == [1, 2]  # ragged tail planned its own
    assert all(s.compressed for s in eng._schedules.values())
    for r in done:
        ref, _ = inception.nc_forward(params, imgs[r.rid], config=cfg)
        np.testing.assert_array_equal(r.logits, np.asarray(ref))
    assert eng.stats()["residency_credit_bytes"] == \
        eng.schedule.residency_credit_bytes


@pytest.mark.slow
def test_warmup_replan_shrinks_quant_passes_logits_unchanged():
    """Warmup re-planning on a synthetically sparse model (first conv
    biased so far negative its post-ReLU outputs are all zero): the
    re-planned quant passes drop below the estimate-planned count, logits
    stay byte-identical, and the calibration curve excludes exactly the
    warmup batch."""
    from repro.launch.serve import NCRequest, NCServingEngine

    cfg = inception.reduced_config(img=47, width_div=8, classes=8,
                                   stages=("a",))
    params = inception.init_params(jax.random.PRNGKey(0), config=cfg)
    first = cfg.stem[0][0]
    params[first]["bias"] = jnp.full_like(params[first]["bias"], -100.0)

    eng = NCServingEngine(params, cfg, max_batch=2, compressed=True,
                          warmup_replan=True)
    est_quant = sum(p.quant_passes for p in eng.schedule.layers)
    rng = np.random.default_rng(3)
    imgs = rng.random((4, 47, 47, 3)).astype(np.float32)
    for r in range(4):
        eng.submit(NCRequest(rid=r, image=imgs[r]))
    done = eng.run()
    assert len(done) == 4 and not eng.failed
    s = eng.stats()
    assert s["warmup_replans"] == 1
    # measured: the dead conv's outputs requantize to the known zero
    # point, so its §IV-D passes vanish from the re-planned schedule
    obs_quant = sum(p.quant_passes for p in eng.schedule.layers)
    assert obs_quant < est_quant, (obs_quant, est_quant)
    assert eng.occupancy[first].live_outputs == 0
    # calibration honest across the re-plan: the warmup batch (executed
    # under the retired estimate plan) is excluded, the rest observed
    assert s["calibration_excluded"] == 1
    assert s["calibration_samples"] == eng.steps - 1
    # logits byte-identical to the estimate-planned standalone forward
    ld, _ = inception.nc_forward(params, imgs, config=cfg)
    got = np.stack([r.logits for r in sorted(done, key=lambda r: r.rid)])
    np.testing.assert_array_equal(got, np.asarray(ld))
