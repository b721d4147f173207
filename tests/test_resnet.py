"""ResNet-50 v1.5 through the Neural Cache engine (models/resnet.py).

The published spec list, the residual join against plain numpy, and the
NC path through ``NCServingEngine`` against the float32 reference
(``resnet.apply``) and against the benchmark's numpy 8-bit reference
(chipbench/families/resnet50.py), on seeded random weights at the small
config."""
import itertools
import pathlib
import sys

import jax
import numpy as np
import pytest

from repro.core import nc_network
from repro.core import quantize as q
from repro.core import simulator as sim
from repro.core.cache_geometry import XEON_E5_35MB
from repro.core.mapper import LayerSpec
from repro.models import resnet

ROOT = pathlib.Path(__file__).resolve().parents[1]
SMALL = {"img": 64, "classes": 10, "width_div": 16, "units": [2, 1, 1, 1],
         "store": "dense"}  # resnet.REDUCED, as the benchmark writes it
SEEDS = (2**31 + 11, 8589934599)
# Widest |NC - float| logit gap, in units of the float logits' standard
# deviation.  Every activation is an 8-bit per-tensor code (half an LSB,
# 1/510 of its range, of rounding at each requantization, weights
# likewise): at this config it reads 0.04 - 0.13 over five seeds.  The
# same pipeline at 4 bits reads 0.41 - 1.31.
FLOAT_TOL = 0.25


@pytest.fixture(scope="module")
def family():
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from chipbench.families import resnet50

    return resnet50


@pytest.fixture(autouse=True, scope="module")
def x32():
    """The program's 32-bit types (other test modules enable x64)."""
    before = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", before)


def test_full_width_spec_list():
    specs = resnet.resnet50_specs()
    convs = [s for s in specs if s.kind == "conv"]
    assert len(convs) == 53
    assert [s.kind for s in specs[-2:]] == ["avgpool", "fc"]
    assert (specs[-1].C, specs[-1].M) == (2048, 1000)
    macs = sum(s.E * s.E * s.R * s.S * s.C * s.M for s in specs
               if s.kind in ("conv", "fc"))
    assert macs == 4_089_184_256
    assert sum(s.R * s.S * s.C * s.M for s in specs
               if s.kind in ("conv", "fc")) == 25_502_912
    joins = [s for s in specs if s.kind == "residual"]
    assert len(joins) == 16
    assert sum(s.M * s.E * s.E for s in joins) == 5_519_360
    # v1.5: a stage's first unit strides on its 3x3 conv and its projection
    strided = {s.name for s in specs if s.stride == 2}
    assert strided == {"conv1", "pool1"} | {
        f"block{b}_unit1_{c}" for b in (2, 3, 4) for c in ("conv2",
                                                           "shortcut")}
    by_name = {s.name: s for s in specs}
    assert (by_name["conv1"].R, by_name["conv1"].E) == (7, 112)
    assert by_name["conv1"].R * by_name["conv1"].S * by_name["conv1"].C == 147
    assert by_name["pool1"].E == 56
    assert by_name["block4_unit3_add"].E == 7
    assert all(f"block{b}_unit{u}_shortcut" in by_name
               for b, u in ((1, 1), (2, 1), (3, 1), (4, 1)))
    assert "block1_unit2_shortcut" not in by_name


def test_specs_plan_and_price_the_join():
    from repro.core import schedule as sched

    schedule = sched.plan_network(resnet.resnet50_specs(), XEON_E5_35MB)
    res = sim.simulate_network(schedule)
    joins = [l for l in res.layers if l.spec.kind == "residual"]
    assert len(joins) == 16
    for l in joins:
        assert l.quant_s > 0 and l.mac_s == 0 and l.filter_s == 0
        assert l.plan.serial_passes == -(-l.spec.M * l.spec.E ** 2
                                         // l.mapped.parallel_convs)


def _operand(rng, shape, scale, zp):
    return (rng.integers(0, 256, size=shape).astype(np.uint8),
            [q.QuantParams(scale=np.float32(s), zero_point=z)
             for s, z in zip(scale, zp)])


def test_residual_join_against_plain_numpy():
    """Operands of different scales and non-zero zero points, per image:
    the join's uint8 output, dequantized, is ReLU(a + b) within the two
    roundings it makes (half the finer operand scale, half the output
    scale), and its range covers the sum's."""
    rng = np.random.default_rng(7)
    shape = (2, 5, 5, 6)
    a = _operand(rng, shape, (0.031, 0.004), (131, 90))
    b = _operand(rng, shape, (0.012, 0.0271), (77, 200))
    spec = LayerSpec("u_add", "residual", H=5, R=1, S=1, C=0, M=6, E=5)
    from repro.core import schedule as sched

    records = []
    ex = nc_network._Exec({}, {}, {"u_add": sched.plan_layer(spec)},
                          XEON_E5_35MB, sim.SimConstants(), "host", records)
    yq, qps = nc_network._nc_residual("u_add", a, b, ex)
    assert yq.dtype == np.uint8 and yq.shape == shape
    for i in range(shape[0]):
        real = sum((x[i].astype(np.float64) - int(p[i].zero_point))
                   * float(p[i].scale) for x, p in (a, b))
        want = np.maximum(real, 0.0)
        s_out, z_out = float(qps[i].scale), int(qps[i].zero_point)
        got = (yq[i].astype(np.float64) - z_out) * s_out
        s_c = min(float(a[1][i].scale), float(b[1][i].scale))
        assert np.abs(got - want).max() <= 0.5 * (s_c + s_out) * (1 + 1e-6)
        assert z_out == 0  # ReLU: the range starts at 0
        assert s_out == pytest.approx(want.max() / 255, rel=0.01)
    [rec] = records
    assert rec.kind == "residual" and rec.batch == 2
    assert rec.emulated_cycles == (2 * nc_network._JOIN_CYCLES
                                   + rec.minmax_cycles)
    assert rec.modeled_cycles > 0 and rec.serial_passes >= 1


def test_residual_join_rejects_operands_of_different_shapes():
    rng = np.random.default_rng(0)
    a = _operand(rng, (1, 4, 4, 2), (0.1,), (3,))
    b = _operand(rng, (1, 4, 4, 3), (0.1,), (3,))
    ex = nc_network._Exec({}, {}, {}, XEON_E5_35MB, sim.SimConstants(),
                          "host", [])
    with pytest.raises(ValueError):
        nc_network._nc_residual("u_add", a, b, ex)


@pytest.fixture(scope="module", params=SEEDS)
def served(request, family):
    """Three images served through ``NCServingEngine`` one at a time, on
    the family's seeded weights, with their float and 8-bit references."""
    from repro.launch.serve import NCRequest, NCServingEngine

    seed = request.param
    params = jax.tree.map(np.asarray, family.make_params(SMALL, seed))
    engine = NCServingEngine(params, resnet.REDUCED, network=resnet,
                             max_batch=1, engine="host")
    images = list(itertools.islice(family.image_stream(SMALL, seed, 1), 3))
    for rid, x in enumerate(images):
        engine.submit(NCRequest(rid=rid, image=x))
    done = engine.run()
    assert [r.rid for r in done] == [0, 1, 2]
    assert engine.degraded_batches == 0 and not engine.failed
    return params, images, [r.logits for r in done], engine


def test_nc_path_tracks_the_float_reference(served, family):
    params, images, logits, _ = served
    for x, got in zip(images, logits):
        f = np.asarray(resnet.apply(params, x[None], config=resnet.REDUCED))[0]
        assert np.abs(got - f).max() / f.std() < FLOAT_TOL
        # the tolerance is tight enough to refuse a 4-bit pipeline
        r4 = family.reference_logits(SMALL, params, x, bits=4)
        assert np.abs(r4 - f).max() / f.std() > FLOAT_TOL


def test_nc_path_equals_the_numpy_8bit_reference(served, family):
    """The same integer pipeline: equal but for the float32 rounding of
    the logits (the program dequantizes in float32, the reference in
    float64), a few parts in 10**7 of their spread."""
    params, images, logits, _ = served
    for x, got in zip(images, logits):
        ref = family.reference_logits(SMALL, params, x)
        assert np.abs(got - ref).max() / ref.std() < 1e-5


def test_engine_reports_every_layer_and_join(served):
    *_, engine = served
    report = engine.reports[-1]
    specs = resnet.resnet50_specs(resnet.REDUCED)
    assert [l.name for l in report.layers] == [s.name for s in specs]
    kinds = [l.kind for l in report.layers]
    assert kinds.count("residual") == 5 and kinds.count("conv") == 20
    linear = {l.name for l in report.layers if l.name.endswith(
        ("_conv3", "_shortcut"))}
    assert len(linear) == 9


def test_warmup_replan_keeps_the_logits(served):
    """Observed occupancy (linear convs included) re-plans every batch
    size; the logits stay byte-identical."""
    from repro.launch.serve import NCRequest, NCServingEngine

    params, images, logits, _ = served
    engine = NCServingEngine(params, resnet.REDUCED, network=resnet,
                             max_batch=1, engine="host", warmup_replan=True)
    for rid, x in enumerate(images[:2]):
        engine.submit(NCRequest(rid=rid, image=x))
    done = engine.run()
    assert engine.warmup_replans == 1
    for r in done:
        assert np.array_equal(r.logits, logits[r.rid])


def test_float_fallback_is_the_networks_reference(served):
    """Off a TPU the recovery ladder's last rung answers with the
    network's own float reference."""
    from repro.launch.serve import NCRequest, NCServingEngine

    params, images, *_ = served
    engine = NCServingEngine(params, resnet.REDUCED, network=resnet,
                             max_batch=1, engine="host")

    def broken(x, schedule):
        raise RuntimeError("emulation down")

    engine._forward = broken
    engine.submit(NCRequest(rid=0, image=images[0]))
    [r] = engine.run()
    assert r.degraded == "float"
    f = np.asarray(resnet.apply(params, images[0][None],
                                config=resnet.REDUCED))[0]
    assert np.array_equal(r.logits, f)
