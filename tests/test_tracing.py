"""Program spans on the profiler's clock (docs/SERVING.md, "Tracing").

One Inception-style split op (a conv branch through the ``pallas``
backend, run by the Pallas interpreter here, beside a max-pool branch,
then the concat), one ResNet-style residual op (a linear conv body
joined to the identity), one pool op per backend and one serving step
run inside
``jax.profiler.trace``; the tests read the ``.xplane.pb`` each writes.
Stage spans are leaves: they never nest in each other, every one of them
appears, and together they cover most of a conv layer's ``nc.layer``
span.  The join's ``nc.residual`` sits in its own ``nc.layer`` and in no
stage span.  ``nc.pool`` names the path its pool took, and the
requantizing ``nc.conv.epilogue`` and ``nc.residual`` the path of their
min/max.
"""
import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import nc_network
from repro.core import quantize as q
from repro.core import schedule as sched
from repro.core import simulator as sim
from repro.core.cache_geometry import XEON_E5_35MB
from repro.models import inception

STAGES = ("nc.conv.im2col", "nc.conv.pack", "nc.pallas.launch",
          "nc.pallas.wait", "nc.pallas.scatter", "nc.conv.store",
          "nc.conv.epilogue", "nc.concat", "nc.pool", "nc.accounting")
SPLIT = ("split", [("conv", 3, 3, 8, 1, "SAME")], [("maxpool", 3, 1, "SAME")])
CONV = "Mixed_t_s0_0"


@pytest.fixture(scope="module")
def x32():
    """The program's 32-bit types (other test modules enable x64 at
    import, which a shared worker would carry into the kernel)."""
    before = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", before)


def _spans(trace_dir):
    """``(thread, name, start_ns, end_ns, stats)`` of every ``nc.*``
    host event of the one trace under ``trace_dir``."""
    [path] = sorted(trace_dir.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("nc."):
                    out.append((f"{plane.name}/{line.name}", ev.name,
                                ev.start_ns, ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def split_spans(x32, tmp_path_factory):
    specs = []
    nc_network._op_specs("Mixed_t", "Mixed_t", SPLIT, 9, 8, specs)
    plans = {p.spec.name: p for p in sched.plan_network(
        [s for s in specs if s.kind == "conv"], XEON_E5_35MB).layers}
    rng = np.random.default_rng(0)
    wq = rng.integers(0, 256, size=(3, 3, 8, 8)).astype(np.uint8)
    w_qp = q.QuantParams(scale=np.float32(0.01), zero_point=128)
    wpack = {CONV: (wq, w_qp, np.zeros(8, np.float32))}
    actq = rng.integers(0, 256, size=(1, 9, 9, 8)).astype(np.uint8)
    qps = [q.QuantParams(scale=np.float32(1 / 255), zero_point=0)]
    trace_dir = tmp_path_factory.mktemp("split")
    with jax.profiler.trace(str(trace_dir)):
        yq, _ = nc_network._nc_apply_op(
            actq, qps, "Mixed_t", SPLIT, nc_network._Exec(
                wpack, {s.name: s for s in specs}, plans, XEON_E5_35MB,
                sim.SimConstants(), "pallas", []))
    assert yq.shape == (1, 9, 9, 16)
    return _spans(trace_dir)


RESIDUAL = ("residual", [("conv", ("conv", 3, 3, 8, 1, "SAME", "linear"))],
            [])
JOIN = "Unit_t_add"


def _trace_residual(engine, trace_dir):
    """The nc.* spans of :data:`RESIDUAL` run on ``engine``."""
    specs = []
    nc_network._op_specs("Unit_t", "Unit_t", RESIDUAL, 9, 8, specs)
    plans = {p.spec.name: p
             for p in sched.plan_network(specs, XEON_E5_35MB).layers}
    rng = np.random.default_rng(1)
    wq = rng.integers(0, 256, size=(3, 3, 8, 8)).astype(np.uint8)
    w_qp = q.QuantParams(scale=np.float32(0.01), zero_point=128)
    wpack = {"Unit_t_conv": (wq, w_qp, np.zeros(8, np.float32))}
    actq = rng.integers(0, 256, size=(1, 9, 9, 8)).astype(np.uint8)
    qps = [q.QuantParams(scale=np.float32(1 / 255), zero_point=0)]
    records = []
    with jax.profiler.trace(str(trace_dir)):
        yq, _ = nc_network._nc_apply_op(
            actq, qps, "Unit_t", RESIDUAL, nc_network._Exec(
                wpack, {s.name: s for s in specs}, plans, XEON_E5_35MB,
                sim.SimConstants(), engine, records))
    assert yq.shape == (1, 9, 9, 8)
    assert [r.kind for r in records] == ["conv", "residual"]
    return _spans(trace_dir)


@pytest.fixture(scope="module")
def residual_spans(x32, tmp_path_factory):
    return _trace_residual("host", tmp_path_factory.mktemp("residual"))


def test_residual_join_span_sits_in_its_layer_span(residual_spans):
    [(thread, lo, hi, stats)] = [(t, s, e, st)
                                 for t, name, s, e, st in residual_spans
                                 if name == "nc.residual"]
    assert stats["layer"] == JOIN
    [(l_thread, l_lo, l_hi)] = [
        (t, s, e) for t, name, s, e, st in residual_spans
        if name == "nc.layer" and st["layer"] == JOIN]
    assert l_thread == thread and l_lo <= lo and hi <= l_hi
    # no stage span holds it, and none opens inside it
    for t, name, s, e, _ in residual_spans:
        if name in STAGES and t == thread:
            assert e <= lo or s >= hi, name


def test_every_stage_span_appears(split_spans):
    names = {name for _, name, *_ in split_spans}
    assert names >= set(STAGES), set(STAGES) - names


def test_stage_spans_never_nest(split_spans):
    stages = sorted((s, e, thread) for thread, name, s, e, _ in split_spans
                    if name in STAGES)
    for thread in {t for *_, t in stages}:
        mine = [(s, e) for s, e, t in stages if t == thread]
        for (_, e0), (s1, _) in zip(mine, mine[1:]):
            assert s1 >= e0


def test_layer_span_carries_the_layer_name(split_spans):
    layers = [stats.get("layer") for _, name, *_, stats in split_spans
              if name == "nc.layer"]
    assert sorted(layers) == [CONV, "Mixed_t_s1_0"]


def test_stage_spans_cover_half_of_a_conv_layer(split_spans):
    [(thread, lo, hi)] = [(t, s, e) for t, name, s, e, stats in split_spans
                          if name == "nc.layer" and stats["layer"] == CONV]
    covered = sum(min(e, hi) - max(s, lo)
                  for t, name, s, e, _ in split_spans
                  if name in STAGES and t == thread and s < hi and e > lo)
    assert covered >= 0.5 * (hi - lo)


@pytest.mark.parametrize("engine,path", [
    ("host", "walk"), ("jit", "compiled"), ("pallas", "compiled")])
def test_pool_span_names_its_path(x32, tmp_path, engine, path):
    op = ("maxpool", 3, 2, "SAME")
    specs = []
    nc_network._op_specs("Pool_t", "Pool_t", op, 9, 8, specs)
    actq = np.random.default_rng(2).integers(
        0, 256, size=(1, 9, 9, 8)).astype(np.uint8)
    qps = [q.QuantParams(scale=np.float32(1 / 255), zero_point=0)]
    with jax.profiler.trace(str(tmp_path)):
        yq, _ = nc_network._nc_apply_op(
            actq, qps, "Pool_t", op, nc_network._Exec(
                {}, {s.name: s for s in specs}, {}, XEON_E5_35MB,
                sim.SimConstants(), engine, []))
    assert yq.shape == (1, 5, 5, 8)
    [stats] = [st for _, name, *_, st in _spans(tmp_path)
               if name == "nc.pool"]
    assert stats["path"] == path


@pytest.mark.parametrize("engine,path", [
    ("host", "tree"), ("jit", "reduce"), ("pallas", "reduce")])
def test_requant_spans_name_their_minmax_path(x32, tmp_path, engine, path):
    """The conv's requantizing ``nc.conv.epilogue`` and the join's
    ``nc.residual`` say how the min/max ran, and no span opens inside
    either (their self time stays the requant stage's)."""
    spans = _trace_residual(engine, tmp_path)
    carriers = [(t, s, e, st["minmax"]) for t, name, s, e, st in spans
                if "minmax" in st]
    names = sorted(name for t, name, s, e, st in spans if "minmax" in st)
    assert names == ["nc.conv.epilogue", "nc.residual"]
    assert [m for *_, m in carriers] == [path, path]
    for thread, lo, hi, _ in carriers:
        for t, name, s, e, _ in spans:
            if t == thread and (s, e) != (lo, hi):
                assert e <= lo or s >= hi or (s <= lo and hi <= e), name


def test_serving_step_span_names_its_requests(tmp_path):
    from repro.launch.serve import NCRequest, NCServingEngine

    cfg = inception.reduced_config(img=47, width_div=8, classes=8, stages=())
    params = {name: {"w": np.full((r, s, c, m), 0.01, np.float32),
                     "scale": np.ones(m, np.float32),
                     "bias": np.zeros(m, np.float32)}
              for name, r, s, c, m in nc_network.iter_convs(cfg)}
    eng = NCServingEngine(params, cfg, max_batch=2, engine="host")
    eng._forward = lambda x, schedule: (np.zeros((len(x), 8)), None)
    image = np.zeros((cfg.img, cfg.img, 3), np.float32)
    for rid in (5, 7):
        eng.submit(NCRequest(rid=rid, image=image))
    with jax.profiler.trace(str(tmp_path)):
        assert eng.step()
    [(_, _, _, _, stats)] = [s for s in _spans(tmp_path)
                             if s[1] == "nc.serve.step"]
    assert str(stats["batch"]) == "2"
    assert stats["request_ids"] == "5 7"
