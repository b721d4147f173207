"""Cross-backend differential conformance for the bit-serial hot path (PR 10).

``core/backends.py`` turns the old ad-hoc ``engine="host"|"jit"`` strings
into ONE registry of :class:`~repro.core.backends.Backend` entries — the
exact numpy host walk (the reference), the bucketed-jit decoded-lane
kernel, and the byte-packed Pallas bit-serial GEMM run through the
interpreter.  This suite is the registry's contract, enforced
differentially:

* **Byte-identity** — every registered backend must reproduce the host
  reference EXACTLY across the operating envelope: 8/4/2/1-bit operands
  (the 4-bit case exercises the W4A4 nibble kernel), SAME/VALID padding,
  stride 2, batch 1 and 4, non-dividing tiles, compressed (CSR bit-plane)
  and dense filter stores, integrity checking on and off, and 0/50/100%
  filter pruning.
* **Cycle invariance** — backends re-time EXECUTION, never the model:
  ``packed_dot_words`` charges §III cycles before dispatch, so every
  conformance case also asserts the modeled cycles are bit-identical to
  the host run's.
* **Selection is configuration** — the backend rides the plan
  (``plan_layer(..., backend=...)``), the ``NC_BACKEND`` environment
  variable, or an explicit ``engine=``; contradictions raise, unknown
  names raise a :class:`ValueError` listing the registered backends, and
  switching needs zero call-site edits (asserted via
  ``backends.dispatch_stats``).
* **Compile-cache reuse** — the bucketed-jit backend compiles exactly
  once per (planes, acc, K) bucket even when the same shapes flow
  through DIFFERENT layers (``engine_cache_info`` reporting matches).

Tier-1 runs the host+jit conformance; the ``pallas``
parametrizations carry the ``backends`` marker (the interpreter is slow)
and run under benchmarks/run.py's gate or
``pytest -m backends -o addopts=``.
"""
import numpy as np
import pytest

from repro.core import backends
from repro.core import bitserial as bs
from repro.core import nc_layers as nc
from repro.core import quantize as q
from repro.core import schedule as sched
from repro.core.cache_geometry import XEON_E5_35MB
from repro.core.mapper import LayerSpec

GEOM = XEON_E5_35MB

# host and jit conformance is tier-1; the interpret-mode sweep runs under
# the `backends` marker (satellite: pytest.ini addopts excludes it)
BACKENDS = ["host", "jit",
            pytest.param("pallas", marks=pytest.mark.backends)]


def _quantized_conv_case(seed, *, bits=8, M=6, C=3, R=3, prune=0.0,
                         batch=1, img=8):
    """Already-quantized integer operands for one conv case: unsigned
    ``bits``-plane activations/weights, ``round(M * prune)`` filters
    pinned to the weight zero point (dequantized exactly zero)."""
    rng = np.random.default_rng(seed)
    hi = 1 << bits
    zw = hi // 2
    wq = rng.integers(0, hi, size=(R, R, C, M)).astype(np.uint8)
    k = int(round(M * prune))
    if k:
        idx = rng.choice(M, size=k, replace=False)
        wq[..., idx] = zw
    shape = (batch, img, img, C) if batch > 1 else (img, img, C)
    xq = rng.integers(0, hi, size=shape).astype(np.uint8)
    x_qp = q.QuantParams(scale=np.float32(1 / hi), zero_point=1, bits=bits)
    w_qp = q.QuantParams(scale=np.float32(0.05), zero_point=zw, bits=bits)
    qps = [x_qp] * batch if batch > 1 else x_qp
    return xq, wq, qps, w_qp


# one row per envelope corner: bits x padding x stride x batch x ragged
# tiles x compressed x integrity x pruning (the cross product is curated,
# not exhaustive — every dimension varies at least twice)
CONV_CASES = [
    pytest.param(dict(bits=8), id="w8a8-valid-dense"),
    pytest.param(dict(bits=8, padding="SAME", stride=2, batch=4,
                      tile_pixels=7, prune=0.5), id="w8a8-same-s2-b4-ragged-p50"),
    pytest.param(dict(bits=8, batch=4, compressed=True, integrity=True,
                      tile_filters=5, prune=0.5), id="w8a8-b4-csr-abft-p50"),
    pytest.param(dict(bits=4), id="w4a4-valid-dense"),
    pytest.param(dict(bits=4, padding="SAME", stride=2, batch=4,
                      compressed=True, prune=0.5), id="w4a4-same-s2-b4-csr-p50"),
    pytest.param(dict(bits=2, integrity=True), id="w2a2-abft"),
    pytest.param(dict(bits=1, padding="SAME", batch=4, prune=0.5),
                 id="w1a1-same-b4-p50"),
    pytest.param(dict(bits=8, batch=4, prune=1.0), id="w8a8-b4-p100"),
]


def _run_conv(case, engine):
    kw = dict(case)
    xq, wq, qps, w_qp = _quantized_conv_case(
        0xC0FFEE, bits=kw.pop("bits"), prune=kw.pop("prune", 0.0),
        batch=kw.setdefault("batch", 1))
    kw.pop("batch")
    stride = kw.pop("stride", 1)
    out, cycles = nc.nc_conv2d(xq, wq, qps, w_qp, stride, geom=GEOM,
                               occupancy="detect", engine=engine, **kw)
    return np.asarray(out), cycles


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", CONV_CASES)
def test_conv_conformance(case, backend):
    """Differential harness: every backend == host, byte for byte, with
    modeled cycles bit-identical (backends re-time, never re-model)."""
    ref, ref_cycles = _run_conv(case, "host")
    backends.dispatch_stats_clear()
    out, cycles = _run_conv(case, backend)
    np.testing.assert_array_equal(out, ref)
    assert out.dtype == ref.dtype
    assert cycles == ref_cycles
    st = backends.dispatch_stats()[backend]
    if case.get("prune") != 1.0:  # fully pruned layers run zero passes
        assert st["native"] + st["fallback"] > 0  # the backend actually ran


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("batch", [1, 4])
def test_fc_conformance(backend, batch):
    """nc_fc (the 1x1-conv FC path) through every backend, K large enough
    (144) that the Pallas adapter runs natively (one row per word line)."""
    rng = np.random.default_rng(7)
    K, M = 144, 10
    x = rng.integers(0, 256, size=(batch, K) if batch > 1 else (K,))
    w = rng.integers(0, 256, size=(K, M)).astype(np.uint8)
    w[:, ::3] = 11  # a third of the filters prune to the zero point
    x_qp = q.QuantParams(scale=np.float32(1 / 256), zero_point=0)
    w_qp = q.QuantParams(scale=np.float32(0.02), zero_point=11)
    qps = [x_qp] * batch if batch > 1 else x_qp
    ref, ref_cycles = nc.nc_fc(x.astype(np.uint8), w, qps, w_qp,
                               occupancy="detect", engine="host")
    out, cycles = nc.nc_fc(x.astype(np.uint8), w, qps, w_qp,
                           occupancy="detect", engine=backend)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert cycles == ref_cycles


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("bits_x,bits_w", [(8, 8), (4, 4), (2, 4), (1, 8)])
@pytest.mark.parametrize("K", [144, 37, 9])
def test_dot_words_conformance(backend, bits_x, bits_w, K):
    """The hot-path entry itself: packed word grids through
    ``packed_dot_words`` on every backend — values AND cycles must match
    the host body bit for bit (K=9 puts rows sharing words, where the
    Pallas adapter must delegate to host, still exactly)."""
    rng = np.random.default_rng(K * 100 + bits_x * 10 + bits_w)
    T, M = 13, 5
    xw = nc._pack_x_rows(
        rng.integers(0, 1 << bits_x, size=(T, K)).astype(np.uint32), bits_x)
    ww = nc._pack_w_rows(
        rng.integers(0, 1 << bits_w, size=(M, K)).astype(np.uint32), bits_w)
    ref, ref_cycles = bs.packed_dot_words(xw, ww, K=K, acc_bits=32,
                                          engine="host")
    vals, cycles = bs.packed_dot_words(xw, ww, K=K, acc_bits=32,
                                       engine=backend)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(ref))
    assert cycles == ref_cycles


def test_pallas_interpret_dot_smoke():
    """Tier-1 keepalive for the Pallas adapter (the full sweep is
    `backends`-marked): one native interpret-mode dot, byte-identical,
    and the dispatch ledger proves the kernel path ran (no silent
    fallback-to-host conformance theater)."""
    rng = np.random.default_rng(3)
    K = 64
    xw = nc._pack_x_rows(rng.integers(0, 16, size=(4, K)).astype(np.uint32), 4)
    ww = nc._pack_w_rows(rng.integers(0, 16, size=(3, K)).astype(np.uint32), 4)
    ref, ref_cycles = bs.packed_dot_words(xw, ww, K=K, acc_bits=32,
                                          engine="host")
    backends.dispatch_stats_clear()
    vals, cycles = bs.packed_dot_words(xw, ww, K=K, acc_bits=32,
                                       engine="pallas")
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(ref))
    assert cycles == ref_cycles
    assert backends.dispatch_stats()["pallas"]["native"] == 1


# ---------------------------------------------------------------------------
# Satellite: unknown backend names raise, naming the registry
# ---------------------------------------------------------------------------
def test_unknown_engine_string_raises():
    rng = np.random.default_rng(0)
    xw = nc._pack_x_rows(rng.integers(0, 256, size=(2, 64)), 8)
    ww = nc._pack_w_rows(rng.integers(0, 256, size=(2, 64)), 8)
    with pytest.raises(ValueError) as ei:
        bs.packed_dot_words(xw, ww, K=64, acc_bits=32, engine="tpu-v9")
    msg = str(ei.value)
    assert "tpu-v9" in msg
    for name in backends.registered_backends():
        assert name in msg  # the error lists every registered backend


def test_unknown_engine_in_conv_raises():
    xq, wq, qps, w_qp = _quantized_conv_case(1)
    with pytest.raises(ValueError, match="registered backends"):
        nc.nc_conv2d(xq, wq, qps, w_qp, engine="cuda")


def test_unknown_env_backend_raises(monkeypatch):
    """The same ValueError surfaces from NC_BACKEND, attributed to the
    environment variable."""
    monkeypatch.setenv(backends.ENV_VAR, "no-such-backend")
    xq, wq, qps, w_qp = _quantized_conv_case(1)
    with pytest.raises(ValueError, match="NC_BACKEND environment"):
        nc.nc_conv2d(xq, wq, qps, w_qp)


def test_unknown_plan_backend_raises():
    spec = LayerSpec(name="c", kind="conv", H=8, R=3, S=3, C=3, M=6, E=6,
                     stride=1)
    with pytest.raises(ValueError, match="plan_layer"):
        sched.plan_layer(spec, GEOM, batch=1, backend="fpga")
    with pytest.raises(ValueError, match="plan_network"):
        sched.plan_network([spec], GEOM, batch=1, backend="fpga")


# ---------------------------------------------------------------------------
# Satellite: selection is pure configuration (plan pin / env var), with
# contradictions raised
# ---------------------------------------------------------------------------
def _conv_spec(M=6, C=3, R=3, img=8, stride=1):
    E = (img - R) // stride + 1
    return LayerSpec(name="c", kind="conv", H=img, R=R, S=R, C=C, M=M, E=E,
                     stride=stride)


def test_plan_backend_is_pure_config():
    """plan_layer(backend=...) routes execution with ZERO call-site edits:
    the same nc_conv2d call, no engine argument, runs whichever backend
    the plan pinned."""
    xq, wq, qps, w_qp = _quantized_conv_case(2)
    ref, ref_cycles = nc.nc_conv2d(xq, wq, qps, w_qp, engine="host")
    for name in ("jit", "host"):
        plan = sched.plan_layer(_conv_spec(), GEOM, batch=1, backend=name)
        assert plan.backend == name
        backends.dispatch_stats_clear()
        out, cycles = nc.nc_conv2d(xq, wq, qps, w_qp, plan=plan)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        assert cycles == ref_cycles
        assert backends.dispatch_stats()[name]["native"] > 0


def test_env_backend_is_pure_config(monkeypatch):
    """NC_BACKEND=jit flips the default engine with zero code changes."""
    xq, wq, qps, w_qp = _quantized_conv_case(3)
    ref, _ = nc.nc_conv2d(xq, wq, qps, w_qp, engine="host")
    monkeypatch.setenv(backends.ENV_VAR, "jit")
    backends.dispatch_stats_clear()
    out, _ = nc.nc_conv2d(xq, wq, qps, w_qp)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert backends.dispatch_stats()["jit"]["native"] > 0
    assert backends.dispatch_stats()["host"]["native"] == 0


def test_explicit_engine_beats_env(monkeypatch):
    monkeypatch.setenv(backends.ENV_VAR, "jit")
    xq, wq, qps, w_qp = _quantized_conv_case(4)
    backends.dispatch_stats_clear()
    nc.nc_conv2d(xq, wq, qps, w_qp, engine="host")
    assert backends.dispatch_stats()["jit"]["native"] == 0
    assert backends.dispatch_stats()["host"]["native"] > 0


def test_engine_contradicting_plan_raises():
    xq, wq, qps, w_qp = _quantized_conv_case(5)
    plan = sched.plan_layer(_conv_spec(), GEOM, batch=1, backend="jit")
    with pytest.raises(ValueError, match="ambiguous"):
        nc.nc_conv2d(xq, wq, qps, w_qp, plan=plan, engine="host")
    # agreement is NOT ambiguous (nc_forward hands matched engine + plans
    # down the layer loop)
    out, _ = nc.nc_conv2d(xq, wq, qps, w_qp, plan=plan, engine="jit")
    ref, _ = nc.nc_conv2d(xq, wq, qps, w_qp, engine="host")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_backend_pin_survives_tile_override_replan():
    """Tile-size overrides replan but must not drop the plan's backend pin
    (same carry rule as sparsity/overlap/integrity/compression)."""
    xq, wq, qps, w_qp = _quantized_conv_case(6)
    plan = sched.plan_layer(_conv_spec(), GEOM, batch=1, backend="jit")
    backends.dispatch_stats_clear()
    nc.nc_conv2d(xq, wq, qps, w_qp, plan=plan, tile_pixels=7)
    assert backends.dispatch_stats()["jit"]["native"] > 0


# ---------------------------------------------------------------------------
# Satellite: bucketed-jit compile-cache reuse across layers and backends
# ---------------------------------------------------------------------------
def test_jit_compile_cache_one_entry_per_bucket():
    """Exactly ONE engine-cache entry (and one compiled executable) per
    (x planes, w planes, acc, K) bucket, even when the same shapes flow
    through a conv and an FC layer: conv 3x3x16 on a 5x5 image and a
    9-row FC over 144 features land on identical padded tile shapes
    (rows 9 -> bucket 16, filters 6 -> bucket 8)."""
    rng = np.random.default_rng(11)
    bs.engine_cache_clear()
    xq = rng.integers(0, 256, size=(5, 5, 16)).astype(np.uint8)
    wq = rng.integers(0, 256, size=(3, 3, 16, 6)).astype(np.uint8)
    x_qp = q.QuantParams(scale=np.float32(1 / 256), zero_point=0)
    w_qp = q.QuantParams(scale=np.float32(0.05), zero_point=128)
    nc.nc_conv2d(xq, wq, x_qp, w_qp, engine="jit")
    info = bs.engine_cache_info()
    assert info["entries"] == 1
    assert info["keys"] == [(8, 8, 32, 144)]
    compiled_after_conv = info["compiled"]

    xf = rng.integers(0, 256, size=(9, 144)).astype(np.uint8)
    wf = rng.integers(0, 256, size=(144, 6)).astype(np.uint8)
    nc.nc_fc(xf, wf, [x_qp] * 9, w_qp, engine="jit")
    info = bs.engine_cache_info()
    assert info["entries"] == 1  # the FC reused the conv's bucket
    assert info["keys"] == [(8, 8, 32, 144)]
    # identical padded operand shapes -> the SAME executable served both
    # layers
    assert info["compiled"] == compiled_after_conv

    # the host backend never touches the compile cache
    nc.nc_conv2d(xq, wq, x_qp, w_qp, engine="host")
    assert bs.engine_cache_info() == info


def test_engine_cache_distinct_buckets():
    """Different (planes, acc, K) tuples get their own entry — the cache
    key is the bucket, nothing finer."""
    rng = np.random.default_rng(12)
    bs.engine_cache_clear()
    for bits, K in ((8, 64), (4, 64), (8, 96)):
        xw = nc._pack_x_rows(
            rng.integers(0, 1 << bits, size=(8, K)).astype(np.uint32), bits)
        ww = nc._pack_w_rows(
            rng.integers(0, 1 << bits, size=(4, K)).astype(np.uint32), bits)
        bs.packed_dot_words(xw, ww, K=K, acc_bits=32, engine="jit")
        bs.packed_dot_words(xw, ww, K=K, acc_bits=32, engine="jit")  # reuse
    info = bs.engine_cache_info()
    assert info["entries"] == 3
    assert set(info["keys"]) == {(8, 8, 32, 64), (4, 4, 32, 64),
                                 (8, 8, 32, 96)}


# ---------------------------------------------------------------------------
# Registry surface: capability flags and dispatch accounting
# ---------------------------------------------------------------------------
def test_registry_capability_flags():
    assert backends.registered_backends() == ("host", "jit",
                                              "pallas")
    host = backends.get_backend("host")
    assert host.acc_bits is None and host.supports_acc(24)
    assert host.max_lane_words is None
    pal = backends.get_backend("pallas")
    assert pal.supports_acc(32) and pal.supports_acc(24)
    assert not pal.supports_acc(16)
    assert pal.w4a4 and pal.compressed_planes and pal.integrity
    assert pal.max_lane_words is not None
    for name in backends.registered_backends():
        assert callable(backends.get_backend(name).dot_words)


def test_dispatch_stats_count_fallbacks():
    """Inputs outside the Pallas native envelope (rows sharing words,
    K <= 16) delegate to host and are COUNTED — the conformance suite's
    proof that 'native' assertions mean what they say."""
    rng = np.random.default_rng(13)
    backends.dispatch_stats_clear()
    xw = nc._pack_x_rows(rng.integers(0, 256, size=(3, 9)), 8)
    ww = nc._pack_w_rows(rng.integers(0, 256, size=(2, 9)), 8)
    ref, _ = bs.packed_dot_words(xw, ww, K=9, acc_bits=32, engine="host")
    vals, _ = bs.packed_dot_words(xw, ww, K=9, acc_bits=32,
                                  engine="pallas")
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(ref))
    st = backends.dispatch_stats()["pallas"]
    assert st == {"native": 0, "fallback": 1}


def test_pallas_on_tpu_raises_instead_of_falling_back(monkeypatch):
    """On a TPU an input outside the Pallas envelope raises: the device
    path never answers from the host (the platform check is steered)."""
    from repro.kernels import ops

    rng = np.random.default_rng(14)
    xw = nc._pack_x_rows(rng.integers(0, 256, size=(3, 9)), 8)
    ww = nc._pack_w_rows(rng.integers(0, 256, size=(2, 9)), 8)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    backends.dispatch_stats_clear()
    with pytest.raises(ValueError, match="rows share words"):
        bs.packed_dot_words(xw, ww, K=9, acc_bits=32, engine="pallas")
    assert backends.dispatch_stats()["pallas"] == {"native": 0,
                                                   "fallback": 0}


def test_tpu_platform_defaults_to_pallas(monkeypatch):
    """On a TPU ``pallas`` is the default below explicit engine, plan pin
    and NC_BACKEND; on CPU each caller keeps its own default."""
    from repro.kernels import ops

    monkeypatch.delenv(backends.ENV_VAR, raising=False)
    assert backends.resolve_backend(default="jit") == "jit"
    assert backends.default_backend() == "host"
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert backends.default_backend() == "pallas"
    assert backends.resolve_backend(default="jit") == "pallas"
    assert backends.resolve_backend("host") == "host"
    assert backends.resolve_backend(None, "jit") == "jit"
    monkeypatch.setenv(backends.ENV_VAR, "jit")
    assert backends.resolve_backend() == "jit"


# ---------------------------------------------------------------------------
# Serving: backend names validated at deployment, calibration per backend
# ---------------------------------------------------------------------------
def test_serving_engine_backend_validation_and_switch():
    """NCServingEngine validates ``engine=`` against the registry at
    construction (a typo fails at deployment, not mid-traffic), and
    ``set_engine`` resets BOTH the priced-plan memo and the measured
    calibration — wall/modeled scale is a property of the execution body
    (docs/SERVING.md)."""
    import jax

    from repro.launch import serve
    from repro.models import inception

    cfg = inception.reduced_config(img=47, width_div=8, classes=8,
                                   stages=("a",))
    params = inception.init_params(jax.random.PRNGKey(0), config=cfg)
    with pytest.raises(ValueError, match="registered backends"):
        serve.NCServingEngine(params, cfg, engine="warp-drive")

    eng = serve.NCServingEngine(params, cfg, engine="host")
    eng.latency_model.observe(1, 0.5)
    assert eng.latency_model.calibrated
    eng.set_engine("host")  # same backend: calibration survives
    assert eng.latency_model.calibrated
    eng.set_engine("jit")  # backend switch: recalibrate from scratch
    assert eng.engine == "jit"
    assert not eng.latency_model.calibrated
    assert eng.latency_model.scale == 1.0
    with pytest.raises(ValueError, match="registered backends"):
        eng.set_engine("warp-drive")


# ---------------------------------------------------------------------------
# Satellite: interpret-mode Pallas inside the full network (slow + backends)
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.backends
def test_nc_forward_pallas_interpret_end_to_end():
    """One reduced-Inception forward routed through ``pallas``
    as a pure config change (``plan_network(backend=...)``): logits and
    modeled cycles byte-identical to the host run, with the dispatch
    ledger showing the Pallas kernel natively served the large-K layers
    (small-K layers legitimately delegate)."""
    import jax
    import jax.numpy as jnp

    from repro.models import inception

    cfg = inception.reduced_config(img=47, width_div=8, classes=8,
                                   stages=("a",))
    key = jax.random.PRNGKey(0)
    params = inception.init_params(key, config=cfg)
    x = jax.random.uniform(key, (47, 47, 3), jnp.float32)

    ref, ref_report = inception.nc_forward(params, x, config=cfg,
                                           engine="host")
    specs = inception.inception_v3_specs(cfg)
    schedule = sched.plan_network(specs, GEOM, batch=1,
                                  backend="pallas")
    assert schedule.backend == "pallas"
    backends.dispatch_stats_clear()
    out, report = inception.nc_forward(params, x, config=cfg,
                                       schedule=schedule)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert report.total_emulated_cycles == ref_report.total_emulated_cycles
    assert report.total_modeled_cycles == ref_report.total_modeled_cycles
    st = backends.dispatch_stats()["pallas"]
    assert st["native"] > 0

    # contradicting the schedule's pin raises (the plan already decided)
    with pytest.raises(ValueError, match="ambiguous"):
        inception.nc_forward(params, x, config=cfg, schedule=schedule,
                             engine="host")
