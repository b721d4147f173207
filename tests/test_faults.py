"""Fault injection + integrity verification (PR 7).

Four layers of defense:

* **Determinism** — the same :class:`~repro.core.faults.FaultProfile`
  seed produces the SAME fault events, independent of run order or
  retries (per-site CRC-keyed generators).
* **Zero silent corruption** — every covered fault class injected into
  a conv is detected by the per-pass ABFT checksums
  (``corrupt_attempts == detected``, an exact equality because
  injection targets only output-changing lanes) and the recovered
  logits are BYTE-IDENTICAL to clean execution; stuck-at faults drive
  the quarantine + re-plan path and still recover.
* **Exact additive pricing** — integrity-off plans, modeled cycles and
  emulated cycles are bit-identical to PR 6; integrity-on pricing adds
  exactly ``checksum_pass_cycles`` per executed pass (per layer, per
  batch, and inside every sparsity credit identity), and the emulation
  charges the same checksum + re-execution cycles it reports.
* **Resilient serving** — an exception mid-batch fails only the
  admitted batch (LM and Neural Cache engines both keep draining), the
  NC recovery ladder walks retry -> fallback schedule -> float
  reference -> failed, and degraded batches are excluded from the
  :class:`~repro.core.slo.LatencyModel` calibration.

The heavy class x rate x padding x batch property sweep is marked
``faults`` (excluded from tier-1 like ``slow``; exercised by
``benchmarks/run.py``'s gate or ``pytest -m faults -o addopts=``).
"""
from __future__ import annotations

import contextlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import faults
from repro.core import nc_layers as nc
from repro.core import quantize as q
from repro.core import schedule as sched
from repro.core.cache_geometry import XEON_E5_35MB
from repro.core.mapper import LayerSpec
from repro.core.simulator import (SimConstants, batch_time_s,
                                  modeled_layer_cycles, simulate_layer,
                                  simulate_network)
from repro.models import inception

GEOM = XEON_E5_35MB
GEOM_1SLICE = XEON_E5_35MB.scaled(1)


# ---------------------------------------------------------------------------
# Helpers: one small conv workload shared by the detection tests
# ---------------------------------------------------------------------------
def _conv_case(seed=0, B=2, img=8, C=3, M=16):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-1, 1, (B, img, img, C)).astype(np.float32)
    w = rng.uniform(-1, 1, (3, 3, C, M)).astype(np.float32)
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    w_qp = q.choose_qparams(jnp.float32(w.min()), jnp.float32(w.max()))
    return x, w, x_qp, w_qp


def _conv(case, padding="SAME", geom=GEOM, **kw):
    x, w, x_qp, w_qp = case
    return nc.nc_conv2d(x, w, [x_qp] * x.shape[0], w_qp, stride=1,
                        padding=padding, geom=geom, **kw)


def _profile_for(cls, rate=1.0, seed=5, geom=GEOM, layer="nc_conv2d"):
    """A FaultProfile exercising one covered class on this geometry; the
    stuck profile targets the slice pass 0 actually maps to."""
    if cls == "stuck":
        probe = faults.FaultState(faults.FaultProfile(n_slices=geom.n_slices))
        sid = probe.slice_for(layer, 0)
        return faults.FaultProfile(seed=seed, stuck_slices=(sid,),
                                   n_slices=geom.n_slices)
    kw = {"filter_flip": "filter_flip_rate", "act_flip": "act_flip_rate",
          "compute": "compute_rate"}[cls]
    return faults.FaultProfile(seed=seed, n_slices=geom.n_slices,
                               **{kw: rate})


# ---------------------------------------------------------------------------
# FaultProfile: parsing and validation
# ---------------------------------------------------------------------------
def test_profile_parse_roundtrip():
    p = faults.FaultProfile.parse(
        "seed=7,filter=0.05,act=0.01,compute=0.02,stuck=2+5,stall=0.1:0.002")
    assert p.seed == 7
    assert p.filter_flip_rate == 0.05 and p.act_flip_rate == 0.01
    assert p.compute_rate == 0.02
    assert p.stuck_slices == (2, 5)
    assert p.stall_rate == 0.1 and p.stall_s == 0.002
    assert p.any_faults
    # stall without an explicit duration defaults to 1 ms
    assert faults.FaultProfile.parse("stall=0.5").stall_s == 0.001
    # stuck ids dedupe and sort
    assert faults.FaultProfile.parse("stuck=5+2+5").stuck_slices == (2, 5)
    assert not faults.FaultProfile.parse("seed=3").any_faults


def test_profile_validation_errors():
    with pytest.raises(ValueError, match="outside"):
        faults.FaultProfile(filter_flip_rate=1.5)
    with pytest.raises(ValueError, match="out of range"):
        faults.FaultProfile(stuck_slices=(99,), n_slices=4)
    with pytest.raises(ValueError, match="every slice stuck"):
        faults.FaultProfile(stuck_slices=(0, 1), n_slices=2)
    with pytest.raises(ValueError, match="unknown fault-profile key"):
        faults.FaultProfile.parse("bogus=1")
    with pytest.raises(ValueError, match="key=value"):
        faults.FaultProfile.parse("seed")


# ---------------------------------------------------------------------------
# Determinism: same seed => same faults
# ---------------------------------------------------------------------------
def test_same_seed_produces_identical_faults():
    case = _conv_case()
    prof = faults.FaultProfile(seed=9, filter_flip_rate=1.0,
                               act_flip_rate=1.0, compute_rate=1.0,
                               n_slices=GEOM.n_slices)

    def run():
        with faults.inject(prof) as fs:
            out, _ = _conv(case, integrity=True)
        return np.asarray(out), list(fs.events), fs.stats()

    out_a, ev_a, st_a = run()
    out_b, ev_b, st_b = run()
    assert ev_a == ev_b and st_a == st_b
    np.testing.assert_array_equal(out_a, out_b)
    assert st_a["injected"] > 0
    # a different seed draws different fault sites
    with faults.inject(faults.FaultProfile(
            seed=10, filter_flip_rate=1.0, act_flip_rate=1.0,
            compute_rate=1.0, n_slices=GEOM.n_slices)) as fs:
        _conv(case, integrity=True)
    assert list(fs.events) != ev_a


# ---------------------------------------------------------------------------
# Integrity off => bit-identical to PR 6 (plans, modeled cycles, emulation)
# ---------------------------------------------------------------------------
def test_integrity_off_bit_identical_everywhere():
    spec = LayerSpec(name="s", kind="conv", H=14, R=3, S=3, C=16, M=32, E=12)
    default = sched.plan_layer(spec, GEOM, batch=2)
    explicit = sched.plan_layer(spec, GEOM, batch=2, integrity=False)
    assert default == explicit
    assert default.integrity is False and default.quarantined_slices == ()
    m_off = modeled_layer_cycles(default, GEOM)
    assert m_off["integrity_cycles"] == 0
    assert m_off["total_cycles"] == (m_off["per_pass_cycles"]
                                     * default.executed_passes)
    assert m_off["integrity_s"] == 0.0
    # the network planner threads the flag without changing off-plans
    specs = inception.inception_v3_specs(inception.reduced_config())
    net_a = sched.plan_network(specs, GEOM, batch=2)
    net_b = sched.plan_network(specs, GEOM, batch=2, integrity=False)
    assert net_a.integrity is False
    for s in specs:
        assert net_a.plan(s.name) == net_b.plan(s.name)
    # emulation: integrity=False is the default path, cycle for cycle
    case = _conv_case()
    out0, cyc0 = _conv(case)
    out1, cyc1 = _conv(case, integrity=False)
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(out1))
    assert cyc0 == cyc1


def test_clean_integrity_run_prices_checksums_exactly():
    """No faults: checked logits byte-identical, and the emulated cycle
    delta IS the reported checksum cost (exact additive, zero re-exec)."""
    case = _conv_case()
    out0, cyc0 = _conv(case)
    out1, cyc1, st = _conv(case, integrity=True, return_stats=True)
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(out1))
    assert st.integrity and st.faults_detected == 0
    assert st.reexec_passes == 0 and st.reexec_cycles == 0
    assert st.verify_passes == st.tiles > 0
    assert cyc1 - cyc0 == st.integrity_cycles > 0
    # the checked path executes serially: overlap never reports true
    outo, cyco, sto = _conv(case, integrity=True, overlap=True,
                            return_stats=True)
    assert sto.overlap is False
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(outo))
    assert cyco == cyc1


# ---------------------------------------------------------------------------
# Exact additive pricing: per layer, inside the sparsity credit, per batch
# ---------------------------------------------------------------------------
def test_modeled_integrity_cycles_exact_additive_per_layer():
    spec = LayerSpec(name="s", kind="conv", H=37, R=3, S=3, C=32, M=64, E=35)
    const = SimConstants()
    occ = sched.LayerOccupancy(spec.M, tuple(range(24)))
    for occupancy in (None, occ):
        p_off = sched.plan_layer(spec, GEOM_1SLICE, batch=2,
                                 occupancy=occupancy)
        p_on = sched.plan_layer(spec, GEOM_1SLICE, batch=2,
                                occupancy=occupancy, integrity=True)
        assert p_on.serial_passes == p_off.serial_passes
        assert p_on.skipped_passes == p_off.skipped_passes
        m_off = modeled_layer_cycles(p_off, GEOM_1SLICE, const)
        m_on = modeled_layer_cycles(p_on, GEOM_1SLICE, const)
        cs = const.checksum_pass_cycles
        assert (m_on["total_cycles"] - m_off["total_cycles"]
                == cs * p_on.executed_passes)
        assert m_on["integrity_cycles"] == cs * p_on.executed_passes
        assert (m_on["skip_credit_cycles"] - m_off["skip_credit_cycles"]
                == cs * p_on.skipped_passes)
        assert m_on["reexec_pass_cycles"] == m_off["per_pass_cycles"] + cs
        # seconds follow the same additive term, and nothing else moved
        r_off = simulate_layer(p_off, GEOM_1SLICE, const)
        r_on = simulate_layer(p_on, GEOM_1SLICE, const)
        assert r_on.integrity_s == pytest.approx(
            cs * p_on.executed_passes / GEOM_1SLICE.compute_freq_hz,
            rel=1e-12)
        assert r_on.compute_s - r_off.compute_s == pytest.approx(
            r_on.integrity_s, rel=1e-12)
        assert r_on.mac_s == r_off.mac_s and r_on.reduce_s == r_off.reduce_s
        assert r_on.filter_s == r_off.filter_s
    # sparsity credit identity survives integrity: dense - sparse == credit
    dense_on = modeled_layer_cycles(
        sched.plan_layer(spec, GEOM_1SLICE, batch=2, integrity=True),
        GEOM_1SLICE, const)
    sparse_on = modeled_layer_cycles(
        sched.plan_layer(spec, GEOM_1SLICE, batch=2, occupancy=occ,
                         integrity=True), GEOM_1SLICE, const)
    assert (dense_on["total_cycles"] - sparse_on["total_cycles"]
            == sparse_on["skip_credit_cycles"])


def test_network_integrity_pricing_exact_additive_per_batch():
    specs = inception.inception_v3_specs(inception.reduced_config())
    s_off = sched.plan_network(specs, GEOM, batch=2)
    s_on = sched.plan_network(specs, GEOM, batch=2, integrity=True)
    assert s_on.integrity is True
    r_off = simulate_network(s_off)
    r_on = simulate_network(s_on)
    assert r_off.integrity_s == 0.0 and r_on.integrity_s > 0.0
    # overlap's hidden-load credit is untouched by the checksum term
    assert r_on.hidden_s == r_off.hidden_s
    for b in (1, 2, 4):
        assert (batch_time_s(r_on, b) - batch_time_s(r_off, b)
                == pytest.approx(b * r_on.integrity_s, rel=1e-12))


# ---------------------------------------------------------------------------
# Zero silent corruption: every covered class detected and recovered
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cls", faults.COVERED_CLASSES)
def test_covered_class_detected_and_recovered(cls):
    case = _conv_case()
    ref, cyc_ref = _conv(case)
    with faults.inject(_profile_for(cls)) as fs:
        out, cyc, st = _conv(case, integrity=True, return_stats=True)
    assert fs.corrupt_attempts > 0, f"{cls}: nothing injected at rate 1"
    assert fs.detected == fs.corrupt_attempts  # zero silent corruption
    assert fs.reexecuted == st.reexec_passes > 0
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    # the emulated total is the clean work plus EXACTLY the verification
    # and re-execution cycles the stats report
    assert cyc == cyc_ref + st.integrity_cycles + st.reexec_cycles


def test_faults_without_integrity_corrupt_silently():
    """The control: the same injection without checksums flows corruption
    straight into the logits — detection is the integrity layer's doing,
    not an artifact of the injection being inert."""
    case = _conv_case()
    ref, _ = _conv(case)
    with faults.inject(_profile_for("compute")) as fs:
        out, _ = _conv(case)
    assert fs.corrupt_attempts > 0 and fs.detected == 0
    assert not np.array_equal(np.asarray(ref), np.asarray(out))


def test_detection_with_jit_engine():
    """The bucketed-jit engine pads tiles to bucket sizes; injection must
    bound its picks to live lanes/filters so every fault stays
    output-changing (corrupt_attempts == detected survives padding)."""
    case = _conv_case()
    ref, _ = _conv(case, engine="jit")
    for cls in ("filter_flip", "compute"):
        with faults.inject(_profile_for(cls)) as fs:
            out, _ = _conv(case, engine="jit", integrity=True)
        assert fs.corrupt_attempts > 0
        assert fs.detected == fs.corrupt_attempts
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_stuck_slice_quarantined_and_replanned():
    case = _conv_case()
    ref, _ = _conv(case)
    prof = _profile_for("stuck")
    sid = prof.stuck_slices[0]
    with faults.inject(prof) as fs:
        out, cyc, st = _conv(case, integrity=True, return_stats=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    # persistent corruption exhausted the retry budget, quarantined the
    # stuck slice and re-planned over the survivors
    assert sid in fs.quarantined
    assert fs.stats()["quarantined_slices"] == tuple(sorted(fs.quarantined))
    assert sid in st.quarantined_slices
    assert st.plan is not None and sid in st.plan.quarantined_slices
    assert fs.detected == fs.corrupt_attempts > prof.max_retries
    assert st.reexec_passes >= prof.max_retries


def test_unrecoverable_corruption_raises_integrity_error():
    """A fault that persists across retries AND quarantine must surface as
    IntegrityError (the serving ladder's trigger), never as silently
    corrupt output.  On a 1-slice geometry there is no slice left to
    quarantine, so the budget is exactly max_retries re-executions."""
    case = _conv_case(img=6, M=8)
    prof = faults.FaultProfile(seed=0, n_slices=GEOM_1SLICE.n_slices)
    with faults.inject(prof) as fs:
        def always_corrupt(vals, layer, pass_index, *, filters, rows):
            out = np.array(vals, dtype=np.int64, copy=True)
            out[0, 0] += 1
            return out

        fs.corrupt_values = always_corrupt
        with pytest.raises(faults.IntegrityError) as ei:
            _conv(case, geom=GEOM_1SLICE, integrity=True)
    assert ei.value.layer == "nc_conv2d"
    assert ei.value.attempts == prof.max_retries + 1
    assert fs.detected == prof.max_retries + 1
    assert fs.reexecuted == prof.max_retries


# ---------------------------------------------------------------------------
# LM serving: a mid-batch failure fails only the admitted batch
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def lm():
    from repro.configs import get_config, reduced_config
    from repro.models import transformer as T
    cfg = reduced_config(get_config("qwen2-7b"), n_layers=1, d_model=32,
                         d_ff=64, vocab_size=64, head_dim=16)
    return cfg, T.init_lm(cfg, jax.random.key(0))


def _lm_requests(cfg, n, max_tokens=3):
    from repro.launch.serve import Request
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(
        2, cfg.vocab_size, 6).astype(np.int32), max_tokens=max_tokens)
        for i in range(n)]


def test_lm_engine_decode_failure_fails_batch_keeps_draining(lm):
    from repro.launch.serve import ServingEngine
    cfg, params = lm
    eng = ServingEngine(cfg, params, max_batch=2, max_len=32)
    for r in _lm_requests(cfg, 3):
        eng.submit(r)
    orig, calls = eng._decode, []

    def flaky(*a, **kw):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("decode exploded")
        return orig(*a, **kw)

    eng._decode = flaky
    done = eng.run()
    # the first admitted batch (2 requests) failed; the third completed
    assert [r.rid for r in eng.failed] == [0, 1]
    assert all(r.failed and r.error == "decode exploded" for r in eng.failed)
    assert eng.errors == ["decode exploded"]
    assert [r.rid for r in done] == [2] and not done[0].failed
    assert not eng.queue and not any(s.active for s in eng.slots)


def test_lm_engine_prefill_failure_fails_one_request(lm, monkeypatch):
    from repro.launch import serve
    from repro.launch.serve import ServingEngine
    cfg, params = lm
    eng = ServingEngine(cfg, params, max_batch=2, max_len=32)
    for r in _lm_requests(cfg, 3):
        eng.submit(r)
    orig, calls = serve.T.prefill, []

    def flaky(*a, **kw):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("prefill oom")
        return orig(*a, **kw)

    monkeypatch.setattr(serve.T, "prefill", flaky)
    done = eng.run()
    assert [r.rid for r in eng.failed] == [0]
    assert eng.failed[0].error == "prefill oom"
    assert sorted(r.rid for r in done) == [1, 2]


# ---------------------------------------------------------------------------
# NC serving: recovery ladder, calibration hygiene, no stranded requests
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    cfg = inception.reduced_config(img=47, width_div=8, classes=8, stages=())
    params = inception.init_params(jax.random.PRNGKey(0), config=cfg)
    return cfg, params


def _nc_engine(cfg, params, **kw):
    from repro.launch.serve import NCServingEngine
    return NCServingEngine(params, cfg, **kw)


def _submit_images(eng, cfg, n, seed=0):
    from repro.launch.serve import NCRequest
    rng = np.random.default_rng(seed)
    imgs = rng.random((n, cfg.img, cfg.img, 3)).astype(np.float32)
    for r in range(n):
        eng.submit(NCRequest(rid=r, image=imgs[r]))
    return imgs


def test_nc_engine_transient_failure_retried_and_observed(tiny):
    """Rung 1: a transient raise is retried on the primary schedule; the
    batch is NOT degraded and its TRUE wall (including the failed
    attempt) calibrates the latency model."""
    cfg, params = tiny
    eng = _nc_engine(cfg, params, max_batch=2)
    imgs = _submit_images(eng, cfg, 2)
    orig, calls = eng._forward, []

    def flaky(x, schedule):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("transient")
        return orig(x, schedule)

    eng._forward = flaky
    done = eng.run()
    assert len(done) == 2 and all(r.degraded is None for r in done)
    assert eng.retries == 1 and eng.degraded_batches == 0
    assert eng.latency_model.samples == eng.steps == 1
    assert eng.latency_model.excluded == 0
    for r in done:
        ref, _ = inception.nc_forward(params, imgs[r.rid], config=cfg)
        np.testing.assert_array_equal(r.logits, np.asarray(ref))


def test_nc_engine_fallback_schedule_excluded_from_calibration(tiny):
    """Rung 2: the primary schedule keeps failing, the dense/no-overlap
    fallback serves the batch — results still bit-identical, but the
    wall time stays OUT of the calibration."""
    cfg, params = tiny
    eng = _nc_engine(cfg, params, max_batch=2)
    imgs = _submit_images(eng, cfg, 2)
    orig = eng._forward

    def primary_broken(x, schedule):
        if any(schedule is s for s in eng._fallback_schedules.values()):
            return orig(x, schedule)
        raise RuntimeError("primary plan broken")

    eng._forward = primary_broken
    done = eng.run()
    assert len(done) == 2
    assert all(r.degraded == "fallback-schedule" and not r.failed
               for r in done)
    assert eng.degraded_batches == 1 and eng.retries == 1
    assert eng.latency_model.samples == 0
    assert eng.latency_model.excluded == 1
    s = eng.stats()
    assert s["calibration_excluded"] == 1 and s["degraded_batches"] == 1
    for r in done:
        ref, _ = inception.nc_forward(params, imgs[r.rid], config=cfg)
        np.testing.assert_array_equal(r.logits, np.asarray(ref))


def test_nc_engine_float_reference_rung(tiny):
    """Rung 3: every emulated path raises; the float reference answers the
    request (tagged, excluded from calibration, no emulation report)."""
    cfg, params = tiny
    eng = _nc_engine(cfg, params, max_batch=2)
    _submit_images(eng, cfg, 2)

    def broken(x, schedule):
        raise RuntimeError("emulation down")

    eng._forward = broken
    done = eng.run()
    assert len(done) == 2 and all(r.degraded == "float" for r in done)
    assert all(r.logits is not None and not r.failed for r in done)
    assert eng.reports == [] and eng.latency_model.excluded == 1
    assert eng.degraded_batches == 1


def test_nc_engine_no_float_rung_on_tpu(tiny, monkeypatch):
    """On a TPU the ladder has no float rung: a failure on the device
    fails the batch with its error recorded, instead of answering from
    the float reference path."""
    from repro.kernels import ops

    cfg, params = tiny
    eng = _nc_engine(cfg, params, max_batch=2)
    _submit_images(eng, cfg, 2)

    def broken(x, schedule):
        raise RuntimeError("device down")

    eng._forward = broken
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    done = eng.run()
    assert done == [] and not eng.queue
    assert all(r.failed and r.error == "device down" for r in eng.failed)
    assert len(eng.failed) == 2 and eng.degraded_batches == 0


def test_nc_engine_unreclaimable_batch_fails_and_drains(tiny):
    """Rung 4: the whole ladder fails — the batch is marked failed with
    the error recorded, and the engine drains the remaining queue
    instead of unwinding (no stranded requests)."""
    cfg, params = tiny
    eng = _nc_engine(cfg, params, max_batch=2)
    _submit_images(eng, cfg, 3)

    def broken(x, schedule):
        raise RuntimeError("emulation down")

    eng._forward = broken
    eng.network = types.SimpleNamespace(
        apply=lambda *a, **kw: (_ for _ in ()).throw(
            RuntimeError("float down")))
    done = eng.run()
    assert done == [] and not eng.queue
    assert sorted(r.rid for r in eng.failed) == [0, 1, 2]
    assert all(r.failed and r.error == "float down" for r in eng.failed)
    assert eng.steps == 2 and len(eng.errors) == 2
    s = eng.stats()
    assert s["failed"] == 3 and s["errors"] == ["float down"] * 2


def test_nc_engine_retry_budget_bounded_by_deadline(tiny):
    """With an SLO, rung-1 retries are bounded by the oldest request's
    REMAINING budget: a blown deadline means zero retries — the ladder
    goes straight to the fallback schedule."""
    cfg, params = tiny
    clock = {"t": 0.0}
    eng = _nc_engine(cfg, params, max_batch=2, slo_ms=1000.0,
                     now_fn=lambda: clock["t"])
    _submit_images(eng, cfg, 1)
    orig = eng._forward

    def primary_broken(x, schedule):
        if any(schedule is s for s in eng._fallback_schedules.values()):
            return orig(x, schedule)
        raise RuntimeError("primary plan broken")

    eng._forward = primary_broken
    clock["t"] = 5.0  # 5 s elapsed >> 1 s SLO: budget is negative
    assert eng.step(flush=True)
    assert eng.retries == 0  # no budget left for a retry
    assert eng.degraded_batches == 1
    assert eng.completed[0].degraded == "fallback-schedule"


def test_latency_model_exclude_never_touches_calibration(tiny):
    from repro.core.slo import LatencyModel
    specs = inception.inception_v3_specs(inception.reduced_config())
    m = LatencyModel(lambda b: sched.plan_network(specs, GEOM, batch=b),
                     window=4)
    base = m.modeled_batch_s(1)
    m.observe(1, 2.0 * base)
    before = (m.scale, m.samples, list(m._recent))
    m.exclude(1, 1000.0 * base)  # a degraded batch's pathological wall
    assert (m.scale, m.samples, list(m._recent)) == before
    assert m.excluded == 1
    assert m.worst == pytest.approx(2.0)  # the spike never entered the tail
    # a fault-retry spike that WAS observed (primary success) ages out of
    # the windowed p99 as steady-state observations refill the window
    m.observe(1, 100.0 * base)
    assert m.worst == pytest.approx(100.0)
    for _ in range(4):
        m.observe(1, 2.0 * base)
    assert m.worst == pytest.approx(2.0)
    assert m.predict_p99_s(1) < 100.0 * base


# ---------------------------------------------------------------------------
# nc_forward integration: end-to-end counters and serving under faults
# ---------------------------------------------------------------------------
def test_nc_forward_integrity_clean_and_faulted_bit_identical():
    cfg = inception.reduced_config(img=31, width_div=8, classes=8,
                                   stages=())
    params = inception.init_params(jax.random.PRNGKey(0), config=cfg)
    rng = np.random.default_rng(3)
    x = rng.random((cfg.img, cfg.img, 3)).astype(np.float32)
    ref, rep0 = inception.nc_forward(params, x, config=cfg)
    out, rep1 = inception.nc_forward(params, x, config=cfg, integrity=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    assert all(r.integrity for r in rep1.layers
               if r.kind in ("conv", "fc"))
    # integrity=True on an explicit schedule is ambiguous — rejected
    net = sched.plan_network(inception.inception_v3_specs(cfg), GEOM,
                             batch=1, integrity=True)
    with pytest.raises(ValueError, match="schedule"):
        inception.nc_forward(params, x, config=cfg, schedule=net,
                             integrity=True)
    # the integrity-planned schedule routes the checked path end to end
    # under faults, recovering bit-identically with consistent counters
    prof = faults.FaultProfile(seed=2, filter_flip_rate=0.5,
                               compute_rate=0.5, n_slices=GEOM.n_slices)
    with faults.inject(prof) as fs:
        outf, repf = inception.nc_forward(params, x, config=cfg,
                                          schedule=net)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(outf))
    assert fs.corrupt_attempts > 0 and fs.detected == fs.corrupt_attempts
    assert sum(r.reexec_passes for r in repf.layers) == fs.reexecuted > 0


@pytest.mark.faults
def test_nc_serving_under_faults_no_stranded_requests(tiny):
    """End to end: an integrity-armed engine under aggressive injection
    finishes every request with logits byte-identical to clean
    standalone runs — zero silent corruption at the serving boundary."""
    cfg, params = tiny
    eng = _nc_engine(cfg, params, max_batch=2, integrity=True)
    imgs = _submit_images(eng, cfg, 4, seed=7)
    prof = faults.FaultProfile(seed=3, filter_flip_rate=1.0,
                               act_flip_rate=1.0, compute_rate=1.0,
                               n_slices=eng.geom.n_slices)
    with faults.inject(prof) as fs:
        done = eng.run()
    assert len(done) == 4 and not eng.failed and not eng.queue
    assert fs.corrupt_attempts > 0
    assert fs.detected == fs.corrupt_attempts
    for r in done:
        assert not r.failed and r.degraded is None
        ref, _ = inception.nc_forward(params, imgs[r.rid], config=cfg)
        np.testing.assert_array_equal(r.logits, np.asarray(ref))


# ---------------------------------------------------------------------------
# Property sweep (marked `faults`): class x rate x padding x batch
# ---------------------------------------------------------------------------
@pytest.mark.faults
@pytest.mark.parametrize("cls", faults.COVERED_CLASSES)
@pytest.mark.parametrize("rate", (0.3, 1.0))
@pytest.mark.parametrize("padding", ("SAME", "VALID"))
@pytest.mark.parametrize("B", (1, 4))
def test_fault_sweep_zero_silent_corruption(cls, rate, padding, B):
    if cls == "stuck" and rate != 1.0:
        pytest.skip("stuck-at is rate-independent (persistent)")
    case = _conv_case(seed=hash((cls, B)) % 100, B=B)
    ref, cyc_ref = _conv(case, padding=padding)
    with faults.inject(_profile_for(cls, rate=rate, seed=11)) as fs:
        out, cyc, st = _conv(case, padding=padding, integrity=True,
                             return_stats=True)
    # EVERY corrupted pass was detected (possibly zero at low rates), and
    # the recovered logits are byte-identical to clean execution
    assert fs.detected == fs.corrupt_attempts
    assert fs.reexecuted == st.reexec_passes
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    assert cyc == cyc_ref + st.integrity_cycles + st.reexec_cycles
