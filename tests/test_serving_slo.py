"""SLO-aware serving admission (core/slo.py + launch/serve.py).

Covers the latency model (monotone predictions, wall-time calibration),
the admission policy (SLO-bounded batch pick, ragged-tail early admission,
the stream-batch-limit cap), and the engine integration: SLO hit/miss
accounting on an injectable clock, plan-cache reuse across admitted batch
sizes, and bit-identity of policy-batched results vs standalone
``nc_forward`` runs."""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from repro.core.cache_geometry import XEON_E5_35MB
from repro.core.schedule import plan_network
from repro.core.simulator import batch_time_s, simulate_network, throughput
from repro.core.slo import AdmissionDecision, AdmissionPolicy, LatencyModel
from repro.models import inception

GEOM = XEON_E5_35MB


@pytest.fixture(scope="module")
def paper_model():
    specs = inception.inception_v3_specs()
    return LatencyModel(lambda b: plan_network(specs, GEOM, batch=b))


# ---------------------------------------------------------------------------
# LatencyModel
# ---------------------------------------------------------------------------
def test_batch_time_matches_throughput(paper_model):
    """batch_time_s is the exact reciprocal view of throughput()."""
    res = paper_model.result_for(8)
    for b in (1, 2, 8, 64):
        assert throughput(res, b, sockets=2) == pytest.approx(
            2 * b / batch_time_s(res, b), rel=1e-12)
    # filter load amortizes, marginal + spill accrue per image
    assert batch_time_s(res, 1) == pytest.approx(
        res.filter_s + res.marginal_s, rel=1e-12)
    assert batch_time_s(res, 4) == pytest.approx(
        res.filter_s + 4 * (res.marginal_s + res.spill_s_per_image()),
        rel=1e-12)


def test_latency_model_strictly_monotone(paper_model):
    batches = (1, 2, 3, 4, 8, 16, 64, 256)
    pred = [paper_model.predict_s(b) for b in batches]
    p99 = [paper_model.predict_p99_s(b) for b in batches]
    assert all(b > a for a, b in zip(pred, pred[1:]))
    assert all(b > a for a, b in zip(p99, p99[1:]))
    # the tail prediction is never thinner than the mean prediction
    assert all(t >= m for m, t in zip(pred, p99))


def test_latency_model_calibration():
    specs = inception.inception_v3_specs(inception.reduced_config())
    m = LatencyModel(lambda b: plan_network(specs, GEOM, batch=b))
    assert not m.calibrated
    assert m.scale == 1.0
    base = m.modeled_batch_s(4)
    # uncalibrated: predictions are modeled time (x tail safety for p99)
    assert m.predict_s(4) == pytest.approx(base)
    assert m.predict_p99_s(4) == pytest.approx(base * m.tail_safety)
    # one observation pins the scale to the observed ratio
    r = m.observe(4, 10.0 * base)
    assert r == pytest.approx(10.0)
    assert m.calibrated and m.scale == pytest.approx(10.0)
    assert m.predict_s(2) == pytest.approx(10.0 * m.modeled_batch_s(2))
    # EWMA folds later evidence; the worst ratio drives the tail
    m.observe(2, 30.0 * m.modeled_batch_s(2))
    assert m.scale == pytest.approx(20.0)  # 0.5 * 30 + 0.5 * 10
    assert m.worst == pytest.approx(30.0)
    assert m.predict_p99_s(1) == pytest.approx(30.0 * m.modeled_batch_s(1))
    # predictions stay monotone through calibration
    vals = [m.predict_p99_s(b) for b in (1, 2, 4, 8)]
    assert all(b > a for a, b in zip(vals, vals[1:]))


def test_latency_model_tail_outlier_ages_out():
    """The p99 multiplier is a WINDOWED max: one cold-compile/CPU-steal
    spike must not cap admitted batch sizes for the engine's lifetime."""
    specs = inception.inception_v3_specs(inception.reduced_config())
    m = LatencyModel(lambda b: plan_network(specs, GEOM, batch=b), window=4)
    base = m.modeled_batch_s(1)
    m.observe(1, 100.0 * base)  # outlier (e.g. first-batch compile)
    assert m.worst == pytest.approx(100.0)
    for _ in range(4):  # steady state fills the window
        m.observe(1, 10.0 * base)
    assert m.worst == pytest.approx(10.0)  # the spike aged out
    assert m.predict_p99_s(1) < 100.0 * base


def test_latency_model_shares_plan_cache():
    """The model prices the very schedule objects its planner returns."""
    specs = inception.inception_v3_specs(inception.reduced_config())
    cache = {}

    def schedule_for(b):
        if b not in cache:
            cache[b] = plan_network(specs, GEOM, batch=b)
        return cache[b]

    m = LatencyModel(schedule_for)
    assert m.result_for(3).schedule is cache[3]
    assert m.result_for(3) is m.result_for(3)  # memoized, priced once
    assert m.stream_batch_limit == cache[1].stream_batch_limit


# ---------------------------------------------------------------------------
# AdmissionPolicy (over a deterministic fake model)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class FakeLatency:
    """predict_p99_s(n) = per_batch_s * n; enough surface for the policy."""

    per_batch_s: float = 1.0
    stream_batch_limit: int = 100

    def predict_p99_s(self, n):
        return self.per_batch_s * n


def test_target_batch_is_largest_under_budget():
    pol = AdmissionPolicy(FakeLatency(1.0), slo_s=10.0, max_batch=64)
    assert pol.target_batch(7.5) == 7
    assert pol.target_batch(7.0) == 7  # boundary: p99(7) == budget
    assert pol.target_batch(100.0) == 64  # max_batch caps
    assert pol.target_batch(0.5) == 1  # below even batch 1: floor, not 0
    assert pol.target_batch(-1.0) == 1
    # monotone in budget
    targets = [pol.target_batch(b) for b in (0.5, 2.0, 5.0, 9.0, 50.0)]
    assert targets == sorted(targets)


def test_target_batch_capped_by_stream_limit():
    pol = AdmissionPolicy(FakeLatency(0.001, stream_batch_limit=5),
                          slo_s=10.0, max_batch=64)
    assert pol.batch_cap == 5
    assert pol.target_batch(10.0) == 5  # budget fits 10000, limit wins


def test_admission_full_queue_admits_target():
    pol = AdmissionPolicy(FakeLatency(1.0), slo_s=10.0, max_batch=8)
    d = pol.admit(queued=20, oldest_wait_s=0.0)
    assert d == AdmissionDecision(8, 8, 10.0, "full")
    # queue wait shrinks the budget, and with it the admitted batch
    d = pol.admit(queued=20, oldest_wait_s=7.0)
    assert d.admit == d.target == 3 and d.reason == "full"


def test_admission_ragged_tail_held_then_flushed_early():
    # hold_slack_s=2: hold while the shallow batch retains >2s slack
    pol = AdmissionPolicy(FakeLatency(1.0), slo_s=10.0, max_batch=8,
                          hold_slack_s=2.0)
    # fresh shallow queue: budget 10, p99(2)=2, slack 8 > 2 -> hold
    d = pol.admit(queued=2, oldest_wait_s=0.0)
    assert d.admit == 0 and d.reason == "hold" and d.target == 8
    # waited 6s: budget 4, slack 4 - 2 = 2 <= 2 -> admit the ragged tail
    d = pol.admit(queued=2, oldest_wait_s=6.0)
    assert d.admit == 2 and d.reason == "ragged-early"
    # flush overrides the hold but keeps the SLO sizing
    d = pol.admit(queued=2, oldest_wait_s=0.0, flush=True)
    assert d.admit == 2 and d.reason == "flush"
    # deadline already blown: the floor batch drains the queue anyway
    d = pol.admit(queued=2, oldest_wait_s=11.0)
    assert d.admit == 1 and d.reason == "full" and d.budget_s < 0


def test_default_hold_slack_is_quarter_slo():
    pol = AdmissionPolicy(FakeLatency(1.0), slo_s=8.0, max_batch=4)
    assert pol.hold_slack == pytest.approx(2.0)
    pol2 = AdmissionPolicy(FakeLatency(1.0), slo_s=8.0, max_batch=4,
                           hold_slack_s=0.5)
    assert pol2.hold_slack == 0.5


# ---------------------------------------------------------------------------
# NCServingEngine integration (tiny stem-only config, injectable clock)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    cfg = inception.reduced_config(img=47, width_div=8, classes=8, stages=())
    params = inception.init_params(jax.random.PRNGKey(0), config=cfg)
    return cfg, params


def _engine(cfg, params, clock, **kw):
    from repro.launch.serve import NCServingEngine
    return NCServingEngine(params, cfg, now_fn=lambda: clock["t"], **kw)


def test_engine_slo_hit_and_miss_accounting(tiny):
    from repro.launch.serve import NCRequest
    cfg, params = tiny
    clock = {"t": 0.0}
    # generous SLO (1e4 s): emulation wall time can never miss it
    eng = _engine(cfg, params, clock, max_batch=2, slo_ms=1e7)
    rng = np.random.default_rng(0)
    imgs = rng.random((3, cfg.img, cfg.img, 3)).astype(np.float32)
    eng.submit(NCRequest(rid=0, image=imgs[0]))
    eng.submit(NCRequest(rid=1, image=imgs[1]))
    assert eng.step(flush=True)
    assert eng.slo_hits == 2 and eng.slo_misses == 0
    assert all(r.slo_ok and r.latency_s is not None for r in eng.completed)
    # a request whose queue wait alone blows the deadline is a miss
    eng.submit(NCRequest(rid=2, image=imgs[2]))
    clock["t"] += 2e4  # 2e4 s >> 1e4 s SLO
    assert eng.step(flush=True)
    late = next(r for r in eng.completed if r.rid == 2)
    assert late.slo_ok is False and late.latency_s >= 2e4
    assert eng.slo_misses == 1
    assert eng.slo_hit_rate == pytest.approx(2 / 3)
    s = eng.stats()
    assert s["slo_hits"] == 2 and s["slo_misses"] == 1
    assert s["batch_histogram"] == {1: 1, 2: 1}


def test_engine_holds_shallow_queue_then_admits_on_deadline(tiny):
    from repro.launch.serve import NCRequest
    cfg, params = tiny
    clock = {"t": 0.0}
    eng = _engine(cfg, params, clock, max_batch=4, slo_ms=60_000.0)
    rng = np.random.default_rng(1)
    eng.submit(NCRequest(rid=0, image=rng.random(
        (cfg.img, cfg.img, 3)).astype(np.float32)))
    # uncalibrated model: target is the full batch of 4, queue holds 1 with
    # ~60s of slack -> the policy holds for more arrivals
    assert eng.step() is False
    assert eng.decisions[-1].reason == "hold" and eng.steps == 0
    # the deadline approaches: slack below hold_slack flushes the tail
    clock["t"] = 50.0
    assert eng.step() is True
    assert eng.decisions[-1].reason == "ragged-early"
    assert eng.decisions[-1].admit == 1 and eng.steps == 1
    assert eng.completed[0].slo_ok  # wait 50s + wall < 60s SLO


def test_engine_slo_batches_bit_identical_and_plan_cache_reuse(tiny):
    from repro.launch.serve import NCRequest
    cfg, params = tiny
    clock = {"t": 0.0}
    eng = _engine(cfg, params, clock, max_batch=4, slo_ms=1e7)
    rng = np.random.default_rng(2)
    imgs = rng.random((5, cfg.img, cfg.img, 3)).astype(np.float32)
    for r in range(5):
        eng.submit(NCRequest(rid=r, image=imgs[r]))
    done = eng.run()
    assert len(done) == 5 and all(r.done for r in done)
    cap = min(eng.max_batch, eng.schedule.stream_batch_limit)
    admitted = sorted(eng.batch_histogram)
    assert all(1 <= n <= cap for n in admitted)
    # plan-cache reuse: one schedule per admitted batch size, and the
    # latency model priced those SAME objects (shared _schedule_for cache)
    for n in admitted:
        assert n in eng._schedules
        assert eng.latency_model.result_for(n).schedule is eng._schedules[n]
    # calibration saw every admitted batch
    assert eng.latency_model.samples == eng.steps
    # results are bit-identical to standalone single-image runs whatever
    # batch sizes the policy picked
    for r in done:
        ref, _ = inception.nc_forward(params, imgs[r.rid], config=cfg)
        np.testing.assert_array_equal(r.logits, np.asarray(ref))


def test_engine_without_slo_unchanged(tiny):
    """No slo_ms: greedy FIFO admission, no hit/miss accounting, stats
    still report the batch histogram."""
    from repro.launch.serve import NCRequest
    cfg, params = tiny
    clock = {"t": 0.0}
    eng = _engine(cfg, params, clock, max_batch=2)
    assert eng.policy is None and eng.slo_s is None
    rng = np.random.default_rng(3)
    for r in range(3):
        eng.submit(NCRequest(rid=r, image=rng.random(
            (cfg.img, cfg.img, 3)).astype(np.float32)))
    done = eng.run()
    assert len(done) == 3 and eng.steps == 2  # 2 + ragged 1
    assert all(r.slo_ok is None for r in done)
    assert eng.slo_hit_rate is None
    assert eng.stats()["batch_histogram"] == {1: 1, 2: 1}
    # per-request latency is tracked even without an SLO
    assert all(r.latency_s is not None for r in done)


def test_simulate_network_const_keyword():
    """simulate_network(schedule, const=...) — the LatencyModel call
    pattern — prices with the supplied constants."""
    from repro.core.simulator import SimConstants
    specs = inception.inception_v3_specs(inception.reduced_config())
    sched = plan_network(specs, GEOM, batch=2)
    a = simulate_network(sched)
    b = simulate_network(sched, const=SimConstants(mac8_cycles=300))
    assert b.latency_s > a.latency_s


# ---------------------------------------------------------------------------
# ArrivalRateEstimator + the fill-time-bounded hold (PR 9)
# ---------------------------------------------------------------------------
def test_arrival_estimator_unknown_until_two_arrivals():
    import math
    from repro.core.slo import ArrivalRateEstimator
    est = ArrivalRateEstimator()
    assert est.rate_hz is None and est.expected_fill_time_s(3) is None
    est.observe(10.0)
    # one arrival: still no interval, callers fall back to slack-only hold
    assert est.rate_hz is None and est.expected_fill_time_s(3) is None
    est.observe(12.0)
    assert est.mean_interval_s == pytest.approx(2.0)
    assert est.rate_hz == pytest.approx(0.5)
    assert est.expected_fill_time_s(3) == pytest.approx(6.0)
    assert est.expected_fill_time_s(0) == 0.0
    assert not math.isnan(est.expected_fill_time_s(1))


def test_arrival_estimator_ewma_tracks_rate_changes():
    from repro.core.slo import ArrivalRateEstimator
    est = ArrivalRateEstimator(ewma=0.5)
    est.observe(0.0)
    est.observe(4.0)   # interval 4
    est.observe(6.0)   # interval 2: 0.5*2 + 0.5*4 = 3
    assert est.mean_interval_s == pytest.approx(3.0)
    # simultaneous arrivals drive the estimate toward zero, never negative
    est.observe(6.0)
    assert est.mean_interval_s == pytest.approx(1.5)
    assert est.mean_interval_s >= 0.0


def test_admission_empty_queue_budget_is_nan():
    """An empty queue has no oldest request and therefore NO deadline
    budget: admit() reports NaN, not a number pretending to be one."""
    import math
    pol = AdmissionPolicy(FakeLatency(1.0), slo_s=10.0, max_batch=8)
    d = pol.admit(queued=0, oldest_wait_s=0.0)
    assert d.admit == 0 and d.target == 0 and d.reason == "hold"
    assert math.isnan(d.budget_s)


def test_admission_hold_bounded_by_expected_fill_time():
    """With an arrivals estimator, a shallow queue is held ONLY while the
    target batch is expected to fill inside the remaining slack — sparse
    traffic flushes ragged batches immediately (PR 5's open thread)."""
    from repro.core.slo import ArrivalRateEstimator
    est = ArrivalRateEstimator()
    pol = AdmissionPolicy(FakeLatency(1.0), slo_s=10.0, max_batch=8,
                          hold_slack_s=2.0, arrivals=est)
    # unknown rate (one arrival): the slack-only rule holds as before
    est.observe(0.0)
    d = pol.admit(queued=2, oldest_wait_s=0.0)
    assert d.reason == "hold"
    # dense traffic (interval 0.1 s): filling 6 more takes ~0.6 s, well
    # inside the 6 s slack -> keep holding
    est.observe(0.1)
    d = pol.admit(queued=2, oldest_wait_s=0.0)
    assert d.reason == "hold"
    # sparse traffic (interval ~100 s): the batch will never fill in
    # time -> admit the ragged tail NOW while the deadline survives
    sparse = ArrivalRateEstimator()
    sparse.observe(0.0)
    sparse.observe(100.0)
    pol_sparse = AdmissionPolicy(FakeLatency(1.0), slo_s=10.0, max_batch=8,
                                 hold_slack_s=2.0, arrivals=sparse)
    d = pol_sparse.admit(queued=2, oldest_wait_s=0.0)
    assert d.admit == 2 and d.reason == "ragged-early"


def test_engine_submit_feeds_arrival_estimator(tiny):
    from repro.launch.serve import NCRequest
    cfg, params = tiny
    clock = {"t": 0.0}
    eng = _engine(cfg, params, clock, max_batch=4, slo_ms=1e7)
    rng = np.random.default_rng(4)
    img = rng.random((cfg.img, cfg.img, 3)).astype(np.float32)
    eng.submit(NCRequest(rid=0, image=img))
    clock["t"] = 2.0
    eng.submit(NCRequest(rid=1, image=img))
    assert eng.arrivals.samples == 2
    assert eng.arrivals.mean_interval_s == pytest.approx(2.0)
    assert eng.policy.arrivals is eng.arrivals


def test_fail_requests_message_precedence():
    """An exception with an empty str() falls back to the TYPE name —
    (str(err) or type name), not str(err or type name)."""
    from repro.launch.serve import BatchQueueEngine, NCRequest
    eng = BatchQueueEngine()
    reqs = [NCRequest(rid=0, image=np.zeros((1, 1, 3), np.float32))]
    eng._fail_requests(reqs, ValueError())
    assert eng.errors[-1] == "ValueError"
    eng._fail_requests(reqs, ValueError("boom"))
    assert eng.errors[-1] == "boom"
    eng._fail_requests(reqs, "plain string")
    assert eng.errors[-1] == "plain string"


# ---------------------------------------------------------------------------
# Rung-4 SLO accounting (PR 9 bugfix): a failed batch HAPPENED
# ---------------------------------------------------------------------------
def test_rung4_failed_batch_slo_accounting(tiny):
    """A batch that exhausts the whole recovery ladder still waited and
    still burned wall time: its requests are stamped SLO misses with a
    latency, the batch lands in the histogram, and the wall is routed
    through LatencyModel.exclude.  Identities:
    slo_hits + slo_misses == completed + failed, and the histogram
    admit-sum covers every finished request."""
    import types
    from repro.launch.serve import NCRequest
    cfg, params = tiny
    clock = {"t": 0.0}
    eng = _engine(cfg, params, clock, max_batch=2, slo_ms=50.0)
    rng = np.random.default_rng(5)
    imgs = rng.random((3, cfg.img, cfg.img, 3)).astype(np.float32)
    for r in range(3):
        eng.submit(NCRequest(rid=r, image=imgs[r]))

    def broken(x, schedule):
        raise RuntimeError("emulation down")

    eng._forward = broken
    eng.network = types.SimpleNamespace(
        apply=lambda *a, **kw: (_ for _ in ()).throw(
            RuntimeError("float down")))
    done = eng.run()
    assert done == [] and len(eng.failed) == 3 and not eng.queue
    s = eng.stats()
    # the fixed identity: every finished request is accounted exactly once
    assert s["slo_hits"] + s["slo_misses"] == s["completed"] + s["failed"] == 3
    assert s["slo_hits"] == 0 and s["slo_misses"] == 3
    # failed batches happened: histogram covers them, totals match steps
    assert sum(s["batch_histogram"].values()) == s["steps"] == eng.steps
    assert sum(n * c for n, c in s["batch_histogram"].items()) == 3
    # their walls never calibrate the model -- excluded, not observed
    assert eng.latency_model.samples == 0
    assert s["calibration_excluded"] == len(s["batch_histogram"]) == 2
    for r in eng.failed:
        assert r.slo_ok is False and r.latency_s is not None
