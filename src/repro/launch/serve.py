"""Batched serving drivers: LM continuous-batching decode AND Neural Cache
batched image inference.

The LM serving loop implements the standard production pattern:

  * requests queue up; a scheduler packs up to ``max_batch`` active
    sequences into the fixed decode batch (padding inactive slots),
  * prefill runs per admitted request (chunked flash attention), its KV
    written into the slot's cache region,
  * one fused ``decode_step`` advances EVERY active slot one token per
    iteration (the decode_32k / long_500k dry-run shapes lower exactly this
    step),
  * finished sequences (eos or max_tokens) free their slot for the queue.

The Neural Cache path (:class:`NCServingEngine`) serves the paper's
workload the paper's way (§VI-C): admitted image requests form one batch
that streams through the reserved I/O way while the filters stay resident
— the engine plans a :class:`~repro.core.schedule.NetworkSchedule` once
per batch size and routes every admitted batch through its network's
``nc_forward`` (Inception v3 by default, ResNet-50 v1.5 with
``network=models.resnet``; both run the shared executor of
core/nc_network.py: batch folded into the packed lane axis, in-cache
§IV-D min/max quantization).

With ``--slo-ms`` the engine turns SLO-aware (core/slo.py): a
:class:`~repro.core.slo.LatencyModel` built over the SAME per-batch-size
plan cache predicts ``latency(batch)`` from the simulator's modeled
cycles calibrated against measured batch wall times, and an
:class:`~repro.core.slo.AdmissionPolicy` picks the largest batch whose
predicted p99 fits the oldest queued request's remaining deadline budget
— never past ``NetworkSchedule.stream_batch_limit`` — admitting ragged
tails early when holding would blow the deadline.  Per-request latency,
the admitted-batch histogram and the SLO hit rate are tracked.

Weights can be served quantized (W8A8 via repro.quant) — the paper's
inference pipeline — with ``--quantize``.

Usage:
    python -m repro.launch.serve --arch olmo-1b --reduced --requests 12
    python -m repro.launch.serve --neural-cache --requests 8 --max-batch 4
    python -m repro.launch.serve --neural-cache --requests 8 --slo-ms 50
    python -m repro.launch.serve --neural-cache --requests 8 \
        --fault-profile seed=7,filter=0.05,stuck=3
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs import REGISTRY, get_config, reduced_config
from repro.kernels import ops
from repro.launch.compile_cache import use_compile_cache
from repro.launch.engine_api import Engine as _EngineAPI
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as T


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [T] int32
    max_tokens: int = 16
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    failed: bool = False
    error: str | None = None


@dataclasses.dataclass
class Slot:
    active: bool = False
    req: Request | None = None
    pos: int = 0


class BatchQueueEngine:
    """Shared admission plumbing: a request queue drained by ``step()``.

    Failure contract (PR 7): an exception raised while executing one
    admitted batch fails ONLY that batch — its requests land in
    ``failed`` with the error string recorded, ``errors`` keeps the
    engine-level log, and the engine keeps draining the rest of the
    queue instead of unwinding ``run()``."""

    def __init__(self):
        self.queue = []
        self.completed = []
        self.failed = []
        self.errors: list[str] = []
        self.steps = 0

    def submit(self, req) -> None:
        self.queue.append(req)

    def _fail_requests(self, reqs, err: BaseException | str) -> None:
        """Mark ``reqs`` failed with the error recorded, engine-wide and
        per-request; they are terminal (never re-queued)."""
        msg = ((str(err) or type(err).__name__)
               if isinstance(err, BaseException) else str(err))
        self.errors.append(msg)
        for r in reqs:
            r.done = True
            r.failed = True
            r.error = msg
            self.failed.append(r)


class ServingEngine(BatchQueueEngine):
    """Fixed-batch continuous-batching engine over decode_step."""

    def __init__(self, cfg, params, *, max_batch: int = 4,
                 max_len: int = 512, eos: int = -1):
        super().__init__()
        self.cfg, self.params = cfg, params
        self.max_batch, self.max_len, self.eos = max_batch, max_len, eos
        self.caches = T.init_caches(cfg, max_batch, max_len)
        self.slots = [Slot() for _ in range(max_batch)]
        self.tokens = jnp.zeros((max_batch, 1), jnp.int32)
        self._decode = jax.jit(
            lambda p, t, c, pos: T.decode_step(cfg, p, t, c, pos))

    def _admit(self) -> None:
        for i, slot in enumerate(self.slots):
            if slot.active or not self.queue:
                continue
            req = self.queue.pop(0)
            # prefill this slot: simple per-request prefill into row i.
            # A prefill failure fails only this request — the slot stays
            # free for the next queued one
            toks = jnp.asarray(req.prompt, jnp.int32)[None]
            try:
                logits, caches1 = T.prefill(self.cfg, self.params, toks,
                                            max_len=self.max_len)
            except Exception as e:  # noqa: BLE001 — batch-failure contract
                self._fail_requests([req], e)
                continue
            self.caches = _write_slot(self.caches, caches1, i)
            nxt = int(jnp.argmax(logits[0]))
            req.out.append(nxt)
            self.tokens = self.tokens.at[i, 0].set(nxt)
            slot.active, slot.req, slot.pos = True, req, len(req.prompt)

    # -- one engine tick -----------------------------------------------------
    def step(self) -> bool:
        self._admit()
        if not any(s.active for s in self.slots):
            return False
        # per-slot positions: slots admitted with different prompt lengths
        # decode — and write KV — each at its OWN position (decoding every
        # slot at max(pos) corrupted shorter sequences; PR 9 bugfix).
        # Inactive slots pass 0; their rows are ignored and overwritten by
        # the next admission's prefill
        pos = jnp.asarray([s.pos if s.active else 0 for s in self.slots],
                          jnp.int32)
        try:
            logits, self.caches = self._decode(self.params, self.tokens,
                                               self.caches, pos)
        except Exception as e:  # noqa: BLE001 — batch-failure contract
            # the fused decode advances every active slot at once, so a
            # mid-batch failure fails exactly the admitted batch (the
            # active slots); freed slots keep draining the queue
            active = [s.req for s in self.slots if s.active]
            self._fail_requests(active, e)
            for s in self.slots:
                if s.active:
                    s.active, s.req = False, None
            self.steps += 1
            return True
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        new_tokens = np.asarray(self.tokens).copy()
        for i, slot in enumerate(self.slots):
            if not slot.active:
                continue
            tok = int(nxt[i])
            slot.req.out.append(tok)
            new_tokens[i, 0] = tok
            slot.pos += 1
            if (tok == self.eos or len(slot.req.out) >= slot.req.max_tokens
                    or slot.pos >= self.max_len - 1):
                slot.req.done = True
                self.completed.append(slot.req)
                slot.active, slot.req = False, None
        self.tokens = jnp.asarray(new_tokens)
        self.steps += 1
        return True

    def run(self) -> list[Request]:
        while self.queue or any(s.active for s in self.slots):
            self.step()
        return self.completed


def _write_slot(caches, caches1, i: int):
    """Copy a single-sequence prefill cache into batch row ``i``."""

    def leaf(c, c1):
        return c.at[:, i : i + 1].set(c1.astype(c.dtype))

    return jax.tree.map(leaf, caches, caches1)


# ---------------------------------------------------------------------------
# Neural Cache image serving (§VI-C batched streaming)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class NCRequest:
    rid: int
    image: np.ndarray  # [H, W, 3] float32 in [0, 1]
    logits: np.ndarray | None = None
    done: bool = False
    failed: bool = False  # unrecoverable after the degradation ladder
    error: str | None = None
    degraded: str | None = None  # "fallback-schedule" | "float" when not primary
    # SLO accounting (stamped by the engine)
    arrival_t: float = 0.0  # engine-clock submit time
    latency_s: float | None = None  # queue wait + batch execution wall
    slo_ok: bool | None = None  # None when the engine has no SLO set


class NCServingEngine(BatchQueueEngine, _EngineAPI):
    """Batched Neural Cache inference server.

    Each ``step()`` admits up to ``max_batch`` queued images and executes
    them as ONE batched forward through the bit-serial emulation (the
    network's ``nc_forward``): the batch folds into the packed
    lane axis, filters pack once per layer per batch, and quantization
    ranges come from the in-cache min/max tree — the serving half of the
    paper's 604 inf/s headline (§VI-C).  The per-layer tiling comes from a
    :class:`~repro.core.schedule.NetworkSchedule` planned once per batch
    size (ragged final batches plan-and-cache their own), so the mapper,
    the packed engine and the server all execute the same plan object.

    ``network`` is the model module whose network the engine serves:
    ``models.inception`` (the default) or ``models.resnet``.  Its
    ``specs``, ``prepare_conv_weights``, ``network_occupancy``,
    ``observed_occupancy``, ``nc_forward`` and float ``apply`` (the
    recovery ladder's last rung) are what the engine calls, and its
    ``REDUCED`` is the default ``config``.

    ``sparse=True`` (the default) plans against the deployed weights'
    detected value sparsity (``network.network_occupancy``): serialized
    passes of all-zero (pruned) filters are dropped from every batch's
    schedule, with logits byte-identical to dense execution — a deployment
    serving an EIE-style pruned model gets the cycle and wall-time win for
    free.  Unpruned weights detect zero sparsity and plan exactly dense.

    ``overlap=True`` (the default) plans every batch size double-buffered
    (PR 6 / §IV-E): serialized passes whose next filter columns fit the
    reserved I/O way stream those columns under the previous pass's
    MAC+reduce, so ``simulator.batch_time_s`` — and therefore the
    ``LatencyModel`` below — prices the overlapped pipeline the engine
    actually executes.  ``overlap=False`` restores the PR 3/4 serial
    plans bit-for-bit.

    ``slo_ms`` arms the SLO-aware admission policy (core/slo.py): instead
    of greedy FIFO-up-to-``max_batch``, each ``step()`` asks the policy
    for the largest batch whose predicted p99 latency (from the
    :class:`~repro.core.slo.LatencyModel` sharing this engine's plan
    cache) fits the oldest queued request's remaining deadline budget,
    capped by ``min(max_batch, schedule.stream_batch_limit)``.  Shallow
    queues are *held* for more arrivals while slack remains and flushed
    early (``ragged-early``) when it runs out; ``run()`` drains with
    ``flush=True`` since no more arrivals are coming.  Execution is
    unchanged — admitted batches route through the same planned
    ``nc_forward``, so logits stay bit-identical to standalone runs
    whatever batch sizes the policy picks.

    ``compressed=True`` (PR 8) plans every batch size with CSR
    bit-plane filter residency (``plan_network(..., compressed=True)``):
    resident filters shrink to their live bit planes plus a per-plane
    live-column bitmap, the modeled time earns the exact residency
    credit, and — because a spilling layer's staged outputs stop
    occupying the reserved I/O way — ``schedule.stream_batch_limit``
    (the SLO policy's hard batch cap) can only rise.  Logits stay
    byte-identical to the dense store.

    ``warmup_replan=True`` (PR 8) treats the first successfully served
    batch as a measurement: its report's observed per-layer input
    sparsity and live output bytes replace the advisory ReLU-chain
    estimate (``network.observed_occupancy``), every cached plan is
    rebuilt from the measured occupancy (requant passes shrink to the
    live output set), and the latency model drops its priced results so
    the calibration curve never mixes estimate-planned and
    measurement-planned predictions.  The warmup batch itself is
    excluded from calibration; logits are byte-identical throughout.

    ``integrity=True`` (PR 7) plans every batch size with ABFT checksum
    verification (``plan_network(..., integrity=True)``): corruption
    under an active ``core.faults`` scope is detected and re-executed
    inside the engine's forward, logits stay byte-identical, and the
    latency model prices the checksum passes.  Independent of the flag, a
    batch whose forward RAISES walks the recovery ladder (``_recover``):
    primary-schedule retries within the oldest request's remaining
    deadline budget, then a dense/no-overlap fallback schedule, then (off
    a TPU) the float reference forward, then the batch is marked failed —
    the engine never strands queued requests.  Only primary successes
    (retries included, at their true total wall time) calibrate the
    :class:`~repro.core.slo.LatencyModel`; degraded batches are
    explicitly excluded (``LatencyModel.exclude``).

    The engine clock is injectable (``now_fn``; ``step``/``submit`` also
    take an explicit ``now``) so deadline behavior is testable without
    wall-clock sleeps.  Stats: ``batch_histogram`` (admitted batch size →
    count), ``slo_hits``/``slo_misses``/``slo_hit_rate``, ``decisions``
    (every :class:`~repro.core.slo.AdmissionDecision`), plus the
    fault/recovery ledger (``failed``/``errors``/``retries``/
    ``degraded_batches``/``calibration_excluded``).
    """

    def __init__(self, params, config=None, *, network=None,
                 max_batch: int = 4,
                 geom=None, engine: str | None = None, sparse: bool = True,
                 overlap: bool = True, integrity: bool = False,
                 compressed: bool = False, warmup_replan: bool = False,
                 slo_ms: float | None = None,
                 hold_slack_ms: float | None = None, now_fn=time.monotonic,
                 name: str = "nc-engine"):
        from repro.core import schedule as nc_schedule
        from repro.core import slo as nc_slo
        from repro.core.cache_geometry import XEON_E5_35MB

        if network is None:
            from repro.models import inception as network

        super().__init__()
        self.name = name
        self.network = network
        self._plan_network = nc_schedule.plan_network
        self.config = config or network.REDUCED
        self.params = params
        self.max_batch = max_batch
        self.geom = geom or XEON_E5_35MB
        # validate the backend name up front (core/backends.py registry);
        # None defers to nc_forward's resolution (NC_BACKEND > batch size)
        if engine is not None:
            from repro.core import backends as nc_backends
            engine = nc_backends.get_backend(engine).name
        self.engine = engine
        self.now_fn = now_fn
        self.specs = network.specs(self.config)
        # resident filters quantize ONCE per deployment, not once per batch;
        # the occupancy scan runs on the same resident weights
        self.wpack = network.prepare_conv_weights(params, self.config)
        self.occupancy = (network.network_occupancy(self.wpack, self.config)
                          if sparse else None)
        self.overlap = overlap
        self.integrity = integrity
        self.compressed = compressed
        self.warmup_replan = warmup_replan
        self._warmup_pending = bool(warmup_replan)
        self.warmup_replans = 0
        self.schedule = self._plan_network(self.specs, self.geom,
                                           batch=max_batch,
                                           occupancy=self.occupancy,
                                           overlap=self.overlap,
                                           integrity=self.integrity,
                                           compressed=self.compressed)
        self._schedules = {max_batch: self.schedule}
        self._fallback_schedules: dict = {}
        self.retries = 0  # primary re-attempts that succeeded or ran
        self.degraded_batches = 0  # batches served off the degradation ladder
        self.reports = []
        # SLO control loop: the latency model prices the SAME plan objects
        # this engine executes (shared _schedule_for cache)
        self.latency_model = nc_slo.LatencyModel(self._schedule_for)
        # EWMA inter-arrival estimator (PR 9): bounds the policy's hold —
        # a shallow queue is kept waiting only while the target batch is
        # expected to fill inside the remaining slack
        self.arrivals = nc_slo.ArrivalRateEstimator()
        self.slo_s = slo_ms / 1e3 if slo_ms is not None else None
        self.policy = None
        if self.slo_s is not None:
            self.policy = nc_slo.AdmissionPolicy(
                self.latency_model, self.slo_s, max_batch,
                hold_slack_s=(hold_slack_ms / 1e3
                              if hold_slack_ms is not None else None),
                arrivals=self.arrivals)
        self.decisions = []
        self.batch_histogram: dict[int, int] = {}
        self.slo_hits = 0
        self.slo_misses = 0

    def _schedule_for(self, n: int):
        if n not in self._schedules:
            self._schedules[n] = self._plan_network(self.specs, self.geom,
                                                    batch=n,
                                                    occupancy=self.occupancy,
                                                    overlap=self.overlap,
                                                    integrity=self.integrity,
                                                    compressed=self.compressed)
        return self._schedules[n]

    def _replan_from_report(self, report) -> None:
        """Warmup re-planning (PR 8): replace the advisory ReLU-chain
        occupancy estimate with what the warmup batch MEASURED —
        ``network.observed_occupancy`` re-scans the resident filters and
        takes each conv's input sparsity and live output bytes from the
        report — then drop every cached plan and the latency model's
        priced results so subsequent batches plan, execute and are
        predicted from the measured occupancy.  The dense/serial fallback
        plans never depended on occupancy, so they stay."""
        self.occupancy = self.network.observed_occupancy(
            self.wpack, self.config, report)
        self._schedules.clear()
        self.schedule = self._schedule_for(self.max_batch)
        self.latency_model.invalidate_plans()
        self.warmup_replans += 1

    def set_engine(self, engine: str | None) -> None:
        """Switch the execution backend (PR 10).  Validates the name
        against the registry, then resets the latency model's priced
        plans AND its measured calibration — wall-clock per modeled cycle
        is a property of the execution body, so a host-calibrated scale
        must not price jit or Pallas batches (see docs/SERVING.md)."""
        if engine is not None:
            from repro.core import backends as nc_backends
            engine = nc_backends.get_backend(engine).name
        if engine == self.engine:
            return
        self.engine = engine
        self.latency_model.invalidate_plans()
        self.latency_model.reset_calibration()

    def _fallback_schedule_for(self, n: int):
        """Degradation rung 2's plan: dense (no pruned passes), serial (no
        double buffering), uncompressed — the most conservative schedule
        the engine can execute, keeping any integrity checking the
        deployment asked for."""
        if n not in self._fallback_schedules:
            self._fallback_schedules[n] = self._plan_network(
                self.specs, self.geom, batch=n, occupancy=None,
                overlap=False, integrity=self.integrity)
        return self._fallback_schedules[n]

    def _forward(self, x: np.ndarray, schedule):
        """One batched forward through the planned emulation (the seam the
        recovery ladder — and fault tests — route every attempt through)."""
        return self.network.nc_forward(
            self.params, x, config=self.config, geom=self.geom,
            engine=self.engine, schedule=schedule, wpack=self.wpack)

    def submit(self, req, now: float | None = None) -> None:
        req.arrival_t = self.now_fn() if now is None else now
        self.arrivals.observe(req.arrival_t)
        super().submit(req)

    def step(self, now: float | None = None, *, flush: bool = False) -> bool:
        """One engine tick: admit a batch (policy-sized under an SLO,
        greedy FIFO otherwise) and execute it.  Returns False when
        nothing was admitted — queue empty, or the policy is holding a
        shallow queue for more arrivals (``flush=True`` overrides the
        hold, not the SLO batch cap)."""
        if not self.queue:
            return False
        now = self.now_fn() if now is None else now
        if self.policy is None:
            n = min(self.max_batch, len(self.queue))
        else:
            decision = self.policy.admit(
                len(self.queue), now - self.queue[0].arrival_t, flush=flush)
            self.decisions.append(decision)
            if decision.admit == 0:
                return False
            n = decision.admit
        batch = [self.queue.pop(0) for _ in range(n)]
        # one profiler span per step, shared by every request it serves;
        # the ids are space-separated (the trace's stat encoding splits
        # values at commas)
        with TraceAnnotation("nc.serve.step", batch=n,
                             request_ids=" ".join(str(r.rid) for r in batch)):
            self._serve(batch, now)
        return True

    def _serve(self, batch, now: float) -> None:
        """Execute one admitted batch through the primary forward (the
        recovery ladder on failure) and stamp its requests."""
        n = len(batch)
        x = np.stack([np.asarray(r.image, np.float32) for r in batch])
        t0 = time.perf_counter()
        try:
            logits, report = self._forward(x, self._schedule_for(len(batch)))
            degraded = None
        except Exception as e:  # noqa: BLE001 — recovery ladder below
            logits, report, degraded = self._recover(batch, x, now, e)
            if logits is None:
                # unreclaimable: the whole ladder failed — the batch is
                # marked failed with the error recorded, and the engine
                # keeps draining the rest of the queue.  The batch still
                # HAPPENED: its requests waited and its wall was burned, so
                # it lands in the histogram, its requests are stamped as
                # SLO misses, and the wall is routed through ``exclude``
                # (it executed no single plan the model prices) — without
                # this, slo_hit_rate overstates under faults and
                # calibration_excluded undercounts
                wall = time.perf_counter() - t0
                self.latency_model.exclude(n, wall)
                self.batch_histogram[n] = self.batch_histogram.get(n, 0) + 1
                for r in batch:
                    r.latency_s = (now - r.arrival_t) + wall
                    if self.slo_s is not None:
                        r.slo_ok = False
                        self.slo_misses += 1
                self.steps += 1
                return
        wall = time.perf_counter() - t0
        if degraded is None:
            if self._warmup_pending and report is not None:
                # warmup batch: fold its MEASURED occupancy back into the
                # planner, then EXCLUDE it from calibration — it executed
                # (and was priced by) the retired estimate plan, and
                # observing it against the re-planned predictions would
                # seed the curve with a stale ratio
                self._warmup_pending = False
                self._replan_from_report(report)
                self.latency_model.exclude(len(batch), wall)
            else:
                # calibrate the latency model with the measured batch wall
                # time (retried batches fold their TRUE total wall in — the
                # retries are real latency the next admission must predict
                # around)
                self.latency_model.observe(len(batch), wall)
        else:
            # degraded batches did not execute the plan the model prices;
            # folding their wall time in would poison later predictions
            self.latency_model.exclude(len(batch), wall)
            self.degraded_batches += 1
        self.batch_histogram[n] = self.batch_histogram.get(n, 0) + 1
        for i, r in enumerate(batch):
            r.logits = np.asarray(logits[i])
            r.done = True
            r.degraded = degraded
            r.latency_s = (now - r.arrival_t) + wall
            if self.slo_s is not None:
                r.slo_ok = r.latency_s <= self.slo_s
                if r.slo_ok:
                    self.slo_hits += 1
                else:
                    self.slo_misses += 1
            self.completed.append(r)
        if report is not None:
            self.reports.append(report)
        self.steps += 1

    def _recover(self, batch, x, now: float, err: BaseException):
        """Degradation ladder for a failed batch (PR 7).

        1. Re-attempt the primary schedule while the oldest request's
           remaining deadline budget still covers a predicted execution
           (no SLO: one retry) — transient faults recover here.
        2. Dense/no-overlap fallback schedule — plan-shape trouble
           (quarantine storms, overlap/sparsity interactions) recovers
           here; the batch is excluded from calibration.
        3. Float reference forward (off a TPU only) — the result is no
           longer the emulation's logits, but the request is answered.
        4. Mark the batch failed (``stats()['errors']`` records why) and
           keep draining.

        Returns ``(logits, report, degraded_tag)``; logits None means
        rung 4."""
        n = len(batch)
        last = err
        # rung 1: bounded retries inside the deadline budget
        retries_left = 1
        if self.slo_s is not None:
            budget = self.slo_s - (now - batch[0].arrival_t)
            predicted = max(self.latency_model.predict_s(n), 1e-9)
            retries_left = max(0, int(budget / predicted) - 1)
        while retries_left > 0:
            retries_left -= 1
            self.retries += 1
            try:
                logits, report = self._forward(x, self._schedule_for(n))
                return logits, report, None
            except Exception as e:  # noqa: BLE001
                last = e
        # rung 2: most conservative emulated plan (dense, serial)
        try:
            logits, report = self._forward(x, self._fallback_schedule_for(n))
            return logits, report, "fallback-schedule"
        except Exception as e:  # noqa: BLE001
            last = e
        # rung 3: float reference — answers the request outside the
        # emulation.  Not on a TPU: there a device failure must fail the
        # batch, never come back as a float answer
        if not ops.on_tpu():
            try:
                logits = np.asarray(self.network.apply(
                    self.params, jnp.asarray(x, jnp.float32),
                    config=self.config))
                return logits, None, "float"
            except Exception as e:  # noqa: BLE001
                last = e
        # rung 4: unreclaimable
        self._fail_requests(batch, last)
        return None, None, None

    @property
    def slo_hit_rate(self) -> float | None:
        total = self.slo_hits + self.slo_misses
        return self.slo_hits / total if total else None

    # -- Engine API (PR 9, launch/engine_api.py) -----------------------------
    @property
    def queue_depth(self) -> int:
        """Requests owned by this engine but not yet executed."""
        return len(self.queue)

    @property
    def batch_cap(self) -> int:
        """Hard admission bound: ``max_batch`` and the §VI-C streaming
        limit, whichever bites first (what the orchestrator may dispatch
        at once)."""
        if self.policy is not None:
            return self.policy.batch_cap
        return max(1, min(self.max_batch,
                          self.latency_model.stream_batch_limit))

    def stats(self) -> dict:
        """Serving stats: admitted-batch histogram, SLO accounting, the
        latency model's calibration state, and the fault/recovery ledger
        (failed requests, error log, retries, degraded batches and the
        calibration exclusions that kept the model honest)."""
        return dict(
            steps=self.steps,
            completed=len(self.completed),
            batch_histogram=dict(sorted(self.batch_histogram.items())),
            slo_ms=self.slo_s * 1e3 if self.slo_s is not None else None,
            slo_hits=self.slo_hits,
            slo_misses=self.slo_misses,
            slo_hit_rate=self.slo_hit_rate,
            calibration_scale=self.latency_model.scale,
            calibration_samples=self.latency_model.samples,
            calibration_excluded=self.latency_model.excluded,
            stream_batch_limit=self.schedule.stream_batch_limit,
            integrity=self.integrity,
            compressed=self.compressed,
            residency_credit_bytes=self.schedule.residency_credit_bytes,
            warmup_replans=self.warmup_replans,
            failed=len(self.failed),
            errors=list(self.errors),
            retries=self.retries,
            degraded_batches=self.degraded_batches,
        )

    def run(self) -> list[NCRequest]:
        # draining: no more arrivals are coming, so holding for a fuller
        # batch is pointless — flush, keeping the SLO batch cap
        while self.queue:
            self.step(flush=True)
        return self.completed


def _main_neural_cache(args) -> int:
    import contextlib

    from repro.core import faults
    from repro.core.simulator import simulate_network, throughput
    from repro.models import inception

    profile = (faults.FaultProfile.parse(args.fault_profile)
               if args.fault_profile else None)
    cfg = inception.reduced_config()
    params = inception.init_params(jax.random.key(0), config=cfg)
    engine = NCServingEngine(params, cfg, max_batch=args.max_batch,
                             overlap=not args.no_overlap,
                             integrity=profile is not None,
                             compressed=args.compressed,
                             warmup_replan=args.warmup_replan,
                             slo_ms=args.slo_ms)
    rng = np.random.default_rng(0)
    for r in range(args.requests):
        engine.submit(NCRequest(
            rid=r, image=rng.random((cfg.img, cfg.img, 3),
                                    dtype=np.float32)))
    scope = (faults.inject(profile) if profile is not None
             else contextlib.nullcontext())
    t0 = time.perf_counter()
    with scope as fs:
        done = engine.run()
    dt = time.perf_counter() - t0
    # modeled throughput from the engine's own schedule: filter load once
    # per batch + per-image marginal + spill (simulator.throughput), NOT
    # images / summed per-image latencies (which overstates by ~batch)
    res = simulate_network(engine.schedule)
    tp = throughput(res, args.max_batch, sockets=1)
    print(f"[serve-nc] {len(done)} images in {dt:.2f}s emulated "
          f"({len(done)/dt:.2f} img/s wall, {engine.steps} batches of "
          f"<= {args.max_batch}); modeled: {res.latency_s*1e3:.3f} ms/img "
          f"unbatched, {tp:.0f} inf/s at batch {args.max_batch} "
          f"(single socket)")
    if args.compressed or args.warmup_replan:
        s = engine.stats()
        print(f"[serve-nc] compressed residency: "
              f"{'on' if s['compressed'] else 'off'}, credit "
              f"{s['residency_credit_bytes']} B/batch, stream limit "
              f"{s['stream_batch_limit']}, warmup re-plans "
              f"{s['warmup_replans']}")
    if args.slo_ms is not None:
        s = engine.stats()
        print(f"[serve-nc] SLO {args.slo_ms:.0f} ms: hit rate "
              f"{s['slo_hit_rate']:.0%} ({s['slo_hits']} hit / "
              f"{s['slo_misses']} miss), admitted batches "
              f"{s['batch_histogram']}, stream limit "
              f"{s['stream_batch_limit']}, calibration x"
              f"{s['calibration_scale']:.1f} over "
              f"{s['calibration_samples']} batches")
    if profile is not None:
        s = engine.stats()
        fstats = fs.stats()
        print(f"[serve-nc] faults (seed {fstats['seed']}): "
              f"{fstats['injected']} injected, {fstats['detected']} "
              f"detected / {fstats['corrupt_attempts']} corrupt passes, "
              f"{fstats['reexecuted']} re-executed, quarantined slices "
              f"{list(fstats['quarantined_slices'])}; serving: "
              f"{s['retries']} batch retries, {s['degraded_batches']} "
              f"degraded, {s['failed']} failed")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(REGISTRY))
    ap.add_argument("--neural-cache", action="store_true",
                    help="serve Inception images through the Neural Cache "
                         "emulation instead of an LM")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--no-overlap", action="store_true",
                    help="plan --neural-cache batches serial (no filter "
                         "streaming under MAC+reduce); default plans are "
                         "double-buffered per §IV-E headroom")
    ap.add_argument("--compressed", action="store_true",
                    help="plan --neural-cache batches with CSR bit-plane "
                         "filter residency (PR 8): smaller resident "
                         "footprint, exact modeled residency credit, and "
                         "a raised streaming batch ceiling; logits stay "
                         "byte-identical")
    ap.add_argument("--warmup-replan", action="store_true",
                    help="treat the first served --neural-cache batch as "
                         "a measurement: re-plan all batch sizes from its "
                         "observed per-layer sparsity and live outputs "
                         "instead of the ReLU-chain estimate")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="per-request latency SLO for --neural-cache: "
                         "batches are sized by the predicted p99 from the "
                         "cycle model (core/slo.py) instead of greedy FIFO")
    ap.add_argument("--fault-profile", type=str, default=None,
                    help="seeded fault injection for --neural-cache, e.g. "
                         "'seed=7,filter=0.05,act=0.01,compute=0.01,"
                         "stuck=3,stall=0.1:0.002' (core/faults.py); "
                         "implies integrity checking, prints the "
                         "detection/recovery ledger")
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-tokens", type=int, default=16)
    args = ap.parse_args()
    use_compile_cache()

    if args.neural_cache:
        return _main_neural_cache(args)
    if args.arch is None:
        ap.error("--arch is required unless --neural-cache is given")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    rng = np.random.default_rng(0)
    params = T.init_lm(cfg, jax.random.key(0))
    engine = ServingEngine(cfg, params, max_batch=args.max_batch,
                           max_len=args.max_len)
    t0 = time.perf_counter()
    for r in range(args.requests):
        engine.submit(Request(
            rid=r,
            prompt=rng.integers(2, cfg.vocab_size,
                                size=args.prompt_len).astype(np.int32),
            max_tokens=args.max_tokens))
    done = engine.run()
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.out) for r in done)
    print(f"[serve] {len(done)} requests, {total_tokens} tokens in "
          f"{dt:.2f}s ({total_tokens/dt:.1f} tok/s, {engine.steps} engine "
          f"steps, batch {args.max_batch})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
