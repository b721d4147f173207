"""The load generator: drives a serving engine as a traffic file says.

A traffic file (``traffic/<mix>.json``) holds only parameters; this one
generator reads every mix, so a new mix is a new data file:

* ``max_batch``: the engine's batch cap;
* ``engine``: further engine options, passed as they stand (for example
  ``{"slo_ms": 400}``); optional;
* ``arrivals``: ``"closed"`` or ``"open"``.

  - ``closed``: each of ``clients`` clients sends its next request as
    soon as its previous one is answered.
  - ``open``: requests arrive at ``rate_per_s`` on average, whatever the
    engine does, with exponential gaps; with ``bursts``
    (``{"period_s": 2.0, "high": 2.5, "low": 0.3}``) the rate is
    ``high`` times the mean for one period and ``low`` times it for the
    next, in turn.  Every seed draws the same set of gaps
    (``GAPS`` quantiles of the unit exponential) in an order of its own,
    so the seed changes the order of the work and not its amount.

The window opens when the first request is due and issues no request
after ``seconds``; the requests in flight then finish and count, and the
window closes at the last completion.  Latency is timed per request on
the host clock, from when the request was due (sent, for a closed
client) to its answer on the host: an open arrival that falls while the
host is busy serving still counts its wait.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Callable, Iterator

import numpy as np

ARRIVALS = ("closed", "open")
GAPS = 4096


@dataclasses.dataclass
class Served:
    req: object
    image: object
    latency_s: float | None = None
    forward_s: float = 0.0  # forward wall of the step that served it


@dataclasses.dataclass
class Window:
    served: list  # [Served], in completion order (failed ones too)
    window_s: float

    @property
    def ok(self) -> list:
        """Requests answered on the primary path: not failed, and not
        served by a degraded rung of the engine's recovery ladder."""
        return [s for s in self.served
                if s.req.done and not s.req.failed and s.latency_s is not None
                and getattr(s.req, "degraded", None) is None]


def check(traffic: dict) -> None:
    kind = traffic.get("arrivals")
    if kind not in ARRIVALS:
        raise ValueError(f"unknown arrivals {kind!r}; known: {ARRIVALS}")
    if int(traffic["max_batch"]) < 1:
        raise ValueError("traffic max_batch must be at least 1")
    if kind == "closed" and int(traffic["clients"]) < 1:
        raise ValueError("traffic clients must be at least 1")
    if kind == "open" and not float(traffic["rate_per_s"]) > 0:
        raise ValueError("traffic rate_per_s must be above 0")


def warm_batches(traffic: dict) -> list[int]:
    """The batch sizes a window of this mix can run: a closed loop runs
    ``min(clients, max_batch)`` and, when they do not divide, the rest;
    an open one any size up to ``max_batch``."""
    b = int(traffic["max_batch"])
    if traffic["arrivals"] == "open":
        return list(range(1, b + 1))
    c = int(traffic["clients"])
    return sorted({min(c, b)} | ({c % b} if c > b and c % b else set()))


def arrival_gaps(seed: int) -> Iterator[float]:
    """Endless unit-free gaps of an open mix: the ``GAPS`` midpoint
    quantiles of the unit exponential, in an order drawn from ``seed``,
    taken again in a new order once spent."""
    q = -np.log1p(-(np.arange(GAPS) + 0.5) / GAPS)
    rng = np.random.default_rng([seed, 2])
    while True:
        yield from rng.permutation(q).tolist()


def arrival_times(traffic: dict, seed: int) -> Iterator[float]:
    """Offsets in seconds from the window's start of an open mix's
    arrivals, the first at 0."""
    rate = float(traffic["rate_per_s"])
    bursts = traffic.get("bursts")
    t = 0.0
    for g in arrival_gaps(seed):
        yield t
        if bursts:
            phase = (bursts["high"] if math.floor(t / bursts["period_s"]) % 2
                     == 0 else bursts["low"])
            t += g / (rate * phase)
        else:
            t += g / rate


def time_forward(engine) -> list:
    """Wrap the engine's forward (the seam every batch runs through) so
    each call's wall time is appended to the returned list."""
    walls: list = []
    forward = engine._forward

    def timed(x, schedule):
        t0 = time.perf_counter()
        try:
            return forward(x, schedule)
        finally:
            walls.append(time.perf_counter() - t0)

    engine._forward = timed
    return walls


def _nospan(name):
    return contextlib.nullcontext()


def serve_batch(engine, make_request: Callable, images: Iterator, n: int,
                walls: list) -> Window:
    """Submit ``n`` requests at once and serve them: the warm-up of one
    batch size."""
    return run(engine, {"arrivals": "closed", "clients": n}, make_request,
               images, 0.0, walls, seed=0)


def run(engine, traffic: dict, make_request: Callable, images: Iterator,
        seconds: float, walls: list, seed: int,
        span: Callable = _nospan) -> Window:
    """One window of ``traffic``; see the module docstring."""
    clock = time.perf_counter
    served: list[Served] = []
    inflight: list[tuple[Served, float]] = []
    rid = 0
    now_fn = getattr(engine, "now_fn", None)

    def send(due: float) -> None:
        nonlocal rid
        with span("chipbench.submit"):
            image = next(images)
            s = Served(make_request(rid, image), image)
            rid += 1
            inflight.append((s, due))
            if now_fn is None:
                engine.submit(s.req)
            else:  # the engine's clock at the moment the request was due
                engine.submit(s.req, now=now_fn() - (clock() - due))

    closed = traffic["arrivals"] == "closed"
    t_start = clock()
    deadline = t_start + seconds
    if closed:
        for _ in range(int(traffic["clients"])):
            send(t_start)
        due = None
    else:
        times = arrival_times(traffic, seed)
        due = t_start + next(times)
    while True:
        now = clock()
        while due is not None and due <= now:
            send(due)
            due = t_start + next(times)
            if due > deadline:
                due = None
        if not inflight:
            if due is None:
                break
            time.sleep(max(due - clock(), 0.0))
            continue
        n_walls = len(walls)
        with span("chipbench.step"):
            admitted = engine.step(flush=due is None)
        if not admitted:
            if due is None:
                raise RuntimeError("the engine admitted nothing while "
                                   "requests were queued and none are due")
            time.sleep(min(max(due - clock(), 0.0), 1e-3))
            continue
        t = clock()
        fwd = sum(walls[n_walls:])
        still = []
        for s, t_due in inflight:
            if s.req.done:
                if not s.req.failed:
                    s.latency_s = t - t_due
                    s.forward_s = fwd
                served.append(s)
            else:
                still.append((s, t_due))
        finished = len(inflight) - len(still)
        inflight[:] = still
        if closed and t < deadline:
            for _ in range(finished):
                send(t)
    return Window(served, clock() - t_start)
