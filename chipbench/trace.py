"""Reduction of a profiler trace to device metrics.

A trace is read into a flat list of :class:`Event` s (``load_events``);
everything else works on that list, so the reduction can be checked on a
small extract (``inspect_trace.py --extract`` cuts one from a recorded
trace; ``load_extract`` reads it back).

* Device planes are ``/device:TPU:<n>``.  Their ``XLA Ops`` line holds
  one event per operation that ran; the ``XLA Modules`` line one event
  per program run.
* The benchmark's own spans are host events named ``chipbench.<what>``
  (``jax.profiler.TraceAnnotation``); ``chipbench.window`` bounds the
  measured window.
* An op event is named by its HLO instruction, operands and all (as
  ``%bitserial_matmul.1 = s32[512,128]{...} custom-call(...)``).  The
  bit-serial kernel's events are the Pallas custom calls named after the
  kernel's entry points (``bitserial_matmul``, ``bitserial_matmul_a4``),
  matched by ``KERNEL_OP`` on the instruction's own name, so that an op
  that only reads the kernel's result is not counted; the word-grid
  decode is every other op that runs inside a module whose name matches
  ``DECODE_MODULE`` (the ``pallas`` backend's jitted adapter program).
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import re
from typing import Iterable, Sequence

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
KERNEL_OP = re.compile(r"^%?bitserial_matmul\w*(\.\d+)? = .*custom-call\(")
DECODE_MODULE = re.compile(r"_pallas_exact")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_events(path: str) -> list[Event]:
    """Device op/module events and the benchmark's host spans of one
    ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns)))
    return out


def save_extract(events: Sequence[Event], path: str) -> None:
    with open(path, "w") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def load_extract(path: str) -> list[Event]:
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]


def _union(intervals: Iterable[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(ivs, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # union of op intervals, averaged over devices
    kernel_s: float  # summed kernel op durations, all devices
    decode_s: float  # other ops inside decode modules, all devices
    kernel_events: int
    devices: int
    top_ops: list  # [[name, seconds], ...] most device time first
    idle_gaps: list  # [[label, seconds], ...] longest first


def window_of(events: Sequence[Event]) -> tuple[float, float]:
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    return spans[0].start_ns, spans[0].end_ns


def summarize(events: Sequence[Event], top: int = 10) -> TraceSummary:
    lo, hi = window_of(events)
    ops = [e for e in events if DEVICE_PLANE.match(e.plane)
           and e.line == OPS_LINE and e.end_ns > lo and e.start_ns < hi]
    modules = [e for e in events if DEVICE_PLANE.match(e.plane)
               and e.line == MODULES_LINE and DECODE_MODULE.search(e.name)
               and e.end_ns > lo and e.start_ns < hi]
    devices = sorted({e.plane for e in events
                      if DEVICE_PLANE.match(e.plane)})
    busy = {d: _union(_clip([(e.start_ns, e.end_ns) for e in ops
                             if e.plane == d], lo, hi)) for d in devices}
    busy_ns = (sum(e - s for d in devices for s, e in busy[d])
               / max(len(devices), 1))

    kernel = [e for e in ops if KERNEL_OP.search(e.name)]
    by_plane: dict[str, list[tuple[float, float]]] = {}
    for m in sorted(modules, key=lambda m: m.start_ns):
        by_plane.setdefault(m.plane, []).append((m.start_ns, m.end_ns))
    starts = {d: [s for s, _ in ivs] for d, ivs in by_plane.items()}
    decode_ns = 0.0
    for e in ops:
        if KERNEL_OP.search(e.name) or e.plane not in by_plane:
            continue
        i = bisect.bisect_right(starts[e.plane], e.start_ns) - 1
        if i >= 0 and e.end_ns <= by_plane[e.plane][i][1]:
            decode_ns += e.dur_ns
    if decode_ns and not kernel:
        # the adapter ran and no op matched the kernel's name: a renamed
        # kernel would otherwise move its time into the decode's
        raise ValueError(f"ops ran inside {DECODE_MODULE.pattern!r} modules "
                         f"but none matched the kernel {KERNEL_OP.pattern!r}")

    per_op: dict[str, float] = {}
    for e in ops:
        per_op[e.name] = per_op.get(e.name, 0.0) + e.dur_ns
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    # the instruction and its result type, without layouts and operands
    top_ops = [(n.split("{")[0].strip(), ns) for n, ns in top_ops]

    spans = [e for e in events if e.name.startswith(SPAN_PREFIX)
             and e.name != WINDOW_SPAN]
    gaps = []
    for d in devices:
        edges = [lo] + [x for s, e in busy[d] for x in (s, e)] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s))
    gaps.sort(reverse=True)
    idle = []
    for length, start in gaps[:top]:
        mid = start + length / 2
        inner = [sp for sp in spans if sp.start_ns <= mid < sp.end_ns]
        label = (max(inner, key=lambda sp: sp.start_ns).name
                 if inner else WINDOW_SPAN)
        idle.append([f"{label}@{(start - lo) / 1e9:.3f}s", length / 1e9])
    return TraceSummary(
        window_s=(hi - lo) / 1e9, busy_s=busy_ns / 1e9,
        kernel_s=sum(e.dur_ns for e in kernel) / 1e9,
        decode_s=decode_ns / 1e9, kernel_events=len(kernel),
        devices=len(devices),
        top_ops=[[n, ns / 1e9] for n, ns in top_ops], idle_gaps=idle)
