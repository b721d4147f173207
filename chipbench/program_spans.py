"""The program's own spans in a traced run, reduced for the metrics.

The program marks its host stages with ``jax.profiler.TraceAnnotation``
spans named ``nc.*`` (docs/SERVING.md, "Tracing").  The profiler times
them from its session's start, as it times the device's ops, so they
share the device trace's clock.  Containers: ``nc.serve.step`` (stats
``batch``, ``request_ids``), ``nc.forward``, ``nc.layer`` (stat
``layer``).  Stages, which never nest in each other, are grouped as the
per-layer metrics read them (``STAGES``).

* ``trace_file``: the newest ``*.xplane.pb`` under
  ``<root>/.chipbench/trace/``.
* ``load``: that file's ``chipbench.window`` span and its ``nc.*`` host
  events with their stats.
* ``summarize``: per span name, the host self time (duration less the
  part covered by ``nc.*`` children on the same thread) and the device
  idle charged to it (idle time during which that stage span is open);
  the idle no stage span covers; and per ``nc.layer`` name
  its host time beside the kernel's device time that started inside it.
* ``summary(run)``: the same for the run the metric readers read, or
  ``None`` where the trace holds no program spans (a program without
  them) or is not the run's own (its window span differs from the one in
  ``run.events`` by a nanosecond or more).

As a script it prints the per-stage and the per-layer table of a trace
of the benchmark's window, and cuts a small extract of it for test data::

    python chipbench/program_spans.py <trace.xplane.pb> \
        [--extract out.json --events N]

The tables are per image of the serving steps begun in the window.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import pathlib
import sys
from typing import Sequence

HERE = pathlib.Path(__file__).resolve().parent
if __name__ == "__main__":
    # run as a script, the benchmark's directory must not shadow
    # top-level modules (``trace``): import its files as ``chipbench.*``
    sys.path[:] = [p for p in sys.path
                   if pathlib.Path(p or ".").resolve() != HERE]
    sys.path.insert(0, str(HERE.parent))

from chipbench import trace  # noqa: E402

ROOT = HERE.parent
PREFIX = "nc."
LAYER_SPAN = "nc.layer"
STEP_SPAN = "nc.serve.step"
# metric stem -> the stage spans it reads
STAGES = {
    "im2col": ("nc.conv.im2col",),
    "pack": ("nc.conv.pack",),
    "launch": ("nc.pallas.launch",),
    "wait": ("nc.pallas.wait",),
    "scatter": ("nc.pallas.scatter", "nc.conv.store"),
    "requant": ("nc.conv.epilogue", "nc.concat"),
    "pool": ("nc.pool",),
    "accounting": ("nc.accounting",),
}
STAGE_SPANS = frozenset(n for names in STAGES.values() for n in names)


@dataclasses.dataclass(frozen=True)
class Span:
    thread: str  # "<plane>/<line>" of the host thread that ran it
    name: str
    start_ns: float
    dur_ns: float
    stats: dict = dataclasses.field(default_factory=dict, compare=False)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class SpanSummary:
    self_ns: dict  # span name -> host self time
    idle_ns: dict  # stage span name -> device idle charged to it
    idle_outside_ns: float  # device idle under no stage span
    idle_total_ns: float
    counts: dict  # span name -> spans in the window
    images: int  # summed ``batch`` of the serving steps begun in the window
    layers: dict  # layer -> {"calls", "host_ns", "kernel_ns", "kernels"}

    def stage_ns(self, stage: str) -> float | None:
        names = [n for n in STAGES[stage] if n in self.self_ns]
        return sum(self.self_ns[n] for n in names) if names else None


def trace_file(root: pathlib.Path = ROOT) -> pathlib.Path | None:
    files = list((root / ".chipbench" / "trace").rglob("*.xplane.pb"))
    return max(files, key=lambda p: p.stat().st_mtime) if files else None


def load(path: str) -> tuple[tuple[float, float] | None, list[Span]]:
    """The window span (``None`` where there is not exactly one) and the
    ``nc.*`` host events of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    windows, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            thread = f"{plane.name}/{line.name}"
            for ev in line.events:
                if ev.name == trace.WINDOW_SPAN:
                    windows.append((float(ev.start_ns),
                                    float(ev.start_ns + ev.duration_ns)))
                elif ev.name.startswith(PREFIX):
                    spans.append(Span(thread, ev.name, float(ev.start_ns),
                                      float(ev.duration_ns),
                                      dict(ev.stats)))
    return (windows[0] if len(windows) == 1 else None), spans


def clip(spans: Sequence[Span], lo: float, hi: float) -> list[Span]:
    out = []
    for sp in spans:
        s, e = max(sp.start_ns, lo), min(sp.end_ns, hi)
        if e > s:
            out.append(dataclasses.replace(sp, start_ns=s, dur_ns=e - s))
    return out


def self_times(spans: Sequence[Span]) -> dict[str, float]:
    """Per span name, duration less the part covered by its children on
    the same thread (spans of one thread nest properly)."""
    own = [sp.dur_ns for sp in spans]
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i].thread, spans[i].start_ns,
                                  -spans[i].end_ns))
    stack: list[int] = []
    for i in order:
        sp = spans[i]
        while stack and (spans[stack[-1]].thread != sp.thread
                         or spans[stack[-1]].end_ns <= sp.start_ns):
            stack.pop()
        if stack:
            own[stack[-1]] -= sp.dur_ns
        stack.append(i)
    out: dict[str, float] = {}
    for sp, ns in zip(spans, own):
        out[sp.name] = out.get(sp.name, 0.0) + ns
    return out


def _overlaps(a, b) -> dict:
    """Summed overlap of two sorted lists of disjoint intervals, per label
    of ``b`` (``(start, end, label)``)."""
    out: dict = {}
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            ov = min(b[k][1], e) - max(b[k][0], s)
            if ov > 0:
                out[b[k][2]] = out.get(b[k][2], 0.0) + ov
            k += 1
    return out


def _gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` that sorted, disjoint ``intervals``
    (``(start, end, ...)``) leave uncovered."""
    edges = [lo] + [x for iv in intervals for x in iv[:2]] + [hi]
    return [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]


def summarize(spans: Sequence[Span], events: Sequence[trace.Event]
              ) -> SpanSummary:
    """Reduce the program's spans against the device ops of ``events``
    over the window span of ``events``."""
    lo, hi = trace.window_of(events)
    images = sum(int(sp.stats.get("batch", 0)) for sp in spans
                 if sp.name == STEP_SPAN and lo <= sp.start_ns < hi)
    spans = clip(spans, lo, hi)
    ops = sorted((e for e in events if trace.DEVICE_PLANE.match(e.plane)
                  and e.line == trace.OPS_LINE and e.end_ns > lo
                  and e.start_ns < hi), key=lambda e: e.start_ns)
    devices = sorted({e.plane for e in events
                      if trace.DEVICE_PLANE.match(e.plane)})

    # device idle: the window less the union of each device's ops
    # stage spans are leaves and never nest: sorted, they are disjoint
    segments = sorted((sp.start_ns, sp.end_ns, sp.name) for sp in spans
                      if sp.name in STAGE_SPANS)
    uncovered = [(s, e, None) for s, e in _gaps(segments, lo, hi)]
    idle_ns: dict[str, float] = {}
    outside = total = 0.0
    for d in devices:
        busy = trace._union(trace._clip(
            [(e.start_ns, e.end_ns) for e in ops if e.plane == d], lo, hi))
        idle = _gaps(busy, lo, hi)
        total += sum(e - s for s, e in idle)
        for name, ns in _overlaps(idle, segments).items():
            idle_ns[name] = idle_ns.get(name, 0.0) + ns
        outside += _overlaps(idle, uncovered).get(None, 0.0)
    n_dev = max(len(devices), 1)

    counts: dict[str, int] = {}
    for sp in spans:
        counts[sp.name] = counts.get(sp.name, 0) + 1

    kernel = [e for e in ops if trace.KERNEL_OP.search(e.name)]
    starts = [e.start_ns for e in kernel]
    layers: dict[str, dict] = {}
    for sp in spans:
        if sp.name != LAYER_SPAN:
            continue
        row = layers.setdefault(str(sp.stats.get("layer")), {
            "calls": 0, "host_ns": 0.0, "kernel_ns": 0.0, "kernels": 0})
        i = bisect.bisect_left(starts, sp.start_ns)
        j = bisect.bisect_left(starts, sp.end_ns)
        row["calls"] += 1
        row["host_ns"] += sp.dur_ns
        row["kernel_ns"] += sum(e.dur_ns for e in kernel[i:j])
        row["kernels"] += j - i
    return SpanSummary(
        self_ns=self_times(spans),
        idle_ns={k: v / n_dev for k, v in idle_ns.items()},
        idle_outside_ns=outside / n_dev, idle_total_ns=total / n_dev,
        counts=counts, images=images, layers=layers)


_CACHE: dict = {}


def summary(run) -> SpanSummary | None:
    """The reduction of the run's own trace, once for all its readers;
    ``None`` where it has no program spans or is not the run's."""
    if not run.events:
        return None
    path = trace_file()
    if path is None:
        return None
    try:
        window = trace.window_of(run.events)
    except ValueError:
        return None
    key = (str(path), window)
    if key not in _CACHE:
        file_window, spans = load(str(path))
        _CACHE.clear()
        _CACHE[key] = (summarize(spans, run.events)
                       if spans and file_window == window else None)
    return _CACHE[key]


def stage_ms_per_image(run, stage: str) -> float | None:
    s = summary(run)
    ns = s.stage_ns(stage) if s is not None else None
    return None if ns is None or not run.images else ns / 1e6 / run.images


# ---------------------------------------------------------------------------
# Extracts: a few hundred events of a trace, kept as test data
# ---------------------------------------------------------------------------
def save_extract(events: Sequence[trace.Event], spans: Sequence[Span],
                 path: str) -> None:
    with open(path, "w") as f:
        json.dump({"events": [dataclasses.astuple(e) for e in events],
                   "spans": [[sp.thread, sp.name, sp.start_ns, sp.dur_ns,
                              sp.stats] for sp in spans]}, f)


def load_extract(path: str) -> tuple[list[trace.Event], list[Span]]:
    with open(path) as f:
        data = json.load(f)
    return ([trace.Event(*row) for row in data["events"]],
            [Span(*row) for row in data["spans"]])


def extract(path: str, n_events: int
            ) -> tuple[list[trace.Event], list[Span]]:
    """The first ``n_events`` device events of the window, the window span
    cut to end with them, and the program spans over the same stretch."""
    events = trace.load_events(path)
    lo, _ = trace.window_of(events)
    dev = sorted((e for e in events if trace.DEVICE_PLANE.match(e.plane)
                  and e.start_ns >= lo), key=lambda e: e.start_ns)
    dev = dev[:n_events]
    end = max(e.end_ns for e in dev)
    [win] = [e for e in events if e.name == trace.WINDOW_SPAN]
    _, spans = load(path)
    return ([dataclasses.replace(win, dur_ns=end - win.start_ns)] + dev,
            [sp for sp in spans if sp.start_ns < end and sp.end_ns > lo])


# ---------------------------------------------------------------------------
# The tables
# ---------------------------------------------------------------------------
def report(s: SpanSummary, top: int = 10) -> str:
    images = max(s.images, 1)
    per = 1e6 * images  # ns -> ms per image
    lines = [f"images {images}; device idle {s.idle_total_ns / per:.3f} ms "
             f"per image, under no stage span "
             f"{s.idle_outside_ns / per:.3f} ms "
             f"({100 * s.idle_outside_ns / max(s.idle_total_ns, 1):.2f}%)",
             f"{'stage':12s} {'span':20s} {'spans/img':>10s} "
             f"{'self ms/img':>12s} {'idle ms/img':>12s}"]
    for stage, names in STAGES.items():
        for n in names:
            lines.append(
                f"{stage:12s} {n:20s} {s.counts.get(n, 0) / images:10.1f} "
                f"{s.self_ns.get(n, 0.0) / per:12.3f} "
                f"{s.idle_ns.get(n, 0.0) / per:12.3f}")
    for n in sorted(set(s.self_ns) - STAGE_SPANS):
        lines.append(f"{'(container)':12s} {n:20s} "
                     f"{s.counts.get(n, 0) / images:10.1f} "
                     f"{s.self_ns[n] / per:12.3f}")
    lines.append(f"{'layer':28s} {'host ms/img':>12s} {'kernel ms/img':>14s} "
                 f"{'kernels/img':>12s}")
    rows = sorted(s.layers.items(), key=lambda kv: -kv[1]["host_ns"])
    for name, row in rows[:top]:
        lines.append(f"{name:28s} {row['host_ns'] / per:12.3f} "
                     f"{row['kernel_ns'] / per:14.3f} "
                     f"{row['kernels'] / images:12.1f}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path")
    ap.add_argument("--extract")
    ap.add_argument("--events", type=int, default=300)
    args = ap.parse_args(argv)
    _, spans = load(args.path)
    s = summarize(spans, trace.load_events(args.path))
    print(report(s))
    if args.extract:
        events, spans = extract(args.path, args.events)
        save_extract(events, spans, args.extract)
        print(f"wrote {len(events)} events and {len(spans)} spans to "
              f"{args.extract}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
