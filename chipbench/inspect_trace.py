"""Look at a profiler trace by hand, and cut a small extract of it.

    python chipbench/inspect_trace.py <trace.xplane.pb> [--extract out.json --events N]

Prints every plane and line with its event count, and the most frequent
event names of each line with the stats of their first event: what
``trace.py`` matches on (device planes, op and module lines, kernel
names) is read from here.  ``--extract`` writes the first ``N`` device
events of the measured window, with the benchmark's spans over the same
stretch and the window span cut to it, in the form ``trace.load_extract``
reads, small enough to keep as test data.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if pathlib.Path(p or ".").resolve() != HERE]
sys.path.insert(0, str(HERE.parent))

from chipbench import trace as tr  # noqa: E402


def describe(path: str, top: int = 12) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            counts: collections.Counter = collections.Counter()
            first = {}
            for ev in line.events:
                counts[ev.name] += 1
                if ev.name not in first:
                    first[ev.name] = (ev.duration_ns, dict(ev.stats))
            print(f"  LINE {line.name!r}: {sum(counts.values())} events, "
                  f"{len(counts)} names")
            for name, n in counts.most_common(top):
                dur, stats = first[name]
                print(f"    {n:7d} x {name[:90]!r} ({dur:.0f} ns) "
                      f"{str(stats)[:300]}")


def extract(path: str, out: str, n_events: int) -> None:
    events = tr.load_events(path)
    lo, hi = tr.window_of(events)
    dev = sorted((e for e in events if tr.DEVICE_PLANE.match(e.plane)
                  and lo <= e.start_ns < hi), key=lambda e: e.start_ns)
    dev = dev[:n_events]
    end = max(e.end_ns for e in dev)
    keep = list(dev)
    for e in events:
        if e.name.startswith(tr.SPAN_PREFIX) and e.start_ns < end \
                and e.end_ns > lo:
            if e.name == tr.WINDOW_SPAN:
                e = dataclasses.replace(e, dur_ns=end - e.start_ns)
            keep.append(e)
    tr.save_extract(keep, out)
    print(f"wrote {len(keep)} events to {out}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path")
    ap.add_argument("--extract")
    ap.add_argument("--events", type=int, default=300)
    args = ap.parse_args(argv)
    describe(args.path)
    if args.extract:
        extract(args.path, args.extract, args.events)
    return 0


if __name__ == "__main__":
    sys.exit(main())
