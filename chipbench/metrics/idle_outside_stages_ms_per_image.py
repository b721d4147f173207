"""Device idle time in the window during which no stage span of the
program is open, per image (``program_spans.py``): the idle that the
stage spans leave unexplained."""

from chipbench.program_spans import summary


def read(run):
    s = summary(run)
    return None if s is None else s.idle_outside_ns / 1e6 / run.images
