"""The program's ``nc.residual`` spans in the window (one per residual
join of one batch), per image (``program_spans.py``); nothing where the
program has no such span."""

from chipbench.program_spans import summary


def read(run):
    s = summary(run)
    if s is None or not s.counts.get("nc.residual") or not run.images:
        return None
    return s.counts["nc.residual"] / run.images
