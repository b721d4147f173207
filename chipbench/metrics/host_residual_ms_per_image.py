"""Host self time of the program's ``nc.residual`` spans (each residual
join: both operands to the common scale, the add and ReLU, the min/max
tree and the requantization), per image (``program_spans.py``); nothing
where the program has no such span."""

from chipbench.program_spans import summary


def read(run):
    s = summary(run)
    if s is None or "nc.residual" not in s.self_ns or not run.images:
        return None
    return s.self_ns["nc.residual"] / 1e6 / run.images
