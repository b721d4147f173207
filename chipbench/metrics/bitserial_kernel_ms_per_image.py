"""Summed device time of the bit-serial Pallas kernel's events, per
image."""


def read(run):
    s = run.trace
    return 1e3 * s.kernel_s / run.images if s and s.kernel_s else None
