"""Median over requests of latency minus the wall time of the forward
that served it (the benchmark's span around ``engine._forward``)."""

from chipbench.stats import percentile


def read(run):
    return 1e3 * percentile(run.overheads_s, 50)
