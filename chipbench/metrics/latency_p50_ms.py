"""Median request latency, submit to logits on the host, over all
requests of the window."""

from chipbench.stats import percentile


def read(run):
    return 1e3 * percentile(run.latencies_s, 50)
