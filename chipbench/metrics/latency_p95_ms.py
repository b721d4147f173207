"""95th percentile (nearest rank) of request latency over all requests
of the window.  With a handful of requests it is their maximum."""

from chipbench.stats import percentile


def read(run):
    return 1e3 * percentile(run.latencies_s, 95)
