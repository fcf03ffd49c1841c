"""``latency_p95_ms``: the 95th percentile over every request scheduled in
the window, from its scheduled arrival to the host holding its answer; a
request never answered counts with the whole drain as its latency."""
from bench.stats import latency_stats


def read(run):
    return latency_stats(run.latencies_s)["p95_ms"]
