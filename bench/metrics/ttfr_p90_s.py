"""90th percentile of due time to first chunk, host clock (s)."""
from bench.harness.readers import latency_p90


def read(run):
    return latency_p90(run, 0)
