"""Median service queue wait, SweepJob.stats["queued_s"] (s)."""
from bench.harness.readers import queue_wait_p50


def read(run):
    return queue_wait_p50(run)
