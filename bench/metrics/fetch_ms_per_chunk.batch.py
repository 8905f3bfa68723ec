"""Device-to-host copy of a chunk's history, SweepChunk.fetch_s from the
sweep.fetch span (ms)."""
from bench.harness.spans import chunk_mean


def read(run):
    return chunk_mean(run, "fetch_s", 1e3)
