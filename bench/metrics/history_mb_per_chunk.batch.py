"""Bytes a chunk copies to the host, SweepChunk.history_bytes from the
sweep.fetch span's count (MB, 1e6 B)."""
from bench.harness.spans import chunk_mean


def read(run):
    return chunk_mean(run, "history_bytes", 1e-6)
