"""A chunk's summaries and surfaces, SweepChunk.summarize_s from the
sweep.summarize span (ms)."""
from bench.harness.spans import chunk_mean


def read(run):
    return chunk_mean(run, "summarize_s", 1e3)
