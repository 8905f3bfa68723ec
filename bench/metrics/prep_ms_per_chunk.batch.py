"""Host timeline prep per chunk, SweepChunk.prep_s (ms)."""
from bench.harness.readers import prep_ms_per_chunk


def read(run):
    return prep_ms_per_chunk(run)
