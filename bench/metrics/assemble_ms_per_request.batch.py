"""A request's final assembly, job.stats["assemble_s"] from the
sweep.assemble span, over requests finished in the window (ms)."""
from bench.harness.spans import request_mean


def read(run):
    return request_mean(run, "assemble_s", 1e3)
