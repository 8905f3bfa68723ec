"""Share of the traced window in which the chip is idle and no sweep.*
leaf span is open, mean over chips (frac)."""
from bench.harness.spans import leaf_spans, unspanned_idle_frac


def read(run):
    if run.trace is None:
        return None
    return unspanned_idle_frac(run.trace, leaf_spans(run.trace, run))
