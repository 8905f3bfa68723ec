"""The program's own spans: what the sweep service records per chunk and
per request (`repro.streams.spans`), read from the chunks and jobs that
landed in the window and, traced, from the ``sweep.*`` host events of
the profiler trace.

Every function returns None where the run has nothing to read: a
program that records no spans (chunks without the field, a trace
without ``sweep.*`` events) leaves the metric out of the result line.
"""
from __future__ import annotations

import statistics

from bench.harness import trace
from bench.harness.trace import Event

PREFIX = "sweep."
ROOT = "sweep.request"          # a request's root span; not a leaf


def chunk_mean(run, field: str, scale: float):
    """Mean of a `SweepChunk` field over the chunks that landed in the
    window, times `scale`."""
    values = [getattr(ch, field, None) for ch in run.chunks_in_window()]
    if not values or None in values:
        return None
    return scale * sum(values) / len(values)


def request_mean(run, stat: str, scale: float):
    """Mean of ``job.stats[stat]`` over the requests whose result came
    in the window, times `scale`."""
    values = [r.job.stats.get(stat) for r in run.requests
              if r.done is not None and run.w0 <= r.done <= run.w1]
    if not values or None in values:
        return None
    return scale * sum(values) / len(values)


def _is_leaf(name: str) -> bool:
    return name.startswith(PREFIX) and name != ROOT


def leaf_spans(report, run=None) -> list[Event]:
    """The ``sweep.*`` leaf spans (all but request roots) on the trace's
    clock. The trace keeps only spans that opened and closed while it
    ran; given the `run`, the spans its requests recorded in memory
    (``SweepJob.spans``) are added, moved onto the trace's clock by the
    offset at which the traced ones match them, so a span cut by the
    window's edge still covers its stretch."""
    traced = [h for h in report.host if _is_leaf(h.name)]
    kept = [] if run is None else [
        Event(s.name, s.start, s.end) for r in run.requests
        for s in getattr(r.job, "spans", ()) if _is_leaf(s.name)]
    offset = _offset(traced, kept)
    if offset is None:
        return traced
    return traced + [Event(k.name, k.start * 1e9 + offset,
                           k.end * 1e9 + offset) for k in kept]


def _offset(traced, kept) -> float | None:
    """Trace-clock ns minus host-clock ns, the median over the traced
    spans matched to the kept ones: per name, both in start order, at the
    shift where their lengths agree best."""
    gaps = []
    for name in {t.name for t in traced}:
        tr = sorted((t for t in traced if t.name == name),
                    key=lambda e: e.start)
        kp = sorted((k for k in kept if k.name == name),
                    key=lambda e: e.start)
        shifts = range(len(kp) - len(tr) + 1)
        if not shifts:
            continue
        best = min(shifts, key=lambda j: sum(
            abs((t.end - t.start) * 1e-9 - (k.end - k.start))
            for t, k in zip(tr, kp[j:])))
        gaps += [t.start - k.start * 1e9 for t, k in zip(tr, kp[best:])]
    return statistics.median(gaps) if gaps else None


def label(report, leaves, s: float, e: float) -> str:
    """The leaf span overlapping (s, e) most; where none does, the host
    event the trace reduction would name."""
    best, name = 0.0, None
    for h in leaves:
        ov = min(e, h.end) - max(s, h.start)
        if ov > best:
            best, name = ov, h.name
    return name if name is not None else report._label(s, e)


def named_gaps(report, leaves, top: int = 10) -> list:
    """The longest idle gaps of the first chip, each named by `label`,
    as ``[name, seconds]``."""
    if not report.ops:
        return []
    first = min(report.ops)
    idle = sorted(trace.gaps([(o.start, o.end) for o in report.ops[first]],
                             report.lo, report.hi),
                  key=lambda g: g[0] - g[1])[:top]
    return [[label(report, leaves, s, e), (e - s) * 1e-9] for s, e in idle]


def unspanned_idle_frac(report, leaves):
    """Time in the traced window when a chip is idle and no leaf span is
    open on any thread, over the window, mean over chips."""
    if not report.ops or not leaves or report.window_s <= 0:
        return None
    spanned = trace.union((h.start, h.end) for h in leaves)
    total = 0.0
    for evs in report.ops.values():
        idle = trace.gaps([(o.start, o.end) for o in evs],
                          report.lo, report.hi)
        total += sum(e - s for s, e in idle) - _overlap(idle, spanned)
    return total / len(report.ops) * 1e-9 / report.window_s


def _overlap(a, b) -> float:
    """Length shared by two sorted, disjoint interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        out += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out
