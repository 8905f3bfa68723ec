"""Arithmetic of the end-to-end metrics, from the client's side of the
service: the rate of work landed in the window and the tails of the
requests due in it."""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass
class Request:
    """One request as its client saw it, on the host's clock: when it
    was due, when it was submitted, each chunk as ``(time it reached
    the subscriber, its job-scenarios, the chunk)``, when the result
    came and what it was, and the error if it failed."""
    index: int
    due: float
    submitted: float | None = None
    chunks: list = dataclasses.field(default_factory=list)
    done: float | None = None
    result: object = None
    error: str | None = None
    queued_s: float | None = None

    @property
    def landings(self) -> list[tuple[float, int]]:
        return [(t, n) for t, n, _ in self.chunks]


def nearest_rank(values, q: float) -> float:
    """The ``q``-quantile by nearest rank: the smallest value with at
    least a share ``q`` of the values at or below it (inf allowed)."""
    vals = sorted(values)
    if not vals:
        return math.nan
    k = max(1, math.ceil(q * len(vals)))
    return vals[k - 1]


def rate(requests, w0: float, w1: float) -> tuple[float, int]:
    """Job-scenarios of every chunk that landed in ``[w0, w1]`` over the
    time from ``w0`` to the last of those landings, and the number of
    such chunks."""
    lands = [(t, n) for r in requests for t, n in r.landings
             if w0 <= t <= w1]
    if not lands:
        return 0.0, 0
    last = max(t for t, _ in lands)
    return sum(n for _, n in lands) / (last - w0), len(lands)


def latencies(requests, w1: float) -> tuple[list[float], list[float]]:
    """Time to first chunk and to the whole result of every request due
    by ``w1``, each from its due time. A request still open at ``w1``
    counts at its age then; a failed one counts as infinitely late."""
    ttfr, wall = [], []
    for r in requests:
        if r.due > w1:
            continue
        if r.error is not None:
            ttfr.append(math.inf)
            wall.append(math.inf)
            continue
        first = r.landings[0][0] if r.landings else None
        ttfr.append((first if first is not None and first <= w1 else w1)
                    - r.due)
        wall.append((r.done if r.done is not None and r.done <= w1
                     else w1) - r.due)
    return ttfr, wall
