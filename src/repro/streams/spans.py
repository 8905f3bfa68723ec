"""Program spans of one sweep request, on the host clock and on the
profiler's.

A `SpanLog` belongs to one request. ``log.span(name, **counts)`` times
the enclosed block with `time.perf_counter` and appends a `Span` record
to the log when the block ends; it also opens a
`jax.profiler.TraceAnnotation` of the same name, so while a profiler
runs the span lands in the trace's host planes on the device trace's
clock (with ``request`` and the counts as its stats), and costs next to
nothing otherwise.

The log is passed explicitly to every thread that records into it (the
`jax_engine.run_chunks` device lane included): a span's parent is the
innermost span of the same log open on the same thread, else the log's
first span while it is still open (the request's root), else None.

    log = SpanLog(request=7)
    with log.span("sweep.request", seeds=32):
        with log.span("sweep.fetch", chunk=0) as sp:
            host = np.asarray(ys)
            sp.count(bytes=host.nbytes)
    log.total("sweep.fetch")        # seconds
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time

import jax


@dataclasses.dataclass
class Span:
    """One span; `end` is None while its block runs."""
    name: str
    request: int | None
    parent: str | None
    start: float                       # time.perf_counter()
    end: float | None = None
    counts: dict = dataclasses.field(default_factory=dict)
    _annotation: object = dataclasses.field(default=None, repr=False,
                                            compare=False)

    @property
    def seconds(self) -> float:
        """Length of the closed span."""
        return self.end - self.start

    def count(self, **counts) -> None:
        """Add counts known only once the block has run (bytes copied,
        cache hits); they reach the trace event too."""
        self.counts.update(counts)
        if self._annotation is not None:
            self._annotation.set_metadata(**counts)


class SpanLog:
    """The spans of one request, in the order they closed; safe to
    record into from several threads."""

    def __init__(self, request: int | None = None):
        self.request = request
        self._spans: list[Span] = []
        self._root: Span | None = None
        self._lock = threading.Lock()
        self._local = threading.local()

    @contextlib.contextmanager
    def span(self, name: str, **counts):
        """Time the enclosed block as span `name` with `counts`; yields
        its `Span`, which the log keeps once the block ends."""
        stack = self._local.__dict__.setdefault("stack", [])
        with self._lock:
            if stack:
                parent = stack[-1].name
            elif self._root is not None and self._root.end is None:
                parent = self._root.name
            else:
                parent = None
            meta = counts if self.request is None else dict(
                counts, request=self.request)
            rec = Span(name, self.request, parent, time.perf_counter(),
                       counts=dict(counts))
            if self._root is None:
                self._root = rec
        stack.append(rec)
        with jax.profiler.TraceAnnotation(name, **meta) as ann:
            rec._annotation = ann
            try:
                yield rec
            finally:
                rec._annotation = None
                rec.end = time.perf_counter()
                stack.pop()
                with self._lock:
                    self._spans.append(rec)

    def __iter__(self):
        with self._lock:
            return iter(list(self._spans))

    def of(self, name: str) -> list[Span]:
        """The closed spans called `name`, in closing order."""
        return [s for s in self if s.name == name]

    def total(self, name: str) -> float:
        """Seconds spent in the closed spans called `name`."""
        return sum(s.seconds for s in self.of(name))
