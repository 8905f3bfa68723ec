"""Sweep-as-a-service: a thread-backed job queue over the chaos-sweep
drivers with incremental per-chunk results and a shared jit cache.

StreamShield's deployment pipeline treats resiliency sweeps as a release
gate — "can this config ship?" — which needs a *service*, not a batch
script: requests arrive concurrently, callers want the first partial
surface now (not the full cube later), and same-shaped requests must
not re-trace. `SweepService` provides exactly that on top of
`streams.chaos_sweep`:

* **Job queue.** `submit(kind, graph, seeds, **kwargs)` enqueues one of
  the five request kinds — ``"sweep"``, ``"sweep_configs"``,
  ``"replication_tradeoff"``, ``"deployment_drill"`` (the flagship
  release-gate cube), ``"traffic_sweep"`` — and returns a `SweepJob`
  immediately; a small worker pool drains the queue.
* **Incremental results.** Each request executes in seed-chunked device
  passes (`seed_chunk=`, driver-side `on_chunk=`): as every ``(C,
  S_chunk)`` chunk lands it is published to the job's replayable chunk
  buffer, so ANY number of subscribers can iterate `SweepJob.chunks()`
  — late subscribers replay the history first (the Ray buffered-
  publisher idiom), early ones block until the next chunk or the final
  result. Time-to-first-result is one chunk's wall time instead of the
  whole cube's; the concatenated final cube is bit-identical to the
  monolithic call (`jax_engine` chunking contract).
* **Shared trace cache.** Compiled traces key on (plan digest / bucket
  signature, grid shape, phase mode) — never on request identity — so
  concurrent requests over same-shaped plans share ONE process-global
  jit cache (`jax_engine._cache_get` under one lock). Per-request
  hit/miss counters land in `SweepJob.stats` via the thread-local
  `scoped_cache_stats`; one-trace-across-requests is pinned by
  tests/test_sweep_service.py.
* **Pipelined prep.** Host-side timeline prep for chunk k+1 overlaps
  device compute for chunk k (`jax_engine.run_chunks`' double-buffered
  lane); the measured split rides each job's ``prep_s`` / ``device_s`` /
  ``fetch_s``.
* **Spans.** Each job records its spans into ``SweepJob.spans`` (a
  `streams.spans.SpanLog` passed to every thread that serves it): the
  root ``sweep.request`` on the worker, then ``sweep.plan``,
  ``sweep.prep``, ``sweep.device``, ``sweep.fetch``,
  ``sweep.summarize`` and ``sweep.assemble``. Each is also a profiler
  trace annotation, so a trace names the host work between device
  passes. Every time in ``stats`` is read from them; ``queued_s``,
  ``ttfr_s`` and ``wall_s`` run from submission.
* **Input check.** A ``phase_mode`` outside `engine.PHASE_MODES`
  raises `ValueError` at `submit`, before anything is queued.
* **Persistent compile cache.** Start-up enables JAX's persistent
  compilation cache (`core.hotupdate.enable_persistent_cache`), so a
  restarted service reads back the traces an earlier process compiled.

Example::

    with SweepService(workers=2) as svc:
        job = svc.submit("deployment_drill", graph, range(64),
                         seed_chunk=8, base_spec=spec, duration_s=120.0,
                         policies=policies, failover=fo)
        for chunk in job.chunks():       # partial (C, S_chunk) surfaces
            gate.update(chunk.recovery_surface)
        cube = job.result()              # == the monolithic cube

CLI smoke (one drill request, incremental chunk lines)::

    PYTHONPATH=src python -m repro.launch.serve --seeds 16 --chunk 4

The old model-serving driver that seeded this module lives on as
`repro.launch.model_serve`.
"""
from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
import time

from repro.core.hotupdate import enable_persistent_cache
from repro.streams import chaos_sweep
from repro.streams.engine import PHASE_MODES
from repro.streams.jax_engine import scoped_cache_stats, trace_cache_stats
from repro.streams.spans import SpanLog

#: request kind → driver. Every driver has signature
#: ``fn(graph, seeds, *, ..., seed_chunk=None, on_chunk=None,
#: spans=None)`` (the cube wrappers forward them through ``**sweep_kw``).
KINDS = {
    "sweep": chaos_sweep.sweep,
    "sweep_configs": chaos_sweep.sweep_configs,
    "replication_tradeoff": chaos_sweep.replication_tradeoff,
    "deployment_drill": chaos_sweep.deployment_drill,
    "traffic_sweep": chaos_sweep.traffic_sweep,
}


@dataclasses.dataclass
class SweepRequest:
    """One queued sweep request: a driver kind, its (graph, seeds)
    positional payload and the driver kwargs. ``seed_chunk`` selects the
    chunked pipeline (None = monolithic single pass — still one
    published "chunk"); ``label`` names the job in stats."""
    kind: str
    graph: object
    seeds: object
    kwargs: dict = dataclasses.field(default_factory=dict)
    seed_chunk: int | None = None
    label: str | None = None

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown request kind {self.kind!r} "
                             f"(one of {sorted(KINDS)})")


class SweepJob:
    """Handle for a submitted request: a replayable chunk buffer plus
    the final result.

    `chunks()` yields `chaos_sweep.SweepChunk`s in landing order and is
    safe for ANY number of concurrent consumers — each iterator keeps
    its own cursor over the buffered history (late subscribers replay
    from chunk 0) and blocks on the job's condition for chunks that
    have not landed yet. `result()` blocks until the driver returns and
    re-raises the driver's exception on failure. `stats` carries the
    service-side telemetry, every time read from the request's `spans`:
    state, queue wait (``queued_s``), time to the first result
    (``ttfr_s``) and to the whole result (``wall_s``), both from
    submission, the summed prep / device-wait / history-copy split
    (``prep_s`` / ``device_s`` / ``fetch_s``), the final assembly
    (``assemble_s``), the resolved tick lowering (``phase_mode``) and
    per-request trace-cache hits/misses."""

    def __init__(self, job_id: int, request: SweepRequest):
        self.id = job_id
        self.request = request
        self._cond = threading.Condition()
        self._chunks: list = []
        self._done = False
        self._error: BaseException | None = None
        self._result = None
        self.spans = SpanLog(request=job_id)
        self.stats: dict = {"state": "queued", "chunks": 0,
                            "ttfr_s": None, "wall_s": None}

    # -- producer side (service worker) --------------------------------
    def _publish(self, chunk) -> None:
        with self._cond:
            self._chunks.append(chunk)
            self.stats["chunks"] = len(self._chunks)
            self._cond.notify_all()

    def _finish(self, result=None, error: BaseException | None = None
                ) -> None:
        with self._cond:
            self._result = result
            self._error = error
            self._done = True
            self.stats["state"] = "failed" if error else "done"
            self._cond.notify_all()

    # -- consumer side --------------------------------------------------
    def chunks(self, timeout: float | None = None):
        """Yield every `SweepChunk` in landing order; returns when the
        job finishes (raises its error if it failed). `timeout` bounds
        each wait, raising TimeoutError on expiry."""
        i = 0
        while True:
            with self._cond:
                while i >= len(self._chunks) and not self._done:
                    if not self._cond.wait(timeout):
                        raise TimeoutError(
                            f"job {self.id}: no chunk within {timeout}s")
                if i < len(self._chunks):
                    chunk = self._chunks[i]
                    i += 1
                else:
                    if self._error is not None:
                        raise self._error
                    return
            yield chunk

    def first_chunk(self, timeout: float | None = None):
        """Block until the first chunk lands and return it."""
        return next(iter(self.chunks(timeout)))

    def result(self, timeout: float | None = None):
        """Block until the driver returns; the full sweep/cube result
        (bit-identical to the monolithic call)."""
        with self._cond:
            if not self._cond.wait_for(lambda: self._done, timeout):
                raise TimeoutError(f"job {self.id}: not done "
                                   f"within {timeout}s")
            if self._error is not None:
                raise self._error
            return self._result

    def done(self) -> bool:
        with self._cond:
            return self._done


def _grid_of(result):
    """The underlying `SweepResult`/`ConfigSweepResult` of any driver's
    return (cube wrappers carry it as ``.grid``)."""
    return getattr(result, "grid", result)


class SweepService:
    """Thread-backed sweep service: a FIFO request queue drained by
    `workers` daemon threads, every job chunk-published as it executes.

    All workers share the process-global jit caches, so concurrent
    same-shaped requests compile once and hit thereafter; per-request
    attribution comes from `scoped_cache_stats` (thread-local counters
    around each driver call). Use as a context manager or call
    `shutdown()`; `stats()` aggregates job telemetry plus the
    process-wide `trace_cache_stats()`."""

    def __init__(self, workers: int = 2,
                 default_seed_chunk: int | None = None):
        self.cache_dir = enable_persistent_cache()
        self.default_seed_chunk = default_seed_chunk
        self._queue: queue.Queue = queue.Queue()
        self._jobs: dict[int, SweepJob] = {}
        self._ids = itertools.count()
        self._lock = threading.Lock()
        self._workers = [threading.Thread(target=self._worker,
                                          name=f"sweep-worker-{i}",
                                          daemon=True)
                         for i in range(max(1, int(workers)))]
        for t in self._workers:
            t.start()

    # -- submission ------------------------------------------------------
    def submit(self, kind: str, graph, seeds, *,
               seed_chunk: int | None = None, label: str | None = None,
               **kwargs) -> SweepJob:
        """Enqueue a sweep request and return its `SweepJob` handle
        immediately. `kind` is one of `KINDS`; `kwargs` go to the
        driver verbatim (``base_spec``, ``duration_s``, ``policies``,
        ...). ``seed_chunk`` falls back to the service default."""
        return self.submit_request(SweepRequest(
            kind, graph, seeds, kwargs=kwargs,
            seed_chunk=(seed_chunk if seed_chunk is not None
                        else self.default_seed_chunk),
            label=label))

    def submit_request(self, request: SweepRequest) -> SweepJob:
        """Enqueue `request`. A phase mode outside `PHASE_MODES` raises
        `ValueError` here, before anything is queued."""
        mode = request.kwargs.get("phase_mode", "auto")
        if mode not in PHASE_MODES:
            raise ValueError(
                f"phase mode must be {'|'.join(PHASE_MODES)}: {mode!r}")
        with self._lock:
            job = SweepJob(next(self._ids), request)
            self._jobs[job.id] = job
        job.stats["submitted_s"] = time.perf_counter()
        self._queue.put(job)
        return job

    def job(self, job_id: int) -> SweepJob:
        with self._lock:
            return self._jobs[job_id]

    def jobs(self) -> list[SweepJob]:
        with self._lock:
            return list(self._jobs.values())

    # -- execution -------------------------------------------------------
    def _worker(self) -> None:
        while True:
            job = self._queue.get()
            if job is None:
                return
            try:
                self._run(job)
            finally:
                self._queue.task_done()

    def _run(self, job: SweepJob) -> None:
        req = job.request
        kwargs = dict(req.kwargs)
        seeds = list(req.seeds)

        job.stats["state"] = "running"
        submitted = job.stats.pop("submitted_s")
        log = job.spans

        def publish(chunk):
            if job.stats["ttfr_s"] is None:
                # the chunk's summary span has just closed
                job.stats["ttfr_s"] = (log.of("sweep.summarize")[0].end
                                       - submitted)
            job._publish(chunk)

        result = error = None
        with log.span("sweep.request", seeds=len(seeds)) as root:
            job.stats["queued_s"] = root.start - submitted
            try:
                # sweep_configs is the one driver with a second
                # positional (the config grid) — accept it as the
                # `configs` kwarg
                args = (req.graph, seeds)
                if req.kind == "sweep_configs":
                    args = (req.graph, kwargs.pop("configs"), seeds)
                with scoped_cache_stats() as counts:
                    result = KINDS[req.kind](*args,
                                             seed_chunk=req.seed_chunk,
                                             on_chunk=publish, spans=log,
                                             **kwargs)
            except BaseException as exc:          # noqa: BLE001
                error = exc
            root.count(chunks=job.stats["chunks"])
            if error is None:
                root.count(configs=len(getattr(_grid_of(result),
                                               "configs", (None,))))
        job.stats["wall_s"] = wall = root.end - submitted
        if error is not None:
            job._finish(error=error)
            return
        grid = _grid_of(result)
        job.stats.update(
            ttfr_s=(job.stats["ttfr_s"] if job.stats["ttfr_s"]
                    is not None else wall),
            prep_s=log.total("sweep.prep"),
            device_s=log.total("sweep.device"),
            fetch_s=log.total("sweep.fetch"),
            assemble_s=log.total("sweep.assemble"),
            phase_mode=getattr(grid, "phase_mode", ""),
            cache_hits=counts["hits"], cache_misses=counts["misses"])
        job._finish(result=result)

    # -- lifecycle / telemetry ------------------------------------------
    def shutdown(self, wait: bool = True) -> None:
        for _ in self._workers:
            self._queue.put(None)
        if wait:
            for t in self._workers:
                t.join()

    def stats(self) -> dict:
        """Service-level telemetry: per-job stats plus the process-wide
        trace-cache counters every request shares."""
        jobs = self.jobs()
        done = [j for j in jobs if j.stats["state"] == "done"]
        return {
            "jobs": {j.id: dict(j.stats, kind=j.request.kind,
                                label=j.request.label) for j in jobs},
            "completed": len(done),
            "trace_cache": trace_cache_stats(),
            "cache_hits": sum(j.stats.get("cache_hits", 0)
                              for j in done),
            "cache_misses": sum(j.stats.get("cache_misses", 0)
                                for j in done),
        }

    def __enter__(self) -> "SweepService":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()


def main() -> None:
    """CLI smoke: one deployment-drill request through the service,
    chunk lines printed as they land."""
    import argparse
    import json
    import math

    from repro.core.chaos import ChaosSpec
    from repro.streams import nexmark
    from repro.streams.engine import FailoverConfig, UpgradeConfig

    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--duration", type=float, default=60.0)
    ap.add_argument("--phase-mode", default="auto")
    args = ap.parse_args()

    g = nexmark.q2(parallelism=4)
    spec = ChaosSpec(host_kill_prob_per_s=0.002,
                     zk_down=((20.0, 24.0),))
    fo = FailoverConfig(mode="single_task", detect_s=1.0,
                        single_restart_s=2.0)
    policies = {"hot": UpgradeConfig(t_upgrade_s=10.0,
                                     wave_stagger_s=1.0)}
    with SweepService(workers=2) as svc:
        job = svc.submit("deployment_drill", g, range(args.seeds),
                         seed_chunk=args.chunk, base_spec=spec,
                         duration_s=args.duration, policies=policies,
                         canary_fracs=(0.25, 0.5),
                         rollback_thresholds=(math.inf, 200.0),
                         failover=fo, n_hosts=8,
                         phase_mode=args.phase_mode,
                         label="cli-drill")
        for chunk in job.chunks():
            print(f"chunk {chunk.index}: seeds "
                  f"[{chunk.seed_lo},{chunk.seed_hi}) "
                  f"prep={chunk.prep_s:.3f}s "
                  f"device={chunk.device_s:.3f}s "
                  f"fetch={chunk.fetch_s:.3f}s", flush=True)
        cube = job.result()
        print(json.dumps({"rollback_frac":
                          cube.rollback_frac.mean(axis=-1).tolist(),
                          **{k: v for k, v in job.stats.items()
                             if isinstance(v, (int, float, str))
                             or v is None}},
                         indent=1, default=str))


if __name__ == "__main__":
    main()
