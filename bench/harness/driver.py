"""One run of one cell: set up the service, warm it up, offer the cell's
traffic for the window, then check the answers against the reference.

    set-up   process start -> window start: imports, fleet, service,
             compile (or persistent-cache load), one warm-up request of
             exactly one chunk at the cell's (configs, seed_chunk) shape
    window   `seconds` of traffic: a closed loop (each client sends its
             next request when the last one finished) or an open loop
             (requests due on a schedule, whatever the service does);
             traced with the profiler when asked
    close    device memory peak read; a sample of the scenarios that
             landed is recomputed by the reference while the service
             finishes what is in flight (at most a minute past close)
"""
from __future__ import annotations

import dataclasses
import json
import queue
import shutil
import sys
import threading
import time

import numpy as np

from bench.harness import check, program
from bench.harness.compile_log import CompileLog
from bench.harness.data import ROOT, Cell, reader
from bench.harness.stats import Request
from bench.reference.fleet import fleet

#: how long after the window closes the run waits for answers in flight
DRAIN_S = 60.0


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def chips(n: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devs)}")
    return devs


@dataclasses.dataclass
class Run:
    """What a run measured, for the metric readers."""
    cell: Cell
    seed: int
    setup_s: float
    w0: float                     # window start, host clock
    w1: float                     # window close
    requests: list[Request]
    setup_compile: dict
    window_compile: dict
    lateness_s: float             # worst lateness of the open loop
    trace: object = None          # bench.harness.trace.Report

    def chunks_in_window(self) -> list:
        return [ch for r in self.requests for t, _, ch in r.chunks
                if self.w0 <= t <= self.w1]

    @property
    def work(self) -> dict:
        """Sizes that turn device time into time per task-tick."""
        tr = self.cell.traffic
        return {"n_tasks": fleet(self.cell.config).n_tasks,
                "n_ticks": int(round(float(tr["horizon_s"])
                                     / float(self.cell.config["dt"]))),
                "seed_chunk": int(tr["seed_chunk"])}


def _follow(req: Request, n_jobs: int) -> None:
    """Wait on the request's chunks as a subscriber, stamping each."""
    try:
        for ch in req.job.chunks():
            req.chunks.append((time.perf_counter(),
                               n_jobs * len(ch.summaries) * ch.n_seeds, ch))
        req.result = req.job.result()
        req.done = time.perf_counter()
    except Exception as exc:                       # noqa: BLE001
        req.error = repr(exc)
    req.queued_s = req.job.stats.get("queued_s")


def _clients(svc, cell: Cell, seed: int, arena, kw: dict, w0: float,
             w1: float, requests: list) -> list[threading.Thread]:
    """The load: closed-loop clients or the open-loop generator and its
    subscriber, as started threads."""
    tr = cell.traffic
    loop = tr["loop"]
    n_jobs = int(cell.config["n_jobs"])
    n = int(tr["seeds_per_request"])
    submit = lambda i: svc.submit(                           # noqa: E731
        tr["kind"], arena, program.request_seeds(seed, i, n),
        seed_chunk=int(tr["seed_chunk"]), **kw)
    lock = threading.Lock()
    ids = iter(range(1, 1 << 30))

    def closed():
        while time.perf_counter() < w1:
            now = time.perf_counter()
            with lock:
                req = Request(next(ids), due=now)
                requests.append(req)
            req.job, req.submitted = submit(req.index), now
            _follow(req, n_jobs)

    if loop["type"] == "closed":
        threads = [threading.Thread(target=closed, name=f"client-{k}")
                   for k in range(int(loop.get("clients", 1)))]
    elif loop["type"] == "open":
        due = w0 + program.open_schedule(float(loop["rate_per_s"]),
                                         w1 - w0, int(loop["order_seed"]))
        pending: queue.Queue = queue.Queue()

        def generator():
            for k, d in enumerate(due):
                wait = d - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                req = Request(k + 1, due=float(d))
                req.job = submit(req.index)
                req.submitted = time.perf_counter()
                requests.append(req)
                pending.put(req)
            pending.put(None)

        def subscriber():
            while (req := pending.get()) is not None:
                _follow(req, n_jobs)

        threads = [threading.Thread(target=generator, name="generator"),
                   threading.Thread(target=subscriber, name="subscriber")]
    else:
        raise ValueError(f"unknown loop {loop['type']!r}")
    for t in threads:
        t.start()
    return threads


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_process: float, root=ROOT) -> tuple[dict, Run]:
    """Measure one run; returns the result line's fields and the run."""
    devs = chips(cell.chips)
    import jax

    from repro.core.hotupdate import enable_persistent_cache
    from repro.launch.serve import SweepService

    enable_persistent_cache()
    log = CompileLog()
    tr = cell.traffic
    kw = program.request_kwargs(tr)
    arena = program.arena(cell.config)
    requests: list[Request] = []
    svc = SweepService(workers=1)
    try:
        warm = svc.submit(tr["kind"], arena,
                          program.request_seeds(seed, 0,
                                                int(tr["seed_chunk"])),
                          seed_chunk=int(tr["seed_chunk"]), **kw)
        warm.result()
        setup_compile = log.snapshot()
        trace_dir = root / ".bench_trace" / cell.name
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=opts)
        w0 = time.perf_counter()
        setup_s = w0 - t_process
        w1 = w0 + seconds
        threads = _clients(svc, cell, seed, arena, kw, w0, w1, requests)
        time.sleep(max(0.0, w1 - time.perf_counter()))
        w1 = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
        window_compile = {k: v - setup_compile[k]
                          for k, v in log.snapshot().items()}
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs[:cell.chips])
        # the reference recomputes a sample of what landed while the
        # service finishes what is in flight
        ref = check.Reference(cell.config, tr)
        picks = check.sample(requests, w1, int(tr["check"]["scenarios"]),
                             np.random.default_rng(
                                 np.random.SeedSequence((int(seed), 2))))
        want: list = []
        failure: list = []

        def reference():
            try:
                want.extend(ref.values(p) for p in picks)
            except Exception as exc:               # noqa: BLE001
                failure.append(exc)

        refs = threading.Thread(target=reference, name="reference")
        refs.start()
        for t in threads:
            t.join(timeout=max(0.0, w1 + DRAIN_S - time.perf_counter()))
        refs.join()
    finally:
        svc.shutdown()
    if failure:
        raise failure[0]
    for r in requests:
        if r.done is None and r.error is None:
            r.error = f"no result {DRAIN_S:g} s after the window closed"
    lateness = max((r.submitted - r.due for r in requests
                    if r.submitted is not None), default=0.0)
    res = Run(cell, seed, setup_s, w0, w1, requests, setup_compile,
              window_compile, lateness)
    if trace:
        from bench.harness import trace as tracemod
        res.trace = tracemod.read(trace_dir, w0, w1, cell.chips,
                                   devs[0].platform)
    numbers = check.compare([p.program for p in picks], want,
                            ref.horizon)
    numbers["concat_mismatch"] = float(check.concat_mismatch(requests))
    numbers["failed_requests"] = float(sum(r.error is not None
                                           for r in requests
                                           if r.due <= w1))
    numbers["unchecked"] = float(not picks)
    correct, table = check.verdict(numbers, tr["check"]["limits"])
    print(f"window: {len(requests)} requests, "
          f"{len(res.chunks_in_window())} chunks landed, "
          f"{window_compile['compiles']} backend compiles in the window, "
          f"generator lateness {lateness:.6f} s, "
          f"peak_bytes_in_use {peak}, device {devs[0].device_kind} "
          f"x{len(devs)}, {len(picks)} scenarios checked",
          flush=True)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct),
           "attempted": sum(r.due <= w1 for r in requests),
           "failed": int(numbers["failed_requests"]),
           "device": device, "table": table}
    return out, res


def result_line(out: dict, measured: Run, trace: bool) -> str:
    """The result line: the cell's end-to-end metrics (or, traced, its
    per-layer metrics) as their readers give them, the device, the
    trace's breakdown, and the compared numbers last. The compared
    numbers also go to standard error as its last lines."""
    cell = measured.cell
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"])(measured)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics,
            "device": dict(out["device"])}
    if trace:
        line["device"].update(busy_s=measured.trace.busy_s,
                              window_s=measured.trace.window_s)
        line["breakdown"] = measured.trace.breakdown()
    line["check"] = out["table"]
    for k, v in out["table"].items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    return json.dumps(line)
