"""Arithmetic shared by the metric readers in ``bench/metrics``. Each
returns None where the run has nothing to read, and the harness then
leaves the metric out of the result line."""
from __future__ import annotations

from bench.harness import stats


def job_scenarios_per_s(run):
    value, n_chunks = stats.rate(run.requests, run.w0, run.w1)
    return value if n_chunks else None


def latency_p90(run, which: int):
    values = stats.latencies(run.requests, run.w1)[which]
    return stats.nearest_rank(values, 0.9) if values else None


def queue_wait_p50(run):
    waits = [r.queued_s for r in run.requests
             if r.due <= run.w1 and r.queued_s is not None]
    return stats.nearest_rank(waits, 0.5) if waits else None


def prep_ms_per_chunk(run):
    chunks = run.chunks_in_window()
    if not chunks:
        return None
    return 1e3 * sum(ch.prep_s for ch in chunks) / len(chunks)


def tick_ns_per_task_tick(run):
    """Device time of the grid program's executions in the traced
    window, summed over chips, per task-tick of one scenario."""
    tr = run.trace
    chunks = run.chunks_in_window()
    if tr is None or not tr.grid_runs or not chunks:
        return None
    w = run.work
    scen_ticks = (tr.grid_runs * len(chunks[0].summaries) * w["seed_chunk"]
                  * w["n_tasks"] * w["n_ticks"])
    return 1e9 * tr.grid_busy_s / scen_ticks


def device_idle_frac(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 1.0 - tr.busy_s / tr.window_s
