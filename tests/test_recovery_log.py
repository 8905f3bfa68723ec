"""Recovery events as columns: every function that builds timelines
records a host kill's failover response as rows of a `RecoveryLog`,
which reads as the list of event dicts those functions used to return,
and the summary reads its columns.

* Each timeline function × arena: the log equals the dict path, the
  same function run with the list-of-dicts resolution below (one kill
  at a time, its own per-job grouping), dict for dict and in order.
* `summarize` / `_recovery_time` give bit-identical summaries over a log
  and over the equal dict list.
* The ``sweep.prep`` span counts the rows of its chunk's logs.
"""
import dataclasses

import numpy as np
import pytest

from bench.harness import program
from bench.tests import tiny
from repro.core import chaos
from repro.core.chaos import (ChaosSpec, ChaosTimeline, GridTimelineBuilder,
                              RecoveryLog, build_perjob_chaos_timeline,
                              refit_failover)
from repro.streams import chaos_sweep, nexmark
from repro.streams.chaos_sweep import _recovery_time, summarize
from repro.streams.engine import (CheckpointConfig, FailoverConfig,
                                  pack_arena)
from repro.streams.jax_engine import JaxBatchMetrics, _Lowered
from repro.streams.spans import SpanLog

T, DT = 160, 0.5
SEEDS = (3, 11)
CKPT = CheckpointConfig(interval_s=10.0, upload_s=4.0)


# ----------------------------------------------------------------------
# the dict path
# ----------------------------------------------------------------------
class _DictRows(list):
    """Stands in for `chaos._RecoveryRows`: the dicts themselves."""

    def __init__(self, job_of_task):
        super().__init__()

    def log(self):
        return list(self)


def _dict_resolve(t, host, targets, down_s, down_r, down, rows, *,
                  down_h, extra=None):
    """One host kill resolved into event dicts, as before the log:
    victims and region expansion per kill, then one dict per hit job in
    ascending job order, reporting its first hit task's downtime."""
    jot = targets.job_of_task
    victims = targets.task_host == host
    for code, mode in ((1, "region"), (2, "single_task"),
                       (3, "hot_standby")):
        hit = victims & (targets.mode_codes == code)
        if not hit.any():
            continue
        if code == 1:
            hit = np.isin(targets.task_region, targets.task_region[hit])
        if code == 3:
            d = down_h
        else:
            d = down_r if code == 1 else down_s
            if extra is not None:
                d = d + extra
        down[hit] = t + d[hit]
        if jot is None:
            rows.append({"t": t, "mode": mode, "tasks": int(hit.sum()),
                         "downtime": float(d[0])})
            continue
        per_job: dict[int, list] = {}
        for tid in np.flatnonzero(hit).tolist():
            per_job.setdefault(int(jot[tid]), [0, float(d[tid])])[0] += 1
        rows.extend({"t": t, "mode": mode, "tasks": n, "downtime": dd,
                     "job": j} for j, (n, dd) in sorted(per_job.items()))


# ----------------------------------------------------------------------
# arenas and timeline functions
# ----------------------------------------------------------------------
def _arena(name: str) -> _Lowered:
    # forward-only q2 at parallelism 3 on 4 hosts: three regions, each
    # over two hosts, so a kill expands to a neighbour region, and the
    # lazy-load stagger gives the regions of one job unequal downtimes
    chain = nexmark.q2(parallelism=3, partitioner="forward")
    if name == "single_job":
        graph = chain
        failover = FailoverConfig(mode="region", region_restart_s=20.0,
                                  restore_base_s=1.5, replay_rate=0.25,
                                  lazyload_stagger_s=1.0)
    else:
        graph = pack_arena([chain, nexmark.q12(parallelism=4),
                            nexmark.q2(parallelism=2)], "shared",
                           n_hosts=4)
        failover = (FailoverConfig(mode="region", region_restart_s=20.0)
                    if name == "packed_shared" else
                    [FailoverConfig(mode="region", region_restart_s=12.0,
                                    restore_base_s=2.0, replay_rate=0.5,
                                    lazyload_stagger_s=1.0),
                     FailoverConfig(mode="single_task",
                                    single_restart_s=4.0,
                                    restore_base_s=1.0, replay_rate=0.1),
                     FailoverConfig(mode="hot_standby")])
    return _Lowered(graph, n_hosts=4, dt=DT, queue_cap=256.0,
                    failover=failover, ckpt=None, seed=0)


def _spec(seed: int) -> ChaosSpec:
    return ChaosSpec(seed=seed, host_kill_prob_per_s=0.02,
                     host_kill_at=((20.0, 1),), burst_at=((45.0, 0),),
                     brownout_at=((10.0, 60.0, 3.0),))


def _fo_kw(low: _Lowered) -> dict:
    fx = low.fo_extras
    return dict(failover_mode=low.fo_codes, detect_s=low.fo_detect,
                single_restart_s=low.fo_rs, region_restart_s=low.fo_rr,
                standby_switch_s=fx["switch"],
                standby_staleness_s=fx["stale"],
                restore_base_s=fx["restore_base"],
                replay_rate=fx["replay_rate"], lazy_extra_s=low.fo_lazy)


def _build(low):
    return [low.timeline(_spec(s), T, ckpt=CKPT) for s in SEEDS]


def _refit(low):
    return [refit_failover(low.timeline(_spec(s), T, ckpt=None),
                           task_host=low.task_host,
                           task_region=low.task_region,
                           job_of_task=low.job_of_task, spec=_spec(s),
                           **_fo_kw(low))
            for s in SEEDS]


def _grid(low):
    cfg = dict(_fo_kw(low), ckpt_interval_s=10.0, ckpt_mode="region",
               ckpt_upload_s=4.0, brownout_at=((30.0, 50.0, 2.0),))
    grid = GridTimelineBuilder(
        [_spec(s) for s in SEEDS], [cfg, dict(cfg, ckpt_interval_s=25.0)],
        n_ticks=T, dt=DT, n_hosts=low.n_hosts, task_host=low.task_host,
        task_region=low.task_region, regions=low.phys.regions,
        job_of_task=low.job_of_task)
    return [tl for row in grid.chunk(0, len(SEEDS)) for tl in row]


def _perjob(low):
    jot = (low.job_of_task if low.job_of_task is not None
           else np.zeros(len(low.task_host), int))
    job_hosts = ([j.hosts for j in low.arena.jobs] if low.arena is not None
                 else [np.arange(low.n_hosts)])
    local = (low.task_local_host if low.arena is not None
             else low.task_host)
    return [build_perjob_chaos_timeline(
                [_spec(s + j) for j in range(len(job_hosts))], n_ticks=T,
                dt=DT, n_hosts=low.n_hosts, task_host=low.task_host,
                job_hosts=job_hosts, task_local_host=local,
                job_of_task=jot, task_region=low.task_region,
                regions=low.phys.regions, ckpt_interval_s=10.0,
                **_fo_kw(low))
            for s in SEEDS]


TIMELINES = {"build_chaos_timeline": _build, "refit_failover": _refit,
            "grid_chunk": _grid, "perjob": _perjob}
ARENAS = ("single_job", "packed_shared", "perjob_mixed_restore")


@pytest.mark.parametrize("arena", ARENAS)
@pytest.mark.parametrize("timelines", list(TIMELINES))
def test_log_reads_as_the_dict_path(timelines, arena, monkeypatch):
    low = _arena(arena)
    tls = TIMELINES[timelines](low)
    with monkeypatch.context() as m:
        m.setattr(chaos, "_RecoveryRows", _DictRows)
        m.setattr(chaos, "_resolve_failover_tick", _dict_resolve)
        refs = TIMELINES[timelines](low)
    with_job = low.job_of_task is not None or timelines == "perjob"
    assert sum(len(ref.recoveries) for ref in refs) > 0
    for tl, ref in zip(tls, refs):
        log, dicts = tl.recoveries, ref.recoveries
        assert isinstance(log, RecoveryLog) and type(dicts) is list
        np.testing.assert_array_equal(tl.ckpt_ok, ref.ckpt_ok)
        # the same dicts, keys, values and value types, in order
        assert list(log) == dicts
        assert [[(k, type(v)) for k, v in r.items()] for r in log] == \
               [[(k, type(v)) for k, v in r.items()] for r in dicts]
        assert all(("job" in r) == with_job for r in log)
        # len, indexing and == as the list
        assert len(log) == len(dicts)
        assert log == dicts and dicts == log and not log != dicts
        assert log[0] == dicts[0] and log[-1] == dicts[-1]
        assert log[1:3] == dicts[1:3]
        assert RecoveryLog.of(dicts) == log
        assert dicts[:-1] != log
    if with_job and arena != "single_job":
        # shared-host kills hit several jobs, each with its own row
        assert len({r["job"] for tl in tls for r in tl.recoveries}) > 1


def test_job_view_rows_are_the_job_column():
    low = _arena("perjob_mixed_restore")
    log = _build(low)[0].recoveries
    for j in range(low.n_jobs):
        assert log.for_job(j) == [r for r in log if r["job"] == j]
    single = _build(_arena("single_job"))[0].recoveries
    assert single.for_job(0) == single and single.for_job(1) == []


# ----------------------------------------------------------------------
# the summary reads the columns
# ----------------------------------------------------------------------
def _series(case: str):
    """Three seeds' lag and downstream backlog over 120 ticks with the
    recovery rows of `case`: none, a failure the fleet recovers from, or
    one whose outage outlasts the horizon."""
    ts = np.arange(120) * DT
    rows = {"no_failure": [],
            "finite": [(12.0, "region", 5, 4.5, 0),
                       (12.0, "single_task", 2, 3.0, 2),
                       (20.5, "hot_standby", 1, 1.05, 1)],
            "unrecovered": [(30.0, "region", 6, 45.0, 0),
                            (40.0, "single_task", 3, 1e3, 1)]}[case]
    dicts = [{"t": t, "mode": m, "tasks": n, "downtime": d, "job": j}
             for t, m, n, d, j in rows]
    lag = 1e6 + 1e4 * ts
    down = np.ones_like(ts)
    if rows:
        t0 = rows[0][0]
        burst = (ts >= t0) & (ts < t0 + 8.0)
        lag = lag + np.where(burst, 5e4 * (ts - t0), 0.0)
        lag = lag + np.where(ts >= t0 + 8.0, 4e5, 0.0)
        down = np.where(burst, 50.0, down)
    return ts, lag, down, dicts


def _batch(ts, lag, down, recoveries) -> JaxBatchMetrics:
    tl = ChaosTimeline(DT, len(ts), ts, np.ones(4), np.zeros((len(ts), 2),
                                                             bool),
                       np.zeros(len(ts), np.int16),
                       np.zeros(len(ts), np.int16), 2, 1, 1, recoveries)
    return JaxBatchMetrics(["src", "sink"], ts, lag[None], None, None,
                           np.array([[5.0]]), np.array([[0.5]]), [tl],
                           backlog_total=(lag + down)[None],
                           down_backlog=down[None])


@pytest.mark.parametrize("case", ["no_failure", "finite", "unrecovered"])
@pytest.mark.parametrize("slo_lag", [None, 2e6])
def test_summary_of_a_log_is_the_summary_of_its_dicts(case, slo_lag):
    ts, lag, down, dicts = _series(case)
    log = RecoveryLog.of(dicts)
    assert log == dicts
    by_log, by_dicts = (summarize(_batch(ts, lag, down, recs), [7],
                                  slo_lag=slo_lag).summaries[0]
                        for recs in (log, dicts))
    assert dataclasses.astuple(by_log) == dataclasses.astuple(by_dicts)
    assert by_log.n_failures == len(dicts)
    rt = by_log.recovery_time_s
    # the finite case heals at 22.0 s, after the latest outage end (the
    # third row's 20.5 + 1.05 s), not the first row's 12.0 + 4.5 s
    assert rt == {"no_failure": 0.0, "finite": 10.0,
                  "unrecovered": np.inf}[case]
    if dicts:
        got = _recovery_time(ts, lag, down, log)
        assert got == _recovery_time(ts, lag, down, dicts) == rt


# ----------------------------------------------------------------------
# the sweep.prep span counts the rows
# ----------------------------------------------------------------------
def test_prep_span_counts_the_chunk_recovery_rows():
    cell = tiny.cell("q12_fleet.replication", n_jobs=4, seeds=4, chunk=2)
    spans = SpanLog()
    cube = chaos_sweep.replication_tradeoff(
        program.arena(cell.config), [5, 6, 7, 8], seed_chunk=2,
        spans=spans, **program.request_kwargs(cell.traffic))
    preps = spans.of("sweep.prep")
    want = [sum(len(r.batch.recoveries[s]) for r in cube.grid.results
                for s in range(lo, lo + 2)) for lo in (0, 2)]
    assert [s.counts["recovery_rows"] for s in preps] == want
    assert min(want) > 0
