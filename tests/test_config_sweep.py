"""Resiliency-config grid axis (ISSUE 4): the engine's third vmap axis.

Pillars:

* **Row parity** — every config row of `run_config_batch` equals a
  standalone `JaxStreamEngine` run with that exact config at 1e-12
  (identical lowering, so down to vmap-reduction reassociation only),
  and pins to the numpy engine at 1e-5. Holds with the kill-tensor
  sharing fast path (no checkpoints) AND with per-config rebuilt
  timelines (checkpoint grids).
* **One trace per grid shape** — resiliency floats (detect, restart
  budgets, mode masks, qcap, selectivities) are traced leaves, so
  sweeping config VALUES never retraces; only a new (C, S) shape does.
* **Per-job configs** — `FailoverConfig`/`CheckpointConfig` lists inside
  a `PackedArena`: disjoint-host packing with per-job configs equals K
  independent runs, each with its own config, in both engines.
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.core.chaos import ChaosEngine, ChaosSpec, refit_failover
from repro.streams import nexmark
from repro.streams.chaos_sweep import (ScenarioSummary, _chunk_surfaces,
                                       summarize, sweep_configs)
from repro.streams.engine import (CheckpointConfig, FailoverConfig,
                                  StreamEngine, UpgradeConfig, pack_arena)
from repro.streams.jax_engine import (ConfigGridPlan, JaxStreamEngine,
                                      backlog_series,
                                      get_cached_config_fn,
                                      run_config_batch)
from repro.streams.spans import SpanLog

TOL = dict(rtol=1e-12, atol=1e-9)
KILLS = ((20.0, 2),)


def _graph():
    return nexmark.q2(parallelism=8, partitioner="weakhash", n_groups=4)


GRID = [FailoverConfig(mode="region", region_restart_s=10.0),
        FailoverConfig(mode="region", region_restart_s=40.0,
                       detect_s=2.5),
        FailoverConfig(mode="single_task", single_restart_s=4.0)]


# ----------------------------------------------------------------------
# config-batch row i == standalone run with that config
# ----------------------------------------------------------------------
def test_config_batch_rows_match_standalone():
    spec = ChaosSpec(host_kill_prob_per_s=0.004, straggler_frac=0.2)
    seeds = list(range(4))
    out = run_config_batch(_graph(), GRID, seeds, base_spec=spec,
                           duration_s=120, n_hosts=8)
    assert len(out) == len(GRID)
    for c, fo in enumerate(GRID):
        bm = out[c]
        assert bm.source_lag.shape == (4, 240)
        for i in seeds:
            sspec = ChaosSpec(host_kill_prob_per_s=0.004,
                              straggler_frac=0.2, seed=i)
            m = JaxStreamEngine(_graph(), n_hosts=8, chaos=sspec,
                                failover=fo).run(120)
            np.testing.assert_allclose(bm.source_lag[i], m.source_lag,
                                       err_msg=f"cfg{c} seed{i}", **TOL)
            np.testing.assert_allclose(bm.dropped[i], m.dropped, **TOL)
            assert bm.recoveries[i] == m.recoveries, (c, i)
    # ... and the grid pins to the numpy engine at 1e-5
    a = StreamEngine(_graph(), n_hosts=8,
                     chaos=ChaosEngine(ChaosSpec(
                         host_kill_prob_per_s=0.004, straggler_frac=0.2,
                         seed=1)),
                     failover=GRID[2])
    a.run(120)
    np.testing.assert_allclose(np.asarray(a.metrics.source_lag),
                               out[2].source_lag[1], rtol=1e-5, atol=1e-5)
    # the budget axis is live: same kills, per-config downtimes
    d0 = [r["downtime"] for r in out[0].recoveries[0]]
    d1 = [r["downtime"] for r in out[1].recoveries[0]]
    assert set(d0) == {11.0} and set(d1) == {42.5}


def test_config_batch_ckpt_interval_axis():
    """Checkpoint-interval grids rebuild per-config timelines (storage
    draws are config-dependent) — rows must still equal standalone
    runs."""
    grid = [(FailoverConfig(mode="region", region_restart_s=15.0),
             CheckpointConfig(interval_s=iv, mode="region"))
            for iv in (20.0, 45.0)]
    spec = ChaosSpec(host_kill_prob_per_s=0.002, storage_slow_prob=0.3,
                     storage_slow_factor=12)
    seeds = [0, 1, 2]
    out = run_config_batch(nexmark.ds(parallelism=6), grid, seeds,
                           base_spec=spec, duration_s=200, n_hosts=6)
    attempts = [int(out[c].ckpt_attempts[0]) for c in range(2)]
    assert attempts[0] > attempts[1] > 0       # interval axis is live
    for c, (fo, ck) in enumerate(grid):
        for i in seeds:
            m = JaxStreamEngine(
                nexmark.ds(parallelism=6), n_hosts=6,
                chaos=ChaosSpec(host_kill_prob_per_s=0.002,
                                storage_slow_prob=0.3,
                                storage_slow_factor=12, seed=i),
                failover=fo, ckpt=ck).run(200)
            np.testing.assert_allclose(out[c].source_lag[i],
                                       m.source_lag,
                                       err_msg=f"cfg{c} seed{i}", **TOL)
            assert int(out[c].ckpt_attempts[i]) == m.ckpt_attempts
            assert int(out[c].ckpt_success[i]) == m.ckpt_success
            assert int(out[c].ckpt_epoch[i]) == m.ckpt_attempts


def test_config_mix_seed_cube():
    """configs × mixes compose: the identity-mix slice of the (M, C, S)
    cube equals the plain (C, S) grid bit-for-bit."""
    arena = pack_arena([nexmark.q2(parallelism=8), nexmark.q12(
        parallelism=8)], "shared", n_hosts=8)
    spec = ChaosSpec(seed=3, host_kill_prob_per_s=0.003)
    grid = [FailoverConfig(mode="region", region_restart_s=r)
            for r in (10.0, 30.0)]
    base = run_config_batch(arena, grid, range(3), base_spec=spec,
                            duration_s=60)
    cube = run_config_batch(arena, grid, range(3), base_spec=spec,
                            duration_s=60,
                            mixes=[[1.0, 1.0], [0.5, 2.0]])
    for c in range(2):
        np.testing.assert_allclose(cube[0][c].source_lag,
                                   base[c].source_lag, rtol=0, atol=0)
        # emission scales per job by exactly the mix multiplier
        np.testing.assert_allclose(
            cube[1][c].emitted_by_job,
            base[c].emitted_by_job * np.array([0.5, 2.0]), rtol=1e-9)


# ----------------------------------------------------------------------
# trace cache: one trace per grid shape, config values are traced
# ----------------------------------------------------------------------
def test_config_grid_one_trace_per_shape():
    from repro.streams.jax_engine import _Lowered
    g = _graph()
    low = _Lowered(g, n_hosts=8, dt=0.5, queue_cap=256.0, failover=None,
                   ckpt=None, seed=0)
    # ckpt-free grids use the shared-kills trace variant (one (S,T,H)
    # kill tensor broadcast over the config axis)
    fn = get_cached_config_fn(low.desc, shared_kills=True)
    before = fn._cache_size()
    spec = ChaosSpec(host_kill_prob_per_s=0.004)
    run_config_batch(g, GRID[:2], range(4), base_spec=spec,
                     duration_s=30, n_hosts=8)
    # different VALUES (and even a different failover MODE mix): the
    # (2, 4) grid shape is unchanged → the same trace serves it
    grid2 = [FailoverConfig(mode="single_task", single_restart_s=2.0),
             {"failover": GRID[0], "qcap_scale": 0.5, "sel_scale": 1.1}]
    run_config_batch(g, grid2, range(4), base_spec=spec,
                     duration_s=30, n_hosts=8)
    assert fn._cache_size() - before == 1
    # a new grid shape (C=3) traces once more
    run_config_batch(g, GRID, range(4), base_spec=spec,
                     duration_s=30, n_hosts=8)
    assert fn._cache_size() - before == 2


def test_qcap_and_selectivity_scales_are_live():
    spec = ChaosSpec(seed=0)        # failure-free: isolate the knobs
    grid = [{"failover": None}, {"failover": None, "sel_scale": 0.5}]
    out = run_config_batch(nexmark.q12(parallelism=4), grid, [0],
                           base_spec=spec, duration_s=30, n_hosts=4)
    # halving window_count selectivity halves sink-side traffic
    q_full = out[0].qps[0, :, -1].sum()
    q_half = out[1].qps[0, :, -1].sum()
    assert q_half < 0.75 * q_full


# ----------------------------------------------------------------------
# per-job configs inside one arena
# ----------------------------------------------------------------------
def _per_job_setup():
    graphs = [nexmark.q2(parallelism=8, partitioner="weakhash",
                         n_groups=4), nexmark.q12(parallelism=8)]
    fos = [FailoverConfig(mode="region", region_restart_s=12.0),
           FailoverConfig(mode="single_task", single_restart_s=4.0,
                          detect_s=2.0)]
    arena = pack_arena(graphs, "disjoint", n_hosts=8)
    at = sum((arena.lift_kills(j, KILLS) for j in range(2)), ())
    return graphs, fos, arena, ChaosSpec(host_kill_at=at)


@pytest.mark.parametrize("engine_cls", [StreamEngine, JaxStreamEngine])
def test_per_job_failover_disjoint_equals_independent(engine_cls):
    """Disjoint-host packing with per-job FailoverConfigs (different
    modes AND budgets) == K independent runs, each under its own
    config."""
    graphs, fos, arena, spec = _per_job_setup()
    chaos = ChaosEngine(spec) if engine_cls is StreamEngine else spec
    eng = engine_cls(arena, chaos=chaos, failover=fos)
    m = eng.run(60)
    pm = m if engine_cls is JaxStreamEngine else eng.metrics
    for j, g in enumerate(graphs):
        solo_chaos = (ChaosEngine(ChaosSpec(host_kill_at=KILLS))
                      if engine_cls is StreamEngine
                      else ChaosSpec(host_kill_at=KILLS))
        solo = engine_cls(g, n_hosts=8, chaos=solo_chaos, failover=fos[j])
        sm = solo.run(60)
        if engine_cls is StreamEngine:
            sm = solo.metrics
        pre = arena.jobs[j].prefix
        for name in g.topo_order():
            np.testing.assert_allclose(
                np.asarray(pm.backlog[pre + name]),
                np.asarray(sm.backlog[name]),
                rtol=1e-6, atol=1e-6, err_msg=f"{j}/{name}")
        np.testing.assert_allclose(pm.emitted_by_job[j], sm.emitted,
                                   rtol=1e-9)
        np.testing.assert_allclose(pm.dropped_by_job[j], sm.dropped,
                                   atol=1e-9)
        mine = [dict(r) for r in pm.recoveries if r.get("job") == j]
        for r in mine:
            r.pop("job")
        assert mine == sm.recoveries, j
    # job 1 runs single_task: its drops are real, job 0's are zero
    assert pm.dropped_by_job[1] > 0
    assert pm.dropped_by_job[0] == 0


def test_per_job_ckpt_schedules_and_parity():
    """Per-job CheckpointConfigs: each job checkpoints on its own
    schedule (per-job counters in both engines), and with draw-free
    storage (slow_prob=0) the packed run equals K independent runs."""
    graphs, fos, arena, spec = _per_job_setup()
    cks = [CheckpointConfig(interval_s=20.0, mode="region"),
           CheckpointConfig(interval_s=35.0, mode="region")]
    a = StreamEngine(arena, chaos=ChaosEngine(spec), failover=fos,
                     ckpt=cks)
    a.run(120)
    mb = JaxStreamEngine(arena, chaos=spec, failover=fos,
                         ckpt=cks).run(120)
    want = np.array([120 // 20, 120 // 35])
    np.testing.assert_array_equal(a.metrics.ckpt_by_job[:, 0], want)
    np.testing.assert_array_equal(mb.ckpt_by_job[:, 0], want)
    assert a.metrics.ckpt_attempts == mb.ckpt_attempts == want.sum()
    assert mb.ckpt_epoch == mb.ckpt_attempts
    np.testing.assert_array_equal(a.metrics.ckpt_by_job, mb.ckpt_by_job)
    for j, g in enumerate(graphs):
        solo = StreamEngine(g, n_hosts=8,
                            chaos=ChaosEngine(ChaosSpec(
                                host_kill_at=KILLS)),
                            failover=fos[j], ckpt=cks[j])
        solo.run(120)
        assert solo.metrics.ckpt_attempts == want[j]
        pre = arena.jobs[j].prefix
        for name in g.topo_order():
            np.testing.assert_allclose(
                a.metrics.backlog[pre + name], solo.metrics.backlog[name],
                rtol=1e-9, atol=1e-9, err_msg=f"{j}/{name}")


def test_per_job_config_inside_config_grid():
    """Per-job FailoverConfig lists work as grid ROWS of
    run_config_batch: row parity against the standalone per-job-config
    engine."""
    graphs, fos, arena, spec = _per_job_setup()
    grid = [{"failover": fos, "label": "per-job"},
            {"failover": FailoverConfig(mode="region",
                                        region_restart_s=25.0)}]
    out = run_config_batch(arena, grid, [0, 1], base_spec=spec,
                           duration_s=60)
    m = JaxStreamEngine(arena, chaos=spec, failover=fos).run(60)
    np.testing.assert_allclose(out[0].source_lag[0], m.source_lag, **TOL)
    assert out[0].recoveries[0] == m.recoveries


def test_per_job_failover_list_rejected_without_arena():
    with pytest.raises(ValueError, match="per-job"):
        StreamEngine(nexmark.q2(parallelism=4), n_hosts=4,
                     failover=[FailoverConfig(), FailoverConfig()])


# ----------------------------------------------------------------------
# sweep driver surfaces + refit guard
# ----------------------------------------------------------------------
def test_sweep_configs_recovery_surface():
    grid = [FailoverConfig(mode="region", region_restart_s=r)
            for r in (10.0, 60.0)]
    # one scheduled early kill per scenario (stragglers vary by seed) and
    # a horizon long enough that every scenario recovers: the surface is
    # then a clean recovery-time-vs-restart-budget curve
    res = sweep_configs(_graph(), grid, range(6),
                        base_spec=ChaosSpec(host_kill_at=((10.0, 2),),
                                            straggler_frac=0.2),
                        duration_s=400, n_hosts=8)
    rec = res.recovery_surface
    assert rec.shape == (2, 6)
    assert res.slo_surface.shape == (2, 6)
    assert len(res.results) == 2 and len(res.labels) == 2
    rows = res.rows()
    assert all(r["failed_scenarios"] == 6 for r in rows)
    assert np.isfinite(rec).all()
    # recovery is bounded below by the failover outage window (detect +
    # restart), so the budget axis shifts the whole surface floor
    assert rec[1].min() >= 60.0
    assert rec[0].min() < 60.0
    # the straggler-free scenario recovers right at the outage boundary
    assert rec[0][0] == pytest.approx(11.0)
    assert rec[1][0] == pytest.approx(61.0)


def test_refit_failover_rejects_ckpt_timelines():
    from repro.core.chaos import build_chaos_timeline
    task_host = np.arange(8) % 4
    tl = build_chaos_timeline(
        ChaosSpec(seed=0), n_ticks=40, dt=0.5, n_hosts=4,
        task_host=task_host, task_region=np.zeros(8, int),
        regions=[set(range(8))], failover_mode="region",
        ckpt_interval_s=5.0)
    assert tl.ckpt_attempts > 0
    with pytest.raises(ValueError, match="checkpoint-free"):
        refit_failover(tl, task_host=task_host,
                       task_region=np.zeros(8, int))


# ----------------------------------------------------------------------
# sweep_configs copies per-tick backlog series, not per-op histories
# ----------------------------------------------------------------------
DRILL_SPEC = ChaosSpec(host_kill_prob_per_s=0.01, zk_down=((10.0, 12.0),))
DRILL = [{"failover": FailoverConfig(mode="single_task", detect_s=1.0,
                                     single_restart_s=2.0),
          "ckpt": CheckpointConfig(interval_s=6.0),
          "upgrade": UpgradeConfig(t_upgrade_s=8.0, wave_stagger_s=1.0,
                                   canary_frac=f, rollback_threshold=thr,
                                   canary_sel_scale=1.5)}
         for f in (0.5, 1.0) for thr in (math.inf, 50.0)]
DRILL_SEEDS = list(range(5))
#: summary fields read off a backlog, lag or flow sum
FLOWS = {"max_backlog", "max_lag", "slo_threshold", "dropped", "emitted"}
SERIES_SURFACES = ("recovery_surface", "slo_surface", "lost_surface",
                   "rollback_surface", "thrash_surface", "rescale_surface",
                   "cost_surface")


@pytest.fixture(scope="module")
def drill():
    """One small drill grid (a Q3 + Q11 fleet, two sources in the Q3
    job) as the summary sweep runs it and as the full-history rows."""
    fleet = nexmark.drill_fleet(n_jobs=2, parallelism=2, n_hosts=4)
    kw = dict(base_spec=DRILL_SPEC, duration_s=40.0, seed_chunk=2)
    cube = sweep_configs(fleet, DRILL, DRILL_SEEDS, **kw)
    full = run_config_batch(fleet, DRILL, DRILL_SEEDS, **kw)
    return fleet, cube, full


def test_summary_sweep_matches_full_history_rows(drill):
    fleet, cube, full = drill
    ref = [summarize(bm, DRILL_SEEDS) for bm in full]
    assert any(s.recovery_time_s > 0 for r in ref for s in r.summaries)
    for got, want in zip(cube.results, ref):
        for a, b in zip(got.summaries, want.summaries):
            for f in dataclasses.fields(ScenarioSummary):
                x, y = getattr(a, f.name), getattr(b, f.name)
                if f.name in FLOWS:
                    assert x == pytest.approx(y, rel=1e-12, abs=0), f.name
                else:
                    assert x == y, f.name
    surf = _chunk_surfaces(full, ref)
    for name in SERIES_SURFACES:
        assert np.array_equal(getattr(cube, name), surf[name]), name
    np.testing.assert_allclose(cube.backlog_surface,
                               surf["backlog_surface"], rtol=1e-12, atol=0)
    assert np.isfinite(cube.rollback_surface).any()


def test_full_history_rows_keep_per_op_histories(drill):
    fleet, cube, full = drill
    n_ops = len(fleet.graph.ops)
    for bm, res in zip(full, cube.results):
        shape = (len(DRILL_SEEDS), len(bm.t), n_ops)
        assert bm.qps.shape == bm.backlog.shape == shape
        assert bm.row(0).qps and bm.row(0).backlog
        total, down = backlog_series(bm.backlog, fleet.plan.src_cols)
        assert np.array_equal(bm.backlog_total, total)
        assert np.array_equal(bm.down_backlog, down)
        np.testing.assert_allclose(res.batch.down_backlog, down,
                                   rtol=1e-12, atol=0)
        np.testing.assert_array_equal(res.batch.source_lag, bm.source_lag)


def test_series_only_batch_refuses_per_op_rows(drill):
    fleet, cube, _ = drill
    bm = cube.results[0].batch
    assert bm.qps is None and bm.backlog is None
    assert bm.down_backlog.shape == bm.backlog_total.shape == \
           bm.source_lag.shape
    with pytest.raises(ValueError, match="per-op histories"):
        bm.row(0)
    with pytest.raises(ValueError, match="per-op histories"):
        bm.job_view(fleet.jobs[0])


def test_summary_copy_moves_under_one_percent_of_the_full_copy():
    # 160 one-task Q12 jobs: 480 ops, 100 ticks
    arena = nexmark.q12_arena(n_tasks=480, parallelism=1, n_hosts=16)
    grid = GRID[:2]
    kw = dict(base_spec=ChaosSpec(host_kill_prob_per_s=0.004),
              duration_s=50.0, seed_chunk=2)
    series, full = [], []
    cube = sweep_configs(arena, grid, range(2), on_chunk=series.append,
                         **kw)
    run_config_batch(arena, grid, range(2), on_chunk=full.append, **kw)
    (chunk,), (full_chunk,) = series, full
    c, s, t = len(grid), 2, len(cube.results[0].batch.t)
    n_ops, n_jobs = len(arena.graph.ops), len(arena.jobs)
    ckpt = cube.results[0].batch.ckpt_epoch.itemsize
    # lag, total and downstream backlog; per-job emitted and dropped;
    # the checkpoint epoch and four f64 scalars per scenario
    finals = c * s * (2 * n_jobs * 8 + ckpt + 4 * 8)
    assert chunk.history_bytes == c * s * 3 * t * 8 + finals
    assert full_chunk.history_bytes == \
           c * s * (2 * n_ops + 1) * t * 8 + finals
    assert chunk.history_bytes < 0.01 * full_chunk.history_bytes


def test_chunks_count_routed_entries_and_fired_rollbacks(drill):
    """Each chunk counts the destination entries its pass routed (Σ over
    the lowering's phases of D, times ticks, configs and the chunk's
    seeds) on its ``sweep.device`` span, and the scenarios whose
    auto-rollback fired on its ``sweep.summarize`` span."""
    fleet = drill[0]
    chunks, log = [], SpanLog()
    sweep_configs(fleet, DRILL, DRILL_SEEDS, base_spec=DRILL_SPEC,
                  duration_s=40.0, seed_chunk=2, on_chunk=chunks.append,
                  spans=log)
    low = ConfigGridPlan(fleet, DRILL, DRILL_SEEDS, base_spec=DRILL_SPEC,
                         duration_s=40.0).low
    per_tick = sum(ph.D for ph in low.tensor.phases)
    assert len(low.tensor.phases) > 2 and per_tick > 0
    # chunks of 2, 2 and 1 seeds pad to no wider bucket
    assert [c.route_entries for c in chunks] == [
        per_tick * 80 * len(DRILL) * (c.seed_hi - c.seed_lo)
        for c in chunks]
    assert [s.counts["route_entries"] for s in log.of("sweep.device")] \
        == [c.route_entries for c in chunks]
    fired = [int(np.isfinite(c.rollback_surface).sum()) for c in chunks]
    assert [c.rollbacks for c in chunks] == fired and sum(fired) > 0
    assert [s.counts["rollbacks"] for s in log.of("sweep.summarize")] \
        == fired
