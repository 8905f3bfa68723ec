"""Sparse-phase (compact) tick lowering (ISSUE 5 tentpole, part 1).

Pillars:

* **Compact == dense at 1e-12** — the row-table sparse tick
  (`engine.CompactPhase` + `jax_engine._build_compact_run`) reproduces
  the dense arena-wide tick over every partitioner family, both
  failover modes, kill-heavy seeds that empty whole phases, and a
  10k-task deep-pipeline mega-arena.
* **One trace per bucket** — compact index/mask tables are traced
  parameters, so same-shaped plans with *different contents* (e.g.
  different partitioner kinds) share one compiled trace; only the pow2
  bucket signature keys the cache.
* **Auto selection** — `select_phase_mode` picks compact exactly when
  the eliminated arena-wide segment reductions dominate (deep packed
  arenas), dense for small/shallow graphs, and the
  ``REPRO_REQUIRE_PHASE_MODE`` guard refuses silent fallbacks.
"""
import dataclasses
import math

import jax
import numpy as np
import pytest

from repro.core.chaos import ChaosSpec
from repro.core.replication import TimingModel
from repro.core.startup import StartupConfig
from repro.streams import nexmark
from repro.streams.chaos_sweep import deployment_drill, replication_tradeoff
from repro.streams.engine import (FailoverConfig, UpgradeConfig, build_plan,
                                  pack_arena,
                                  select_phase_mode)
from repro.streams.jax_engine import (JaxStreamEngine, _FN_CACHE,
                                      _Lowered, get_cached_run_fns,
                                      run_batch)

TOL = dict(rtol=1e-12, atol=1e-9)


def _pair(graph, duration=120, n_hosts=8, **kw):
    md = JaxStreamEngine(graph, n_hosts=n_hosts, phase_mode="dense",
                         **kw).run(duration)
    mc = JaxStreamEngine(graph, n_hosts=n_hosts, phase_mode="compact",
                         **kw).run(duration)
    return md, mc


def _assert_match(md, mc):
    for n in md.qps:
        np.testing.assert_allclose(md.qps[n], mc.qps[n],
                                   err_msg=f"qps[{n}]", **TOL)
        np.testing.assert_allclose(md.backlog[n], mc.backlog[n],
                                   err_msg=f"backlog[{n}]", **TOL)
    np.testing.assert_allclose(md.source_lag, mc.source_lag, **TOL)
    np.testing.assert_allclose(md.dropped, mc.dropped, **TOL)
    np.testing.assert_allclose(md.emitted, mc.emitted, **TOL)


@pytest.mark.parametrize("partitioner", ["rebalance", "hash", "weakhash",
                                         "backlog", "rescale",
                                         "group_rescale"])
def test_compact_matches_dense_partitioners(partitioner):
    spec = ChaosSpec(seed=1, host_kill_prob_per_s=0.004,
                     straggler_frac=0.2)
    md, mc = _pair(nexmark.q2(parallelism=16, partitioner=partitioner,
                              n_groups=4),
                   chaos=spec,
                   failover=FailoverConfig(mode="region",
                                           region_restart_s=20.0))
    _assert_match(md, mc)


@pytest.mark.parametrize("graph_fn", [
    lambda: nexmark.q12(parallelism=8),
    lambda: nexmark.ss(parallelism=8),
])
def test_compact_matches_dense_pipelines(graph_fn):
    spec = ChaosSpec(seed=3, host_kill_prob_per_s=0.004,
                     straggler_frac=0.25)
    md, mc = _pair(graph_fn(), chaos=spec,
                   failover=FailoverConfig(mode="single_task",
                                           single_restart_s=4.0))
    _assert_match(md, mc)
    assert mc.dropped > 0 or not md.recoveries


def test_compact_matches_dense_kill_heavy():
    """Kill-heavy seed: whole regions go down repeatedly, so phases run
    near-empty — the masks/pads of the compact rows must keep routing,
    drops and requeues pinned to dense through every outage."""
    spec = ChaosSpec(seed=5, host_kill_prob_per_s=0.05,
                     straggler_frac=0.3)
    md, mc = _pair(nexmark.ss(parallelism=8), duration=240, chaos=spec,
                   failover=FailoverConfig(mode="region",
                                           region_restart_s=10.0))
    assert len(mc.recoveries) > 5          # the chaos actually fired
    _assert_match(md, mc)


def test_compact_matches_dense_10k_arena():
    """Deep-pipeline mega-arena (36 packed SS jobs, 6 phases — the
    CI-sized twin of the 10k-task benchmark arena): one jitted short
    run per mode, 1e-12 parity."""
    arena = nexmark.ss_arena(n_tasks=2016, parallelism=8, n_hosts=32)
    assert select_phase_mode(arena.plan) == "compact"
    spec = ChaosSpec(seed=0, host_kill_prob_per_s=0.01,
                     straggler_frac=0.2)
    fo = FailoverConfig(mode="region", region_restart_s=15.0)
    outs = {}
    for mode in ("dense", "compact"):
        low = _Lowered(arena, n_hosts=32, dt=0.5, queue_cap=256.0,
                       failover=fo, ckpt=None, seed=0, phase_mode=mode)
        run_fn, _ = get_cached_run_fns(low.desc)
        with jax.enable_x64(True):
            st, xs, _ = low.prepare(spec, 32)
            _, ys = run_fn(low.arrays, st, xs)
            outs[mode] = {k: np.asarray(v) for k, v in ys.items()}
    for k in outs["dense"]:
        np.testing.assert_allclose(outs["dense"][k], outs["compact"][k],
                                   err_msg=k, **TOL)


def test_one_trace_per_bucket():
    """Two same-shaped graphs with DIFFERENT partitioner kinds land in
    the same compact bucket signature → one compiled trace serves both
    (index/mask tables are traced, not baked), and the results still
    differ (the content is live)."""
    a = JaxStreamEngine(nexmark.q2(parallelism=8,
                                   partitioner="rebalance"),
                        n_hosts=8, phase_mode="compact")
    b = JaxStreamEngine(nexmark.q2(parallelism=8, partitioner="backlog"),
                        n_hosts=8, phase_mode="compact")
    assert a.lowered.desc == b.lowered.desc
    n0 = len(_FN_CACHE)
    ma = a.run(30)
    n1 = len(_FN_CACHE)
    mb = b.run(30)
    assert len(_FN_CACHE) == n1 and n1 <= n0 + 1
    # dense mode keys on content: same pair, two descs
    c = JaxStreamEngine(nexmark.q2(parallelism=8,
                                   partitioner="rebalance"),
                        n_hosts=8, phase_mode="dense")
    d = JaxStreamEngine(nexmark.q2(parallelism=8, partitioner="backlog"),
                        n_hosts=8, phase_mode="dense")
    assert c.lowered.desc != d.lowered.desc
    assert ma.qps["filter"].shape == mb.qps["filter"].shape


def test_phase_mode_auto_selection():
    # shallow/small graphs stay dense
    assert select_phase_mode(
        build_plan(nexmark.q2(parallelism=8), 0.5, 256.0)) == "dense"
    # deep packed arenas go compact
    assert select_phase_mode(
        nexmark.ss_arena(n_tasks=2016, parallelism=8).plan) == "compact"
    assert select_phase_mode(
        nexmark.q12_arena(n_tasks=2016, parallelism=8).plan) == "compact"
    with pytest.raises(ValueError, match="dense|compact|auto"):
        select_phase_mode(build_plan(nexmark.q2(), 0.5, 256.0), "spicy")


def test_require_phase_mode_guard(monkeypatch):
    """scripts/ci.sh smoke targets set REPRO_REQUIRE_PHASE_MODE so a
    silent fallback to the dense path fails loudly."""
    monkeypatch.setenv("REPRO_REQUIRE_PHASE_MODE", "compact")
    with pytest.raises(RuntimeError, match="refusing to fall back"):
        _Lowered(nexmark.q2(parallelism=4), n_hosts=4, dt=0.5,
                 queue_cap=256.0, failover=None, ckpt=None, seed=0,
                 phase_mode="auto")
    # explicit compact passes the guard
    low = _Lowered(nexmark.q2(parallelism=4), n_hosts=4, dt=0.5,
                   queue_cap=256.0, failover=None, ckpt=None, seed=0,
                   phase_mode="compact")
    assert low.tensor.mode == "compact"


def test_compact_config_grid_rows_match_dense():
    """The config axis composes with the compact lowering: a (C, S)
    grid run through phase_mode='compact' equals the dense grid row for
    row at 1e-12."""
    from repro.streams.jax_engine import run_config_batch
    g = nexmark.ss(parallelism=8)
    grid = [FailoverConfig(mode="region", region_restart_s=r)
            for r in (10.0, 40.0)]
    spec = ChaosSpec(host_kill_prob_per_s=0.004, straggler_frac=0.2)
    outd = run_config_batch(g, grid, range(4), base_spec=spec,
                            duration_s=60, phase_mode="dense")
    outc = run_config_batch(g, grid, range(4), base_spec=spec,
                            duration_s=60, phase_mode="compact")
    for c in range(2):
        np.testing.assert_allclose(np.asarray(outd[c].source_lag),
                                   np.asarray(outc[c].source_lag), **TOL)
        np.testing.assert_allclose(np.asarray(outd[c].qps),
                                   np.asarray(outc[c].qps), **TOL)


def test_compact_packed_arena_job_metrics():
    """Per-job emitted/dropped segments survive the compact lowering on
    a packed arena (row tables by job)."""
    arena = pack_arena([nexmark.q2(parallelism=8),
                        nexmark.q12(parallelism=8)], "shared", n_hosts=8)
    spec = ChaosSpec(seed=2, host_kill_prob_per_s=0.01)
    fo = FailoverConfig(mode="single_task", single_restart_s=3.0)
    md, mc = _pair(arena, chaos=spec, failover=fo)
    np.testing.assert_allclose(md.emitted_by_job, mc.emitted_by_job,
                               **TOL)
    np.testing.assert_allclose(md.dropped_by_job, mc.dropped_by_job,
                               **TOL)


def test_phase_mode_seed_width_selection():
    """The seed-width argument widens the compact region (wide sweeps
    amortize row-table overhead)."""
    plan = build_plan(nexmark.ss(parallelism=8), 0.5, 256.0)
    assert select_phase_mode(plan, seed_width=1) == "dense"
    assert select_phase_mode(plan, seed_width=64) == "compact"
    # tiny graphs stay dense at any width via the absolute floor
    tiny = build_plan(nexmark.q2(parallelism=2), 0.5, 256.0)
    assert select_phase_mode(tiny, seed_width=1) == "dense"


def test_run_batch_compact_matches_dense():
    """run_batch's vmapped seed batch over a deep packed arena: compact
    equals dense seed for seed at 1e-12, per-job segments included."""
    arena = nexmark.ss_arena(n_tasks=168, parallelism=4, n_hosts=8)
    spec = ChaosSpec(host_kill_prob_per_s=0.02, straggler_frac=0.2)
    bd = run_batch(arena, range(5), duration_s=60, base_spec=spec,
                   phase_mode="dense")
    bc = run_batch(arena, range(5), duration_s=60, base_spec=spec,
                   phase_mode="compact")
    np.testing.assert_allclose(bd.source_lag, bc.source_lag, **TOL)
    np.testing.assert_allclose(bd.qps, bc.qps, **TOL)
    np.testing.assert_allclose(bd.backlog, bc.backlog, **TOL)
    np.testing.assert_allclose(bd.emitted_by_job, bc.emitted_by_job,
                               **TOL)
    np.testing.assert_allclose(bd.dropped_by_job, bc.dropped_by_job,
                               **TOL)


def _replication_cube(phase_mode):
    """The replication cell's request shape at test size: three failover
    modes × two checkpoint intervals × two brownouts over a 10-job Q12
    arena."""
    timing = TimingModel()
    failovers = {
        "hot_standby": FailoverConfig.from_replication(
            timing, mode="hot_standby"),
        "passive": FailoverConfig.from_replication(
            timing, mode="single_task", state_bytes=8 << 30),
        "passive_lazy": dataclasses.replace(
            FailoverConfig.from_replication(timing, mode="region",
                                            state_bytes=8 << 30),
            region_restart_s=5.0, lazyload_stagger_s=1.0)}
    return replication_tradeoff(
        nexmark.q12_arena(n_tasks=240, n_hosts=16), range(6),
        base_spec=nexmark.ha_drill_spec(
            burst_t=15.0, brownout=(10.0, 30.0, 6.0),
            mq_outage=(35.0, 40.0), host_kill_prob_per_s=0.002),
        duration_s=45.0, failovers=failovers, ckpt_intervals=(10.0, 30.0),
        brownouts=((), ((10.0, 30.0, 4.0),)), seed_chunk=4,
        phase_mode=phase_mode).grid


def _drill_cube(phase_mode):
    """The drill cell's request shape at test size: three upgrade
    policies × two canary fractions × rollback off/on over a 6-job
    Q3/Q11 fleet."""
    up = dict(t_upgrade_s=8.0, wave_stagger_s=1.0, canary_sel_scale=1.5,
              rollback_window_s=4.0)
    return deployment_drill(
        nexmark.drill_fleet(n_jobs=6, parallelism=2, n_hosts=8), range(6),
        base_spec=ChaosSpec(host_kill_prob_per_s=0.004,
                            zk_down=((20.0, 24.0),),
                            hdfs_down=((22.0, 28.0),)),
        duration_s=40.0,
        policies={"hot": UpgradeConfig(hot=True, **up),
                  "cold": UpgradeConfig(hot=False, **up),
                  "cold+accel": UpgradeConfig(hot=False,
                                              startup=StartupConfig(),
                                              **up)},
        canary_fracs=(0.25, 0.5), rollback_thresholds=(math.inf, 100.0),
        failover=FailoverConfig(mode="single_task", detect_s=1.0,
                                single_restart_s=2.0),
        seed_chunk=4, phase_mode=phase_mode).grid


_DISCRETE = ("seed", "n_failures", "recovery_time_s", "slo_violation_ticks",
             "slo_violation_frac", "ckpt_attempts", "ckpt_success")
_FLOWS = ("max_backlog", "max_lag", "slo_threshold", "dropped", "emitted")


@pytest.mark.parametrize("fleet", ["q12_replication", "q3q11_drill"])
def test_served_grid_compact_matches_dense(fleet):
    """The served summary path (`sweep_configs` over a `SummaryGridPlan`,
    chunked) gives the same summaries and surfaces under both lowerings:
    discrete fields equal, flows at 1e-12."""
    cube = {"q12_replication": _replication_cube,
            "q3q11_drill": _drill_cube}[fleet]
    gd, gc = cube("dense"), cube("compact")
    assert (gd.phase_mode, gc.phase_mode) == ("dense", "compact")
    # the cube sees failures that recover within the horizon
    rec = gc.recovery_surface
    assert (np.isfinite(rec) & (rec > 0)).any()
    assert gd.labels == gc.labels
    for rd, rc in zip(gd.results, gc.results):
        for sd, sc in zip(rd.summaries, rc.summaries):
            for f in _DISCRETE:
                assert getattr(sd, f) == getattr(sc, f), f
            for f in _FLOWS:
                assert getattr(sc, f) == pytest.approx(getattr(sd, f),
                                                       rel=1e-12), f
    for name in ("recovery_surface", "slo_surface", "rollback_surface",
                 "thrash_surface", "rescale_surface"):
        np.testing.assert_array_equal(getattr(gd, name), getattr(gc, name),
                                      err_msg=name)
    for name in ("backlog_surface", "lost_surface", "cost_surface"):
        np.testing.assert_allclose(getattr(gc, name), getattr(gd, name),
                                   rtol=1e-12, atol=0, err_msg=name)
