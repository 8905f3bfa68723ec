"""Mega-arena correctness: packed co-located jobs vs independent runs.

Two pillars (ISSUE 3 / paper's cluster perspective):

* **Disjoint parity** — K jobs packed onto disjoint host ranges are
  K independent clusters: every per-job metric of the packed run must
  match the standalone `StreamEngine`/`JaxStreamEngine` runs at 1e-6.
* **Shared-host interference** — with overlapping host maps, one chaos
  host kill must down tasks of EVERY co-located job on that host, in
  both engines, with per-job recovery attribution.

Plus: packed numpy-vs-jax parity under random chaos, per-job sweep
summaries, the job-mix vmap axis, device-sharded sweeping, retrace-free
seed padding, and the opt-in numpy baseline of the sweep driver.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.chaos import ChaosEngine, ChaosSpec
from repro.streams import nexmark
from repro.streams.chaos_sweep import sweep
from repro.streams.engine import (CheckpointConfig, FailoverConfig,
                                  StreamEngine, pack_arena)
from repro.streams.jax_engine import (JaxStreamEngine, run_batch,
                                      run_mix_batch)

TOL = dict(rtol=1e-6, atol=1e-6)
KILLS = ((20.0, 2),)                      # job-local host kill schedule


def _jobs():
    return [nexmark.q2(parallelism=8, partitioner="weakhash", n_groups=4),
            nexmark.q12(parallelism=8)]


def _lifted_spec(arena):
    """One global spec delivering each job's local KILLS schedule."""
    at = sum((arena.lift_kills(j, KILLS) for j in range(arena.n_jobs)), ())
    return ChaosSpec(host_kill_at=at)


# ----------------------------------------------------------------------
# disjoint-host packing == K independent runs (parity, 1e-6)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["region", "single_task"])
def test_disjoint_packed_matches_independent_numpy(mode):
    graphs = _jobs()
    fo = FailoverConfig(mode=mode, region_restart_s=15.0,
                        single_restart_s=5.0)
    arena = pack_arena(graphs, "disjoint", n_hosts=8)
    packed = StreamEngine(arena, chaos=ChaosEngine(_lifted_spec(arena)),
                          failover=fo)
    packed.run(60)
    for j, g in enumerate(graphs):
        solo = StreamEngine(g, n_hosts=8,
                            chaos=ChaosEngine(ChaosSpec(host_kill_at=KILLS)),
                            failover=fo)
        solo.run(60)
        pre = arena.jobs[j].prefix
        for name in g.topo_order():
            np.testing.assert_allclose(
                packed.metrics.backlog[pre + name],
                solo.metrics.backlog[name], err_msg=f"backlog {j}/{name}",
                **TOL)
            np.testing.assert_allclose(
                packed.metrics.qps[pre + name], solo.metrics.qps[name],
                err_msg=f"qps {j}/{name}", **TOL)
        np.testing.assert_allclose(packed.metrics.emitted_by_job[j],
                                   solo.metrics.emitted, rtol=1e-9)
        np.testing.assert_allclose(packed.metrics.dropped_by_job[j],
                                   solo.metrics.dropped, atol=1e-9)
        # per-job recovery events mirror the solo run's (plus the job tag)
        mine = [dict(r) for r in packed.metrics.recoveries
                if r.get("job") == j]
        for r in mine:
            r.pop("job")
        assert mine == solo.metrics.recoveries


def test_disjoint_packed_matches_independent_jax():
    graphs = _jobs()
    fo = FailoverConfig(mode="region", region_restart_s=15.0)
    arena = pack_arena(graphs, "disjoint", n_hosts=8)
    pm = JaxStreamEngine(arena, chaos=_lifted_spec(arena),
                         failover=fo).run(60)
    for j, g in enumerate(graphs):
        sm = JaxStreamEngine(g, n_hosts=8,
                             chaos=ChaosSpec(host_kill_at=KILLS),
                             failover=fo).run(60)
        pre = arena.jobs[j].prefix
        for name in g.topo_order():
            np.testing.assert_allclose(pm.backlog[pre + name],
                                       sm.backlog[name],
                                       err_msg=f"{j}/{name}", **TOL)
        np.testing.assert_allclose(pm.emitted_by_job[j], sm.emitted,
                                   rtol=1e-9)


# ----------------------------------------------------------------------
# shared-host kills: interference drill through both engines
# ----------------------------------------------------------------------
@pytest.mark.parametrize("engine_cls", [StreamEngine, JaxStreamEngine])
def test_shared_host_kill_downs_every_colocated_job(engine_cls):
    graphs = _jobs()
    fo = FailoverConfig(mode="region", region_restart_s=15.0)
    arena = pack_arena(graphs, "shared", n_hosts=8)
    spec = ChaosSpec(host_kill_at=KILLS)
    chaos = ChaosEngine(spec) if engine_cls is StreamEngine else spec
    eng = engine_cls(arena, chaos=chaos, failover=fo)
    m = eng.run(60)
    recs = m.recoveries
    # ONE host kill → one recovery event PER co-located job
    assert {r["job"] for r in recs} == {0, 1}
    assert all(r["t"] == recs[0]["t"] for r in recs)
    assert all(r["tasks"] > 0 for r in recs)
    # both jobs' pipelines stall: downstream qps of each job dips to 0
    # inside the outage window
    t = np.asarray(m.t)
    outage = (t >= 20.0) & (t <= 20.0 + 16.0)
    for j, g in enumerate(graphs):
        sink = arena.jobs[j].prefix + g.topo_order()[-1]
        assert float(np.min(np.asarray(m.qps[sink])[outage])) == 0.0, sink


def test_packed_random_chaos_numpy_jax_parity():
    """Packed arena under Poisson kills + stragglers + checkpoints: the
    numpy engine and the JAX twin consume the identical chaos stream over
    the shared pool, so full-run metrics pin at 1e-5."""
    graphs = _jobs()
    fo = FailoverConfig(mode="region", region_restart_s=20.0)
    ck = CheckpointConfig(interval_s=30.0, mode="region")
    spec = ChaosSpec(seed=5, host_kill_prob_per_s=0.004,
                     straggler_frac=0.2, storage_slow_prob=0.2)
    arena = pack_arena(graphs, "shared", n_hosts=8)
    a = StreamEngine(arena, chaos=ChaosEngine(spec), failover=fo, ckpt=ck)
    a.run(120)
    mb = JaxStreamEngine(arena, chaos=spec, failover=fo, ckpt=ck).run(120)
    assert len(mb.recoveries) > 1        # chaos actually fired
    for name in arena.graph.topo_order():
        np.testing.assert_allclose(np.array(a.metrics.backlog[name]),
                                   mb.backlog[name], rtol=1e-5, atol=1e-5,
                                   err_msg=name)
    assert a.metrics.recoveries == mb.recoveries
    np.testing.assert_allclose(a.metrics.emitted_by_job,
                               mb.emitted_by_job, rtol=1e-6)
    assert (a.metrics.ckpt_attempts, a.metrics.ckpt_success) == \
        (mb.ckpt_attempts, mb.ckpt_success)


# ----------------------------------------------------------------------
# per-job sweep summaries
# ----------------------------------------------------------------------
def test_packed_sweep_reports_per_job_breakdowns():
    graphs = _jobs()
    fo = FailoverConfig(mode="region", region_restart_s=15.0)
    arena = pack_arena(graphs, "disjoint", n_hosts=8)
    # kill only job 0's hosts: job 0 must report failures, job 1 none
    spec = ChaosSpec(host_kill_at=arena.lift_kills(0, KILLS))
    res = sweep(arena, [ChaosSpec(host_kill_at=arena.lift_kills(0, KILLS),
                                  seed=s) for s in range(3)],
                base_spec=spec, duration_s=60)
    assert set(res.job_results) == {j.name for j in arena.jobs}
    r0 = res.job_results[arena.jobs[0].name]
    r1 = res.job_results[arena.jobs[1].name]
    assert all(s.n_failures == 1 for s in r0.summaries)
    assert all(s.n_failures == 0 for s in r1.summaries)
    assert all(s.recovery_time_s > 0 for s in r0.summaries)
    assert all(s.recovery_time_s == 0 for s in r1.summaries)
    # per-job emitted segments sum to the fleet total
    em = res.batch.emitted_by_job
    np.testing.assert_allclose(em.sum(axis=1), res.batch.emitted)


def test_sweep_numpy_baseline_is_opt_in():
    g = nexmark.q2(parallelism=4)
    spec = ChaosSpec(host_kill_prob_per_s=0.003)
    res = sweep(g, range(3), base_spec=spec, duration_s=30, n_hosts=4)
    assert res.numpy_check is None       # the default: no replay cost
    res = sweep(g, range(3), base_spec=spec, duration_s=30, n_hosts=4,
                compare_numpy=True)
    assert res.numpy_check["seeds_checked"] == [0, 1, 2]
    assert res.numpy_check["max_rel_lag_dev"] < 1e-5


# ----------------------------------------------------------------------
# job-mix vmap axis + device-sharded batches
# ----------------------------------------------------------------------
def test_mix_batch_second_vmap_axis():
    arena = pack_arena(_jobs(), "shared", n_hosts=8)
    spec = ChaosSpec(seed=3, host_kill_prob_per_s=0.003)
    fo = FailoverConfig(mode="region", region_restart_s=15.0)
    mixes = [[1.0, 1.0], [0.5, 2.0]]
    out = run_mix_batch(arena, mixes, range(3), base_spec=spec,
                        duration_s=60, failover=fo)
    base = run_batch(arena, range(3), base_spec=spec, duration_s=60,
                     failover=fo)
    # identity mix row == the plain batch
    np.testing.assert_allclose(out[0].source_lag, base.source_lag,
                               rtol=1e-9, atol=1e-9)
    # emission scales per job by exactly the mix multiplier (chaos and
    # liveness are rate-independent)
    np.testing.assert_allclose(out[1].emitted_by_job,
                               base.emitted_by_job * np.array([0.5, 2.0]),
                               rtol=1e-9)


def test_mix_batch_rejects_bad_mix_width():
    arena = pack_arena(_jobs(), "shared", n_hosts=8)
    with pytest.raises(ValueError, match="one multiplier per job"):
        run_mix_batch(arena, [[1.0, 1.0, 1.0]], [0], duration_s=10,
                      base_spec=ChaosSpec())


def test_sharded_batch_matches_unsharded():
    """devices= routes through the repro.dist shard_map path; with one
    local device the shard axis is 1 but the full sharded path and
    result reassembly run — results must be identical."""
    g = nexmark.q2(parallelism=4, partitioner="weakhash", n_groups=2)
    spec = ChaosSpec(host_kill_prob_per_s=0.004, straggler_frac=0.2)
    a = run_batch(g, range(5), base_spec=spec, duration_s=40, n_hosts=4)
    b = run_batch(g, range(5), base_spec=spec, duration_s=40, n_hosts=4,
                  devices=1)
    np.testing.assert_allclose(a.source_lag, b.source_lag, rtol=1e-12,
                               atol=1e-9)
    np.testing.assert_allclose(a.emitted, b.emitted, rtol=1e-12)
    c = run_batch(g, range(5), base_spec=spec, duration_s=40, n_hosts=4,
                  devices="auto")
    np.testing.assert_allclose(a.source_lag, c.source_lag, rtol=1e-12,
                               atol=1e-9)


# ----------------------------------------------------------------------
# pack_arena API contracts
# ----------------------------------------------------------------------
def test_pack_arena_layout_contracts():
    graphs = _jobs()
    arena = pack_arena(graphs, "shared", n_hosts=8)
    assert arena.n_jobs == 2 and arena.n_hosts == 8
    n0 = sum(o.parallelism for o in graphs[0].ops)
    assert (arena.jobs[0].task_lo, arena.jobs[0].task_hi) == (0, n0)
    assert arena.jobs[1].task_lo == n0
    assert arena.plan.n_tasks == arena.jobs[1].task_hi
    # job op columns partition the topo op axis, names un-namespaced
    cols = np.concatenate([j.op_cols for j in arena.jobs])
    assert sorted(cols) == list(range(len(arena.plan.ops)))
    assert arena.jobs[0].op_names == list(graphs[0].topo_order())
    # disjoint pool is K× larger; shared pool hosts overlap
    dis = pack_arena(graphs, "disjoint", n_hosts=8)
    assert dis.n_hosts == 16
    assert set(dis.jobs[0].hosts) & set(dis.jobs[1].hosts) == set()
    assert set(arena.jobs[0].hosts) == set(arena.jobs[1].hosts)
    # regions never merge across jobs
    for r in arena.phys.regions:
        assert len({arena.job_of_task[t] for t in r}) == 1


def test_pack_arena_rejects_bad_input():
    with pytest.raises(ValueError, match="at least one"):
        pack_arena([])
    with pytest.raises(ValueError, match="rows for"):
        pack_arena(_jobs(), [np.arange(8)], n_hosts=8)
    with pytest.raises(ValueError, match="all local hosts"):
        pack_arena(_jobs(), [np.arange(8), np.arange(4)], n_hosts=8)


def test_single_job_arena_matches_plain_graph():
    """K=1 packing is the identity refactor: same metrics as the plain
    engine construction (bit-level for numpy, 1e-12 for jax)."""
    g = nexmark.q12(parallelism=8)
    spec = ChaosSpec(seed=1, host_kill_prob_per_s=0.004)
    fo = FailoverConfig(mode="region", region_restart_s=15.0)
    arena = pack_arena([g], "shared", n_hosts=8)
    a = StreamEngine(g, n_hosts=8, chaos=ChaosEngine(spec), failover=fo)
    a.run(60)
    b = StreamEngine(arena, chaos=ChaosEngine(spec), failover=fo)
    b.run(60)
    for name in g.topo_order():
        np.testing.assert_allclose(a.metrics.backlog[name],
                                   b.metrics.backlog["j0." + name],
                                   rtol=0, atol=0)
    assert a.metrics.emitted == b.metrics.emitted
    # recovery events differ only by the job tag
    stripped = [dict(r) for r in b.metrics.recoveries]
    for r in stripped:
        r.pop("job")
    assert stripped == a.metrics.recoveries
