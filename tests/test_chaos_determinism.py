"""Chaos determinism regression pins (paper §V-B: every drill must be
reproducible bit-for-bit) plus the pregenerated-event-tensor contract:
`build_chaos_timeline` must consume the chaos rng stream draw-for-draw
as the live engine does, so a timeline is interchangeable with
sequential draws."""
import numpy as np
import pytest

from repro.core.chaos import (ChaosEngine, ChaosSpec, build_chaos_timeline,
                              failover_recovery_entries)
from repro.streams import nexmark
from repro.streams.engine import (CheckpointConfig, FailoverConfig,
                                  StreamEngine)


def test_chaos_engine_streams_are_deterministic():
    spec = ChaosSpec(seed=3, host_kill_prob_per_s=0.01,
                     storage_slow_prob=0.3, storage_slow_factor=8.0,
                     straggler_frac=0.25)
    a, b = ChaosEngine(spec), ChaosEngine(spec)
    for h in range(8):
        assert a.host_speed(h) == b.host_speed(h)
    for i in range(200):
        t0, t1 = i * 0.5, (i + 1) * 0.5
        ka = a.step_kills(t0, t1, n_hosts=8)
        kb = b.step_kills(t0, t1, n_hosts=8)
        assert ka == kb
        for h in ka:
            a.revive(h)
            b.revive(h)
        np.testing.assert_array_equal(a.storage_latency_factors(16),
                                      b.storage_latency_factors(16))


def test_pregenerated_kill_tensor_matches_sequential_draws():
    spec = ChaosSpec(seed=11, host_kill_prob_per_s=0.02)
    n_ticks, dt, n_hosts = 400, 0.5, 8
    task_host = np.arange(16) % n_hosts
    tl = build_chaos_timeline(
        spec, n_ticks=n_ticks, dt=dt, n_hosts=n_hosts,
        task_host=task_host, task_region=np.zeros(16, int),
        regions=[set(range(16))], failover_mode="region")
    assert tl.kills.any()
    eng = ChaosEngine(spec)
    t = 0.0
    for i in range(n_ticks):
        kills = eng.step_kills(t, t + dt, n_hosts=n_hosts)
        assert np.nonzero(tl.kills[i])[0].tolist() == kills, i
        for h in kills:
            eng.revive(h)
        t += dt


def test_timeline_rejects_desynchronizing_defaults():
    """Configurations that would silently diverge from the live engine's
    rng consumption (or crash mid-replay) must fail fast."""
    import pytest
    spec = ChaosSpec(seed=0, host_kill_prob_per_s=0.05)
    with pytest.raises(ValueError, match="task_region"):
        build_chaos_timeline(spec, n_ticks=10, dt=0.5, n_hosts=4,
                             task_host=np.arange(8) % 4,
                             failover_mode="region")
    with pytest.raises(ValueError, match="regions"):
        build_chaos_timeline(ChaosSpec(seed=0), n_ticks=10, dt=0.5,
                             n_hosts=4, task_host=np.arange(8) % 4,
                             failover_mode="none", ckpt_interval_s=2.0)


def test_timeline_is_reproducible():
    spec = ChaosSpec(seed=4, host_kill_prob_per_s=0.01,
                     storage_slow_prob=0.2, straggler_frac=0.3)
    kw = dict(n_ticks=300, dt=0.5, n_hosts=6,
              task_host=np.arange(12) % 6,
              task_region=np.arange(12) % 3,
              regions=[set(range(0, 4)), set(range(4, 8)),
                       set(range(8, 12))],
              failover_mode="region", ckpt_interval_s=30.0)
    a = build_chaos_timeline(spec, **kw)
    b = build_chaos_timeline(spec, **kw)
    np.testing.assert_array_equal(a.kills, b.kills)
    np.testing.assert_array_equal(a.task_speed, b.task_speed)
    np.testing.assert_array_equal(a.ckpt_ok, b.ckpt_ok)
    assert a.recoveries == b.recoveries


def test_timeline_matches_live_engine_run():
    """Integration pin: the pregenerated timeline reproduces the live
    numpy engine's straggler speeds, recovery events and checkpoint
    outcomes — interleaved kill + storage draws included."""
    spec = ChaosSpec(seed=5, host_kill_prob_per_s=0.002,
                     straggler_frac=0.25, storage_slow_prob=0.2,
                     storage_slow_factor=12)
    fo = FailoverConfig(mode="region", region_restart_s=15.0)
    ck = CheckpointConfig(interval_s=40, mode="region")
    eng = StreamEngine(nexmark.ds(parallelism=6), n_hosts=6,
                       chaos=ChaosEngine(spec), failover=fo, ckpt=ck)
    m = eng.run(500)
    tl = build_chaos_timeline(
        spec, n_ticks=1000, dt=eng.dt, n_hosts=eng._n_hosts,
        task_host=eng._task_host, task_region=eng._task_region,
        regions=eng.phys.regions, failover_mode=fo.mode,
        detect_s=fo.detect_s, region_restart_s=fo.region_restart_s,
        single_restart_s=fo.single_restart_s,
        ckpt_interval_s=ck.interval_s, ckpt_mode=ck.mode,
        ckpt_upload_s=ck.upload_s, ckpt_retry=ck.retry_failed_region)
    np.testing.assert_array_equal(tl.task_speed, eng._speed)
    assert tl.recoveries == m.recoveries
    assert len(tl.recoveries) > 0
    assert (tl.ckpt_attempts, tl.ckpt_success, tl.ckpt_failed) == \
        (m.ckpt_attempts, m.ckpt_success, m.ckpt_failed)
    np.testing.assert_array_equal(tl.ts, np.array(m.t))


@pytest.mark.parametrize("per_task", [False, True])
def test_recovery_entries_match_per_job_loop(per_task):
    """The one-pass grouping of a kill's hit tasks into per-job entries
    equals the plain loop over jobs: one entry per hit job, ascending,
    counting its hit tasks, with its first hit task's downtime."""
    rng = np.random.default_rng(0)
    job_of_task = rng.integers(0, 40, 600)
    for _ in range(20):
        hit = rng.random(600) < 0.1
        down = rng.uniform(1.0, 9.0, 600) if per_task else 4.5
        ref = [{"t": 7.5, "mode": "region",
                "tasks": int((hit & (job_of_task == j)).sum()),
                "downtime": float(np.asarray(down, float)[
                    hit & (job_of_task == j)][0]) if per_task
                else float(down),
                "job": int(j)}
               for j in np.unique(job_of_task[hit])]
        assert failover_recovery_entries(7.5, "region", hit, down,
                                         job_of_task) == ref
