"""Recovery-correctness invariants for the external-system chaos layer:
hot-standby vs passive replication, storage brownouts, MQ outage gates
and region bursts — property tests pinned numpy-vs-jax against the
frozen `reference_engine.py` oracle, plus the FallbackStorage /
LeaderService outage drill."""
from __future__ import annotations

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.core.chaos import (ChaosEngine, ChaosSpec, brownout_curve,
                              brownout_factor_at, ckpt_age_curve,
                              timeline_build_count)
from repro.streams import nexmark
from repro.streams.engine import (CheckpointConfig, FailoverConfig,
                                  StreamEngine)
from repro.streams.jax_engine import JaxStreamEngine, run_config_batch
from repro.streams.reference_engine import ReferenceStreamEngine


def _drill_spec(seed: int, peak: float = 6.0) -> ChaosSpec:
    return nexmark.ha_drill_spec(seed=seed, burst_t=20.0,
                                 brownout=(10.0, 50.0, peak),
                                 mq_outage=(55.0, 62.0))


def _run_all(g, spec, fo, ck, duration=90.0, n_hosts=6):
    ref = ReferenceStreamEngine(g, chaos=ChaosEngine(spec), failover=fo,
                                ckpt=ck, n_hosts=n_hosts)
    mr = ref.run(duration)
    eng = StreamEngine(g, chaos=ChaosEngine(spec), failover=fo, ckpt=ck,
                       n_hosts=n_hosts)
    me = eng.run(duration)
    rows = {}
    for pm in ("dense", "compact"):
        jx = JaxStreamEngine(g, chaos=spec, failover=fo, ckpt=ck,
                             n_hosts=n_hosts, phase_mode=pm)
        rows[pm] = jx.run(duration)
    return mr, me, rows


# ----------------------------------------------------------------------
# cross-engine parity under external-system chaos
# ----------------------------------------------------------------------
@pytest.mark.parametrize("mode,kw", [
    ("hot_standby", {}),
    ("region", dict(restore_base_s=2.0, replay_rate=0.5,
                    lazyload_stagger_s=0.3)),
    ("single_task", dict(restore_base_s=1.0, replay_rate=1.0)),
])
def test_external_chaos_parity_vs_reference(mode, kw):
    g = nexmark.q12(parallelism=4)
    fo = FailoverConfig(mode=mode, **kw)
    ck = CheckpointConfig(interval_s=8.0, upload_s=2.0)
    mr, me, rows = _run_all(g, _drill_spec(3), fo, ck)
    ref_lag = np.asarray(mr.source_lag)
    scale = max(1.0, float(np.abs(ref_lag).max()))
    assert np.max(np.abs(np.asarray(me.source_lag) - ref_lag)) \
        <= 1e-5 * scale
    assert me.recoveries == mr.recoveries
    assert (mr.ckpt_attempts, mr.ckpt_success) == \
        (me.ckpt_attempts, me.ckpt_success)
    for pm, mj in rows.items():
        assert np.max(np.abs(np.asarray(mj.source_lag) - ref_lag)) \
            <= 1e-5 * scale, pm
    # dense == compact bit-for-bit
    d, c = rows["dense"], rows["compact"]
    np.testing.assert_array_equal(np.asarray(d.source_lag),
                                  np.asarray(c.source_lag))
    for op in d.qps:
        np.testing.assert_allclose(np.asarray(d.qps[op]),
                                   np.asarray(c.qps[op]), rtol=1e-12)


# ----------------------------------------------------------------------
# invariant: hot standby never loses emitted records vs passive
# ----------------------------------------------------------------------
@settings(max_examples=12, deadline=None)
@given(st.integers(0, 40), st.floats(1.5, 10.0))
def test_hot_standby_never_loses_records(seed, peak):
    """Single-task passive recovery drops records routed to dead tasks
    (γ=partial); a hot standby assumes execution instead — same chaos
    draws must never show MORE drops (and never fewer emits) under
    hot_standby."""
    g = nexmark.q2(parallelism=4)
    spec = _drill_spec(seed, peak)
    hot = StreamEngine(g, chaos=ChaosEngine(spec),
                       failover=FailoverConfig(mode="hot_standby"),
                       n_hosts=6).run(60.0)
    passive = StreamEngine(g, chaos=ChaosEngine(spec),
                           failover=FailoverConfig(
                               mode="single_task", restore_base_s=2.0,
                               replay_rate=1.0),
                           n_hosts=6).run(60.0)
    assert hot.dropped == 0.0
    assert hot.dropped <= passive.dropped
    assert hot.emitted >= passive.emitted - 1e-9


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 40))
def test_hot_standby_downtime_independent_of_ckpt_age(seed):
    """Hot-standby recovery cost is switch + staleness only — recovery
    entries must not grow with checkpoint age or brownout severity."""
    g = nexmark.q2(parallelism=4)
    fo = FailoverConfig(mode="hot_standby", detect_s=0.5,
                        standby_switch_s=0.05, standby_staleness_s=0.5)
    for peak in (1.0, 8.0):
        spec = _drill_spec(seed, peak)
        m = StreamEngine(g, chaos=ChaosEngine(spec), failover=fo,
                         n_hosts=6).run(60.0)
        for r in m.recoveries:
            assert r["mode"] == "hot_standby"
            assert r["downtime"] == pytest.approx(0.5 + 0.05 + 0.5)


# ----------------------------------------------------------------------
# invariant: brownout-stretched checkpoints never ack early
# ----------------------------------------------------------------------
@settings(max_examples=12, deadline=None)
@given(st.integers(0, 30), st.floats(2.0, 12.0))
def test_brownout_checkpoints_never_ack_early(seed, peak):
    """A brownout multiplies every upload duration, so an attempt that
    succeeds UNDER the brownout must also succeed without it (with the
    same rng draws), and success counts are monotone non-increasing in
    brownout severity."""
    g = nexmark.q2(parallelism=3)
    ck = CheckpointConfig(interval_s=6.0, upload_s=2.0,
                          retry_failed_region=False)
    base = ChaosSpec(seed=seed, storage_slow_prob=0.3,
                     storage_slow_factor=2.5)
    import dataclasses as dc
    succ, attempts = [], []
    for p in (1.0, peak, 2.0 * peak):
        spec = dc.replace(base, brownout_at=(
            () if p == 1.0 else ((0.0, 1e9, p),)))
        m = StreamEngine(g, chaos=ChaosEngine(spec), ckpt=ck,
                         n_hosts=4).run(60.0)
        succ.append(m.ckpt_success)
        attempts.append(m.ckpt_attempts)
    # the attempt schedule is brownout-independent; only success is
    assert attempts[0] == attempts[1] == attempts[2]
    assert succ[0] >= succ[1] >= succ[2]


def test_brownout_curve_matches_scalar_factor():
    ramps = ((5.0, 15.0, 4.0), (10.0, 30.0, 2.0))
    ts = np.linspace(0.0, 35.0, 141)
    curve = brownout_curve(ramps, ts)
    for i, t in enumerate(ts):
        assert curve[i] == brownout_factor_at(ramps, float(t))
    # outside every ramp the factor is exactly 1 (bit-identity contract)
    assert brownout_factor_at(ramps, 35.0) == 1.0


def test_ckpt_age_curve_is_tick_exclusive():
    ts = np.array([0.0, 1.0, 2.0, 3.0])
    ok = np.array([0, 1, 0, 0], np.int16)
    age = ckpt_age_curve(ts, ok, 1)[:, 0]
    # success at tick 1 only lowers the age from tick 2 on
    np.testing.assert_allclose(age, [0.0, 1.0, 1.0, 2.0])


# ----------------------------------------------------------------------
# MQ outage gate: sources emit nothing inside the window
# ----------------------------------------------------------------------
def test_mq_outage_gates_sources_across_engines():
    g = nexmark.q2(parallelism=4)
    spec = ChaosSpec(seed=1, mq_down=((10.0, 20.0),))
    mr = ReferenceStreamEngine(g, chaos=ChaosEngine(spec),
                               n_hosts=4).run(40.0)
    me = StreamEngine(g, chaos=ChaosEngine(spec), n_hosts=4).run(40.0)
    mj = JaxStreamEngine(g, chaos=spec, n_hosts=4,
                         phase_mode="compact").run(40.0)
    no = StreamEngine(g, chaos=ChaosEngine(ChaosSpec(seed=1)),
                      n_hosts=4).run(40.0)
    # 10s of a 40s run gated → emitted drops by exactly that share
    assert me.emitted == pytest.approx(no.emitted * 0.75)
    assert mr.emitted == pytest.approx(me.emitted)
    assert float(np.sum(np.asarray(mj.emitted))) == \
        pytest.approx(me.emitted, rel=1e-9)


def test_region_burst_downs_all_region_hosts():
    g = nexmark.q12(parallelism=4)
    spec = ChaosSpec(seed=2, burst_at=((15.0, 0),))
    fo = FailoverConfig(mode="region")
    me = StreamEngine(g, chaos=ChaosEngine(spec), failover=fo,
                      n_hosts=6).run(40.0)
    assert me.recoveries, "burst must trigger at least one recovery"
    assert all(abs(r["t"] - 15.0) <= 0.5 for r in me.recoveries)
    mj = JaxStreamEngine(g, chaos=spec, failover=fo, n_hosts=6,
                         phase_mode="dense").run(40.0)
    np.testing.assert_allclose(np.asarray(mj.source_lag),
                               np.asarray(me.source_lag), atol=1e-6)


# ----------------------------------------------------------------------
# grid path: config-axis brownouts stay bit-identical to rebuilds and
# timeline_build_count stays flat
# ----------------------------------------------------------------------
def test_config_grid_brownout_matches_rebuild():
    g = nexmark.q2(parallelism=4)
    base = ChaosSpec(seed=7, host_kill_prob_per_s=0.004,
                     storage_slow_prob=0.2, storage_slow_factor=2.0)
    fo = FailoverConfig(mode="region", restore_base_s=2.0,
                        replay_rate=1.0)
    ck = CheckpointConfig(interval_s=8.0, upload_s=2.0)
    bro = ((0.0, 1e9, 5.0),)
    c0 = timeline_build_count()
    rows = run_config_batch(
        g, [{"failover": fo, "ckpt": ck},
            {"failover": fo, "ckpt": ck, "brownout": bro}],
        range(3), base_spec=base, duration_s=60.0, n_hosts=6,
        phase_mode="compact")
    assert timeline_build_count() == c0  # grid refit, zero full rebuilds
    import dataclasses as dc
    heavy = dc.replace(base, brownout_at=bro, seed=base.seed)
    for s in range(3):
        spec = dc.replace(heavy, seed=s)
        jx = JaxStreamEngine(g, chaos=spec, failover=fo, ckpt=ck,
                             n_hosts=6, phase_mode="compact")
        m = jx.run(60.0)
        np.testing.assert_array_equal(
            np.asarray(rows[1].source_lag[s]), np.asarray(m.source_lag))


def test_lazyload_stagger_orders_region_ready_times():
    """Lazy-load restore: a task blocks only until its OWN region is
    restored — later regions pay a strictly larger surcharge."""
    # ds() is forward chains → one region per chain, so region ranks
    # actually differ within the job (q2/q12 all-to-all = one region)
    g = nexmark.ds(parallelism=4)
    spec = ChaosSpec(seed=4, burst_at=((15.0, 1),))
    fo = FailoverConfig(mode="region", lazyload_stagger_s=1.5)
    me = StreamEngine(g, chaos=ChaosEngine(spec), failover=fo,
                      n_hosts=6).run(40.0)
    mj = JaxStreamEngine(g, chaos=spec, failover=fo, n_hosts=6,
                         phase_mode="compact").run(40.0)
    np.testing.assert_allclose(np.asarray(mj.source_lag),
                               np.asarray(me.source_lag), atol=1e-6)
    # per-task ready times inside the engine are staggered by region rank
    eng = StreamEngine(g, chaos=ChaosEngine(spec), failover=fo, n_hosts=6)
    assert float(eng._lazy_extra.max()) > 0.0
    assert float(eng._lazy_extra.min()) == 0.0


# ----------------------------------------------------------------------
# FallbackStorage + LeaderService outage drill
# ----------------------------------------------------------------------
def test_storage_and_leader_outage_drill():
    """The paper's HA drill: HDFS namenode goes dark mid-run — puts land
    on the fallback store, reads fall back, and the leader service keeps
    answering from its HDFS-fallback path without terminating jobs."""
    import tempfile

    from repro.core.backoff import RetryPolicy
    from repro.core.clock import VirtualClock
    from repro.core.ha import LeaderService, ZooKeeperSim
    from repro.ckpt.storage import FallbackStorage, ObjectStoreSim, SimHDFS

    clock = VirtualClock()
    root = tempfile.mkdtemp(prefix="ha_drill_")
    primary = SimHDFS(root + "/primary", clock=clock)
    fallback = ObjectStoreSim(root + "/fallback", clock=clock)
    store = FallbackStorage(primary, fallback, clock=clock,
                            policy=RetryPolicy(base_delay_s=0.01,
                                               max_attempts=2))
    store.put("pre", b"pre-outage")
    primary.available = False          # namenode outage
    store.put("during", b"written-during-outage")
    assert store.fallback_puts == 1
    assert store.get("during") == b"written-during-outage"
    primary.available = True           # namenode back
    assert store.get("pre") == b"pre-outage"

    # leader metadata: ZK quorum lost mid-window → HDFS fallback read,
    # no job termination (the paper's dual-store HA semantics)
    zk = ZooKeeperSim(clock=clock,
                      chaos=ChaosEngine(ChaosSpec(
                          zk_down=((clock.now() + 1.0,
                                    clock.now() + 100.0),))))
    svc = LeaderService(zk, store, clock=clock)
    svc.elect("jm-host-7")
    clock.sleep(5.0)                   # step into the outage window
    rec = svc.get_leader()
    assert rec.leader_id == "jm-host-7"
    assert svc.fallback_reads == 1
    assert svc.terminations == 0


@pytest.mark.parametrize("extra_growth, recovered", [(0.0, True),
                                                     (1e-6, False)])
def test_recovery_time_ignores_f64_rounding_of_large_lags(extra_growth,
                                                          recovered):
    """A 10k-task fleet retains ~3e9 records of source lag, growing by a
    steady ~1.8e7 per tick. Rounding at the 3e-13 relative level (the
    spread between a TPU's emulated f64 and the CPU's f64) must not read
    as lag growth after a 1 s hot-standby failover, while growth one
    part in a million above the pre-failure rate still does."""
    from repro.streams.chaos_sweep import _recovery_time

    rng = np.random.default_rng(0)
    ts = np.arange(360) * 0.5
    growth = np.where(ts < 60.0, 1.8e7, 1.8e7 * (1.0 + extra_growth))
    lag = 1.4e9 + np.cumsum(growth)
    lag *= 1.0 + rng.uniform(-3e-13, 3e-13, lag.shape)
    recs = [{"t": 60.0, "downtime": 1.05}]
    rt = _recovery_time(ts, lag, np.zeros_like(ts), recs)
    assert np.isfinite(rt) == recovered
    if recovered:
        assert rt == 1.5
