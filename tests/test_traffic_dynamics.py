"""Traffic-dynamics chaos: traced diurnal/flash-crowd load, in-trace DS2
autoscaling, and rescale-during-recovery drills.

Pins the traffic contract across all engine lowerings:

* a schedule that evaluates to a constant 1.0 rate factor (zero-amplitude
  diurnal, unit-peak flash) is a bit-exact no-op — rate curves multiply
  emission and must never perturb the draw streams;
* numpy == jax (1e-5) and dense == compact (1e-12) under the
  full `traffic_drill_spec` drill: diurnal + flash crowd + a host burst
  INSIDE the flash hold window + the in-trace DS2 controller rescaling
  while failover recovery is still replaying;
* the thrash guard latches under induced autoscaler oscillation and
  halts further actions; the failover-aware breaker opens under a kill
  storm and degrades gracefully (load shed) instead of rescaling into
  the outage;
* the `traffic_sweep` (scaler × traffic × failover × seed) cube comes
  out of ONE `sweep_configs` call with `timeline_build_count` flat
  (rate schedules and scale events are in-trace only);
* regression pins for the host-side control plane: per-op breaker
  counts and stale-rollback expiry in `DS2Scaler.notify_result`,
  exception chaining in `backoff.retry`, and in-flight await in
  `IdempotencyRegistry.run`.
"""
import dataclasses
import math
import threading

import numpy as np
import pytest

from repro.core.autoscaler import DS2Scaler, OpMetrics, ScalerConfig
from repro.core.backoff import (IdempotencyRegistry, PermanentError,
                                RetryPolicy, TransientError, retry)
from repro.core.chaos import (ChaosEngine, ChaosSpec, timeline_build_count,
                              traffic_curve)
from repro.core.clock import VirtualClock
from repro.streams import nexmark
from repro.streams.chaos_sweep import traffic_sweep
from repro.streams.engine import (AutoscaleConfig, FailoverConfig,
                                  StreamEngine)
from repro.streams.jax_engine import JaxStreamEngine, run_batch

FO = FailoverConfig(mode="region", detect_s=1.0)
DS2 = AutoscaleConfig(interval_s=5.0, cooldown_s=10.0, ewma_alpha=0.35,
                      hysteresis=0.15)


# ----------------------------------------------------------------------
# (a) constant-rate schedule == no schedule, bit-exact
# ----------------------------------------------------------------------
def test_constant_schedule_is_bit_exact_noop():
    """Zero-amplitude diurnal and unit-peak flash entries evaluate to a
    factor of exactly 1.0, so the scheduled run replays the constant-rate
    run draw-for-draw."""
    ts = np.arange(0.0, 60.0, 0.5)
    curve = traffic_curve(((0.0, 240.0, 0.0),), ((10.0, 5.0, 5.0, 1.0),),
                          ts)
    assert np.array_equal(curve, np.ones_like(ts))

    g = nexmark.q3()
    base_spec = ChaosSpec(seed=3, host_kill_prob_per_s=0.002)
    flat_spec = dataclasses.replace(
        base_spec, diurnal=((0.0, 240.0, 0.0),),
        flash_at=((10.0, 5.0, 5.0, 1.0),))
    base = StreamEngine(g, chaos=ChaosEngine(base_spec), failover=FO,
                        queue_cap=1e9).run(60.0)
    flat = StreamEngine(g, chaos=ChaosEngine(flat_spec), failover=FO,
                        queue_cap=1e9).run(60.0)
    assert flat.emitted == base.emitted
    assert flat.dropped == base.dropped
    assert np.array_equal(np.asarray(base.source_lag),
                          np.asarray(flat.source_lag))
    for n in base.backlog:
        assert np.array_equal(np.asarray(base.backlog[n]),
                              np.asarray(flat.backlog[n]))

    j_base = JaxStreamEngine(g, chaos=base_spec, failover=FO,
                             queue_cap=1e9, phase_mode="compact").run(60.0)
    j_flat = JaxStreamEngine(g, chaos=flat_spec, failover=FO,
                             queue_cap=1e9, phase_mode="compact").run(60.0)
    assert np.array_equal(np.asarray(j_base.source_lag),
                          np.asarray(j_flat.source_lag))
    assert j_flat.emitted == j_base.emitted


def test_inert_autoscale_leaves_are_noop():
    """An engine built WITHOUT a scaler carries the inert autoscale
    leaves; they must not perturb the PR-8 drill-era results (speed
    stays 1, no actions, no thrash)."""
    g = nexmark.q3()
    spec = ChaosSpec(seed=7, host_kill_prob_per_s=0.004)
    m = JaxStreamEngine(g, chaos=spec, failover=FO,
                        phase_mode="compact").run(60.0)
    assert m.n_rescale == 0.0
    assert math.isinf(m.thrash_t)
    n_tasks = sum(o.parallelism for o in g.ops)
    assert m.resource_s == pytest.approx(n_tasks * 60.0)


# ----------------------------------------------------------------------
# (b) full drill parity: rescale-during-recovery across lowerings
# ----------------------------------------------------------------------
def _drill():
    """Flash crowd [90, 130]s, host burst at 110s (inside the hold), a
    diurnal swing and background kills: the scaler reacts to the surge
    while failover recovery is still replaying."""
    return nexmark.traffic_drill_spec(seed=5, host_kill_prob_per_s=0.003)


def test_numpy_matches_jax_rescale_during_recovery():
    g = nexmark.q3()
    spec = _drill()
    m_np = StreamEngine(g, chaos=ChaosEngine(spec), failover=FO,
                        autoscale=DS2).run(150.0)
    m_j = JaxStreamEngine(g, chaos=spec, failover=FO, autoscale=DS2,
                          phase_mode="compact").run(150.0)
    assert m_np.n_rescale > 0, "the surge must actually trigger rescales"
    assert m_j.n_rescale == m_np.n_rescale
    assert m_j.resource_s == pytest.approx(m_np.resource_s, rel=1e-9)
    assert m_j.emitted == pytest.approx(m_np.emitted, rel=1e-9)
    np.testing.assert_allclose(np.asarray(m_j.source_lag),
                               np.asarray(m_np.source_lag), atol=1e-5)
    for n in m_np.backlog:
        np.testing.assert_allclose(np.asarray(m_j.backlog[n]),
                                   np.asarray(m_np.backlog[n]), atol=1e-5)


def test_dense_compact_agree_under_drill():
    g = nexmark.q3()
    spec = _drill()
    runs = {}
    for mode in ("dense", "compact"):
        runs[mode] = JaxStreamEngine(g, chaos=spec, failover=FO,
                                     autoscale=DS2,
                                     phase_mode=mode).run(150.0)
    ref = runs["compact"]
    assert ref.n_rescale > 0
    m = runs["dense"]
    assert m.n_rescale == ref.n_rescale
    assert m.thrash_t == ref.thrash_t
    assert m.resource_s == pytest.approx(ref.resource_s, abs=1e-9)
    np.testing.assert_allclose(np.asarray(m.source_lag),
                               np.asarray(ref.source_lag),
                               rtol=0, atol=1e-12)
    for n in ref.backlog:
        np.testing.assert_allclose(np.asarray(m.backlog[n]),
                                   np.asarray(ref.backlog[n]),
                                   rtol=0, atol=1e-12)


def test_autoscaler_tracks_flash_crowd():
    """Under the flash crowd the scaler buys capacity and beats the
    frozen-parallelism run on integrated source lag."""
    g = nexmark.q3()
    spec = nexmark.traffic_drill_spec(seed=5)
    frozen = JaxStreamEngine(g, chaos=spec, failover=FO,
                             phase_mode="compact").run(150.0)
    scaled = JaxStreamEngine(g, chaos=spec, failover=FO, autoscale=DS2,
                             phase_mode="compact").run(150.0)
    assert scaled.n_rescale > 0
    lag_f = np.asarray(frozen.source_lag)
    lag_s = np.asarray(scaled.source_lag)
    assert lag_s.sum() < 0.8 * lag_f.sum(), \
        "scaling into the surge must beat frozen parallelism on lag"


# ----------------------------------------------------------------------
# (c) guards: thrash latch and failover-aware breaker
# ----------------------------------------------------------------------
def test_thrash_guard_latches_and_halts_actions():
    """A fast square-ish load swing with zero cooldown makes the
    controller flip direction every interval; the guard must latch and
    stop the oscillation instead of rescaling forever."""
    g = nexmark.q3()
    spec = ChaosSpec(seed=2, diurnal=((0.9, 12.0, 0.0),))
    osc = AutoscaleConfig(interval_s=3.0, cooldown_s=0.0, hysteresis=0.02,
                          ewma_alpha=0.9, max_actions=1e18)
    guarded = dataclasses.replace(osc, thrash_flips=4.0,
                                  thrash_window_s=60.0)
    free = dataclasses.replace(osc, thrash_flips=1e18)
    m_g = StreamEngine(g, chaos=ChaosEngine(spec), failover=FO,
                       autoscale=guarded).run(120.0)
    m_f = StreamEngine(g, chaos=ChaosEngine(spec), failover=FO,
                       autoscale=free).run(120.0)
    assert math.isfinite(m_g.thrash_t), "thrash guard must latch"
    assert math.isinf(m_f.thrash_t)
    assert m_g.n_rescale < m_f.n_rescale, \
        "after the latch no further actions fire"
    # same latch in the traced lowering
    j_g = JaxStreamEngine(g, chaos=spec, failover=FO, autoscale=guarded,
                          phase_mode="compact").run(120.0)
    assert math.isfinite(j_g.thrash_t)
    assert j_g.thrash_t == pytest.approx(m_g.thrash_t)
    assert j_g.n_rescale == m_g.n_rescale


def test_breaker_opens_under_kill_storm_and_sheds():
    """Failovers landing right after scale actions trip the traced
    breaker: actions stop and the fleet degrades gracefully by shedding
    load instead of rescaling into the outage."""
    g = nexmark.q3()
    # a fast load swing keeps the controller acting every interval, and
    # the host kills land inside fail_window_s of those actions
    spec = ChaosSpec(seed=4, host_kill_at=((20.0, 0), (22.0, 1), (24.0, 2)),
                     diurnal=((0.9, 12.0, 0.0),))
    hot = AutoscaleConfig(interval_s=3.0, cooldown_s=0.0, hysteresis=0.02,
                          ewma_alpha=0.9, max_actions=1e18,
                          thrash_flips=1e18,
                          breaker_failures=2.0, breaker_reset_s=300.0,
                          fail_window_s=30.0, shed_factor=0.5)
    off = dataclasses.replace(hot, breaker_failures=1e18)
    m_b = StreamEngine(g, chaos=ChaosEngine(spec), failover=FO,
                       autoscale=hot).run(120.0)
    m_o = StreamEngine(g, chaos=ChaosEngine(spec), failover=FO,
                       autoscale=off).run(120.0)
    assert m_b.n_rescale < 0.5 * m_o.n_rescale, \
        "an open breaker must block further scale actions"
    # shed shows up as less work flowing downstream: same breaker
    # trajectory, shed 0.5 vs 1.0, some op's backlog must bend
    noshed = dataclasses.replace(hot, shed_factor=1.0)
    m_n = StreamEngine(g, chaos=ChaosEngine(spec), failover=FO,
                       autoscale=noshed).run(120.0)
    assert any(not np.array_equal(np.asarray(m_b.backlog[n]),
                                  np.asarray(m_n.backlog[n]))
               for n in m_b.backlog), \
        "load shed must actually bend the pipeline"
    # traced parity under the breaker drill
    j_b = JaxStreamEngine(g, chaos=spec, failover=FO, autoscale=hot,
                          phase_mode="compact").run(120.0)
    assert j_b.n_rescale == m_b.n_rescale
    np.testing.assert_allclose(np.asarray(j_b.source_lag),
                               np.asarray(m_b.source_lag), atol=1e-5)


# ----------------------------------------------------------------------
# (d) the traffic cube: ONE sweep_configs call, flat timeline builds
# ----------------------------------------------------------------------
def test_traffic_cube_flat_builds():
    g = nexmark.q3()
    seeds = [1, 2]
    before = timeline_build_count()
    tw = traffic_sweep(
        g, seeds, base_spec=ChaosSpec(seed=0, host_kill_prob_per_s=0.002),
        duration_s=60.0,
        scalers={"off": None, "ds2": DS2},
        traffics={"base": ((), ()),
                  "surge": {"flash": ((20.0, 5.0, 15.0, 2.0),)}},
        failovers={"region": FO,
                   "single": FailoverConfig(mode="single_task")})
    builds = timeline_build_count() - before
    assert builds == len(seeds), \
        "rate schedules and scale events are in-trace only: one " \
        "timeline per seed, flat across all 8 cube config rows"
    assert tw.recovery.shape == (2, 2, 2, len(seeds))
    assert tw.cost.shape == (2, 2, 2, len(seeds))
    assert (tw.rescales[0] == 0).all(), "no-scaler rows never rescale"
    assert (tw.rescales[1] > 0).any(), "the DS2 rows must act"
    # the no-scaler resource bill is exactly flat speed × tasks × time
    n_tasks = sum(o.parallelism for o in g.ops)
    assert np.allclose(tw.cost[0], n_tasks * 60.0)
    assert any("ds2" in lbl for lbl in tw.grid.labels)
    assert any("surge" in lbl for lbl in tw.grid.labels)


def test_run_batch_carries_autoscale_metrics():
    g = nexmark.q3()
    specs = [_drill(), dataclasses.replace(_drill(), seed=9)]
    batch = run_batch(g, specs, duration_s=150.0, failover=FO,
                      autoscale=DS2, phase_mode="compact")
    assert batch.n_rescale.shape == (2,)
    assert (batch.n_rescale > 0).all()
    assert (batch.resource_s > 0).all()
    single = JaxStreamEngine(g, chaos=specs[0], failover=FO,
                             autoscale=DS2, phase_mode="compact").run(150.0)
    assert batch.n_rescale[0] == single.n_rescale
    np.testing.assert_allclose(batch.source_lag[0],
                               np.asarray(single.source_lag),
                               rtol=0, atol=1e-12)


# ----------------------------------------------------------------------
# (e) control-plane regressions: DS2Scaler, backoff, idempotency
# ----------------------------------------------------------------------
def test_scaler_breaker_counts_failures_per_op():
    """A healthy op's successful resize must not mask a flapping op: the
    breaker counts consecutive failures PER OP."""
    cfg = ScalerConfig(cooldown_s=0, ewma_alpha=1.0, breaker_failures=2)
    sc = DS2Scaler(cfg)
    sc.notify_result("flappy", 1.0, success=False)
    sc.notify_result("healthy", 2.0, success=True)   # must NOT reset
    sc.notify_result("flappy", 3.0, success=False)
    m = [OpMetrics("flappy", 50_000, 600_000, 6_000, 10)]
    assert sc.observe(4.0, m) == [], \
        "two flappy failures trip the breaker despite the healthy success"


def test_scaler_stale_pending_rollback_expires():
    """A resize that aged past cooldown_s without a reported failure is
    settled; a later unrelated failure must not roll back to it."""
    cfg = ScalerConfig(cooldown_s=10.0, ewma_alpha=1.0,
                       breaker_failures=100)
    sc = DS2Scaler(cfg)
    d = sc.observe(0.0, [OpMetrics("op", 50_000, 600_000, 6_000, 10)])
    assert d, "the resize must be proposed"
    rb = sc.notify_result("op", 100.0, success=False)
    assert rb is None, \
        "an anchor older than cooldown_s must not produce a rollback"
    # a fresh resize still rolls back on prompt failure
    d2 = sc.observe(101.0, [OpMetrics("op", 90_000, 600_000, 6_000,
                                      d[0].new)])
    assert d2
    rb2 = sc.notify_result("op", 102.0, success=False)
    assert rb2 is not None and rb2.new == d[0].new


def test_retry_chains_the_last_transient():
    clock = VirtualClock()
    boom = TransientError("dependency down")
    with pytest.raises(PermanentError) as ei:
        retry(lambda: (_ for _ in ()).throw(boom),
              RetryPolicy(base_delay_s=0.01, max_attempts=3), clock)
    assert ei.value.__cause__ is boom, \
        "retry must chain the last TransientError for diagnosis"


def test_idempotency_awaits_inflight_token():
    """A duplicate submission arriving while the first is still
    executing must await it and return its result — not re-execute."""
    reg = IdempotencyRegistry()
    started = threading.Event()
    release = threading.Event()
    calls = {"n": 0}

    def slow():
        calls["n"] += 1
        started.set()
        assert release.wait(5.0)
        return "done"

    first = {}

    def runner():
        first["out"] = reg.run("tok", slow)

    th = threading.Thread(target=runner)
    th.start()
    assert started.wait(5.0)
    dup = {}

    def dup_runner():
        dup["out"] = reg.run("tok", slow)

    td = threading.Thread(target=dup_runner)
    td.start()
    release.set()
    th.join(5.0)
    td.join(5.0)
    assert calls["n"] == 1, "the in-flight token must not re-execute"
    assert first["out"] == ("done", False)
    assert dup["out"] == ("done", True)


def test_idempotency_failed_inflight_hands_over_to_waiter():
    """If the first execution raises, the waiter takes over the retry —
    the failed attempt produced no effect to deduplicate against."""
    reg = IdempotencyRegistry()
    started = threading.Event()
    release = threading.Event()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            started.set()
            assert release.wait(5.0)
            raise TransientError("first attempt dies mid-flight")
        return "second"

    err = {}

    def runner():
        try:
            reg.run("tok", flaky)
        except TransientError as e:
            err["e"] = e

    th = threading.Thread(target=runner)
    th.start()
    assert started.wait(5.0)
    out = {}

    def waiter():
        out["r"] = reg.run("tok", flaky)

    tw = threading.Thread(target=waiter)
    tw.start()
    release.set()
    th.join(5.0)
    tw.join(5.0)
    assert "e" in err, "the first caller sees the failure"
    assert out["r"] == ("second", False), \
        "the waiter re-executes after the in-flight failure"
    assert calls["n"] == 2
