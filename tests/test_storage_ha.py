"""Storage + external-dependency HA: backoff, idempotency, fallback stores,
ZK→HDFS leader fallback, termination on double failure (paper §IV-B)."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.ckpt.storage import (FallbackStorage, LocalFS, ObjectStoreSim,
                                SimHDFS, StorageUnavailable)
from repro.core.backoff import (IdempotencyRegistry, PermanentError,
                                RetryPolicy, TransientError, retry)
from repro.core.chaos import ChaosEngine, ChaosSpec
from repro.core.clock import VirtualClock
from repro.core.ha import JobTerminated, LeaderService, ZooKeeperSim


def test_retry_succeeds_after_transients():
    clock = VirtualClock()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientError("boom")
        return "ok"

    out, stats = retry(flaky, RetryPolicy(base_delay_s=0.1), clock)
    assert out == "ok" and stats.attempts == 3
    assert clock.now() > 0, "backoff must consume (virtual) time"


def test_retry_gives_up_and_delays_grow():
    clock = VirtualClock()
    policy = RetryPolicy(base_delay_s=1.0, multiplier=2.0, max_attempts=4,
                         jitter=0.0)
    with pytest.raises(PermanentError):
        retry(lambda: (_ for _ in ()).throw(TransientError("x")), policy,
              clock)
    # 1 + 2 + 4 (no delay after final attempt)
    assert clock.now() == pytest.approx(7.0)


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2 ** 31), st.integers(1, 5))
def test_idempotency_registry(job, repeats):
    reg = IdempotencyRegistry()
    calls = {"n": 0}

    def submit():
        calls["n"] += 1
        return f"exec-{job}"

    token = IdempotencyRegistry.token("job", job)
    results = [reg.run(token, submit) for _ in range(repeats)]
    assert calls["n"] == 1, "duplicate submissions must not re-execute"
    assert all(r[0] == f"exec-{job}" for r in results)
    assert [r[1] for r in results] == [False] + [True] * (repeats - 1)


def test_fallback_storage_survives_primary_outage(tmp_path):
    clock = VirtualClock()
    primary = SimHDFS(tmp_path / "p", clock=clock,
                      chaos=ChaosEngine(ChaosSpec(seed=1,
                                                  storage_fail_prob=1.0)))
    fallback = ObjectStoreSim(tmp_path / "f", clock=clock)
    fs = FallbackStorage(primary, fallback, clock=clock,
                         policy=RetryPolicy(base_delay_s=0.01,
                                            max_attempts=2))
    fs.put("k", b"data")
    assert fs.fallback_puts == 1
    assert fs.get("k") == b"data"


def test_atomic_writes_idempotent(tmp_path):
    fs = LocalFS(tmp_path)
    fs.put("a/b", b"v1")
    fs.put("a/b", b"v1")  # retried write is a no-op effectswise
    assert fs.get("a/b") == b"v1"
    assert fs.list() == ["a/b"]


def test_leader_fallback_chain(tmp_path):
    clock = VirtualClock()
    chaos = ChaosEngine(ChaosSpec(zk_down=((10.0, 100.0),)))
    zk = ZooKeeperSim(clock=clock, chaos=chaos)
    hdfs = LocalFS(tmp_path)
    svc = LeaderService(zk, hdfs, clock=clock)
    svc.elect("jm-0")
    assert svc.get_leader().leader_id == "jm-0"
    clock.sleep(20)  # ZK now down
    assert svc.get_leader().leader_id == "jm-0"
    assert svc.fallback_reads == 1, "must fall back to the HDFS copy"


def test_leader_double_failure_terminates(tmp_path):
    clock = VirtualClock()
    chaos = ChaosEngine(ChaosSpec(zk_down=((0.0, 100.0),)))
    zk = ZooKeeperSim(clock=clock, chaos=chaos)

    class DeadStore:
        def get(self, k):
            raise KeyError(k)

        def put(self, k, v):
            raise StorageUnavailable("down")

    svc = LeaderService(zk, DeadStore(), clock=clock)
    with pytest.raises(JobTerminated):
        svc.get_leader()
    assert svc.terminations == 1


def test_leader_inconsistency_terminates(tmp_path):
    clock = VirtualClock()
    chaos = ChaosEngine(ChaosSpec(zk_down=((5.0, 100.0),)))
    zk = ZooKeeperSim(clock=clock, chaos=chaos)
    hdfs = LocalFS(tmp_path)
    svc = LeaderService(zk, hdfs, clock=clock)
    svc.elect("jm-0")
    # HDFS copy tampered / stale while ZK is down → terminate for correctness
    from repro.core.ha import LeaderRecord
    hdfs.put("ha/leader", LeaderRecord("jm-9", 42).to_bytes())
    clock.sleep(10)
    with pytest.raises(JobTerminated):
        svc.get_leader()


def test_missing_leader_on_healthy_zk_is_not_an_outage(tmp_path):
    # regression: a missing leader key on a HEALTHY ZK used to be caught
    # together with ZKUnavailable and silently served from the HDFS copy,
    # inflating fallback_reads.
    from repro.core.ha import LeaderRecord, NoLeader

    clock = VirtualClock()
    zk = ZooKeeperSim(clock=clock, chaos=ChaosEngine())
    hdfs = LocalFS(tmp_path)
    hdfs.put("ha/leader", LeaderRecord("stale-jm", 7).to_bytes())
    svc = LeaderService(zk, hdfs, clock=clock)
    with pytest.raises(NoLeader):
        svc.get_leader()
    assert svc.fallback_reads == 0, ("no-leader on healthy ZK must not be "
                                     "served from the HDFS copy")


def test_programming_error_in_fallback_is_not_a_double_outage(tmp_path):
    # regression: the bare `except Exception` around the HDFS fallback
    # turned programming errors into JobTerminated "double outages".
    clock = VirtualClock()
    chaos = ChaosEngine(ChaosSpec(zk_down=((0.0, 100.0),)))
    zk = ZooKeeperSim(clock=clock, chaos=chaos)

    class BuggyStore:
        def get(self, k):
            raise ZeroDivisionError("bug in the fallback path")

    svc = LeaderService(zk, BuggyStore(), clock=clock)
    with pytest.raises(ZeroDivisionError):
        svc.get_leader()
    assert svc.terminations == 0


def test_simhdfs_slow_reads_not_counted_as_slow_puts(tmp_path):
    # regression: _charge incremented slow_puts from get() too.
    clock = VirtualClock()
    chaos = ChaosEngine(ChaosSpec(seed=0, storage_slow_prob=1.0,
                                  storage_slow_factor=10.0))
    s = SimHDFS(tmp_path, clock=clock, chaos=chaos, bandwidth_bps=1e6,
                base_latency_s=0.0)
    s.put("k", b"x" * 1000)
    s.get("k")
    assert s.slow_puts == 1, "a slow GET must not count as a slow upload"
    assert s.slow_gets == 1


def test_simhdfs_charges_time(tmp_path):
    clock = VirtualClock()
    chaos = ChaosEngine(ChaosSpec(seed=0, storage_slow_prob=1.0,
                                  storage_slow_factor=10.0))
    s = SimHDFS(tmp_path, clock=clock, chaos=chaos, bandwidth_bps=1e6,
                base_latency_s=0.0)
    s.put("k", b"x" * 1_000_000)
    assert clock.now() == pytest.approx(10.0), "slow factor must apply"
    assert s.slow_puts == 1


def test_simhdfs_brownout_scales_upload_queueing(tmp_path):
    """Concurrent uploads queue on the single upload pipeline, and the
    queueing delay scales with `brownout_factor_at`: a brownout does not
    just stretch each op independently, it backs up the whole queue
    (regression: arrival-time queueing was not modeled — the virtual
    clock's blocking sleeps made sequential callers never wait, so
    brownouts left `queue_wait_s` at zero)."""
    peak = 6.0

    def run(ramps, tag):
        clock = VirtualClock()
        clock.sleep(100.0)      # mid-ramp, where the factor is `peak`
        s = SimHDFS(tmp_path / tag, clock=clock,
                    chaos=ChaosEngine(ChaosSpec(seed=1,
                                                brownout_at=ramps)),
                    bandwidth_bps=1e8, base_latency_s=0.02)
        t0 = clock.now()
        # both region uploads of one snapshot arrive at the snapshot
        # instant — the second queues behind the first
        s.put("a", b"x" * (1 << 20), arrival_s=t0)
        dur_first = clock.now() - t0
        s.put("b", b"x" * (1 << 20), arrival_s=t0)
        return dur_first, s.queue_wait_s

    dur_calm, wait_calm = run((), "calm")
    dur_brown, wait_brown = run(((0.0, 200.0, peak),), "brown")
    # the queued op waits exactly as long as its predecessor's service
    assert wait_calm == pytest.approx(dur_calm)
    assert wait_brown == pytest.approx(dur_brown)
    # brownout stretches service → the queue backs up with the factor
    assert wait_brown == pytest.approx(peak * wait_calm, rel=0.02)
