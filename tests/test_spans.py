"""Program spans of the sweep service: the span log itself (nesting,
parents, counts, threads, two requests kept apart), the chunk and job
times read from them, the tick's named scopes, and the annotations in a
profiler trace recorded on the CPU."""
import math
import pathlib
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.core.chaos import ChaosSpec
from repro.launch.serve import SweepService
from repro.streams import nexmark
from repro.streams.engine import CheckpointConfig, FailoverConfig
from repro.streams.jax_engine import (TICK_STEPS, ConfigGridPlan,
                                      SeedBatchPlan, run_chunks)
from repro.streams.spans import SpanLog

SPEC = ChaosSpec(host_kill_prob_per_s=0.01, zk_down=((10.0, 12.0),))
FO = FailoverConfig(mode="single_task", detect_s=1.0,
                    single_restart_s=2.0)
GRID = [FO, {"failover": FO, "ckpt": CheckpointConfig(interval_s=6.0)},
        FailoverConfig(mode="region", detect_s=1.0,
                       region_restart_s=4.0)]
LEAVES = {"sweep.plan", "sweep.prep", "sweep.device", "sweep.fetch",
          "sweep.summarize", "sweep.assemble"}
FETCHED = ("source_lag", "backlog_total", "down_backlog", "emitted",
           "dropped", "ckpt_epoch", "rollback_t", "thrash_t", "n_rescale",
           "resource_s")


def test_nesting_parents_and_counts():
    log = SpanLog(request=3)
    with log.span("sweep.request", seeds=8) as root:
        with log.span("sweep.plan") as plan:
            with log.span("inner", k=1):
                pass
            plan.count(hits=2, misses=0)
        with log.span("sweep.prep", chunk=0):
            pass
    got = {s.name: s for s in log}
    assert [s.name for s in log] == ["inner", "sweep.plan", "sweep.prep",
                                    "sweep.request"]
    assert got["sweep.request"].parent is None
    assert got["sweep.plan"].parent == "sweep.request"
    assert got["inner"].parent == "sweep.plan"
    assert got["sweep.prep"].parent == "sweep.request"
    assert got["sweep.plan"].counts == {"hits": 2, "misses": 0}
    assert got["inner"].counts == {"k": 1}
    assert root.counts == {"seeds": 8}
    assert {s.request for s in log} == {3}
    for s in log:
        assert root.start <= s.start <= s.end <= root.end
    assert log.total("sweep.prep") == pytest.approx(
        got["sweep.prep"].seconds)
    assert log.total("absent") == 0.0 and log.of("absent") == []


def test_spans_of_another_thread_go_to_the_request_root():
    log = SpanLog(request=5)
    with log.span("sweep.request"):
        def lane():
            with log.span("sweep.device", chunk=0):
                with log.span("sweep.wait"):
                    pass
        t = threading.Thread(target=lane)
        t.start()
        t.join()
    got = {s.name: s for s in log}
    assert got["sweep.device"].parent == "sweep.request"
    assert got["sweep.wait"].parent == "sweep.device"
    assert {s.request for s in log} == {5}
    # with no root open, a span on a fresh thread has no parent
    log2 = SpanLog()
    with log2.span("sweep.plan"):
        pass

    def fetch():
        with log2.span("sweep.fetch"):
            pass
    t = threading.Thread(target=fetch)
    t.start()
    t.join()
    assert [(s.name, s.parent) for s in log2] == [("sweep.plan", None),
                                                 ("sweep.fetch", None)]


def test_two_concurrent_logs_are_kept_apart():
    logs = [SpanLog(request=i) for i in range(2)]
    barrier = threading.Barrier(2)

    def request(log):
        with log.span("sweep.request"):
            barrier.wait()
            for k in range(3):
                with log.span("sweep.prep", chunk=k):
                    time.sleep(0.001)
                barrier.wait()

    ts = [threading.Thread(target=request, args=(lg,)) for lg in logs]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for i, log in enumerate(logs):
        assert [s.counts.get("chunk") for s in log.of("sweep.prep")] == \
               [0, 1, 2]
        assert {s.request for s in log} == {i}
        assert len(list(log)) == 4


def test_many_threads_lose_no_span():
    log = SpanLog(request=9)
    n_threads, n_spans = 32, 200

    def record(k):
        for i in range(n_spans):
            with log.span("sweep.prep", chunk=i):
                with log.span("sweep.inner", thread=k):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with log.span("sweep.request"):
            ts = [threading.Thread(target=record, args=(k,))
                  for k in range(n_threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    spans = list(log)
    assert len(spans) == 2 * n_threads * n_spans + 1
    assert {s.parent for s in spans if s.name == "sweep.prep"} == \
           {"sweep.request"}
    assert {s.parent for s in spans if s.name == "sweep.inner"} == \
           {"sweep.prep"}


def _nbytes(cube, lo, hi) -> int:
    return sum(getattr(r.batch, f)[lo:hi].nbytes
               for r in cube.results for f in FETCHED)


def test_service_chunks_and_stats_come_from_the_spans():
    g = nexmark.q2(parallelism=2)
    kw = dict(configs=GRID, base_spec=SPEC, duration_s=20.0, n_hosts=4)
    with SweepService(workers=1) as svc:
        first = svc.submit("sweep_configs", g, range(8), seed_chunk=4,
                           **kw)
        job = svc.submit("sweep_configs", g, range(8), seed_chunk=4,
                         **kw)
        chunks = list(job.chunks(timeout=600))
        cube = job.result(600)
        first.result(600)
    st = job.stats
    # chunks of 4 seeds pad to no wider bucket, so the fetched arrays
    # are exactly the chunk's slices of the final cube
    assert [(c.seed_lo, c.seed_hi) for c in chunks] == [(0, 4), (4, 8)]
    for c in chunks:
        assert c.fetch_s > 0.0 and c.summarize_s > 0.0
        assert c.device_s > 0.0 and c.prep_s > 0.0
        assert c.history_bytes == _nbytes(cube, c.seed_lo, c.seed_hi)
        assert c.total_s == pytest.approx(c.prep_s + c.device_s
                                          + c.fetch_s)
        assert c.total_s <= st["wall_s"]
    # the lane and the caller thread each do their steps one at a time
    assert sum(c.device_s + c.fetch_s for c in chunks) <= st["wall_s"]
    assert sum(c.prep_s for c in chunks) <= st["wall_s"]

    log = job.spans
    names = {s.name for s in log}
    assert names == LEAVES | {"sweep.request"}
    assert {s.request for s in log} == {job.id}
    root, = log.of("sweep.request")
    assert root.parent is None
    assert {s.parent for s in log if s.name != "sweep.request"} == \
           {"sweep.request"}
    assert root.counts == {"seeds": 8, "chunks": 2, "configs": 3}
    assert [s.counts["chunk"] for s in log.of("sweep.fetch")] == [0, 1]
    assert [s.counts["bytes"] for s in log.of("sweep.fetch")] == \
           [c.history_bytes for c in chunks]
    assert [s.counts["scenarios"] for s in log.of("sweep.summarize")] \
        == [12, 12]
    plan, = log.of("sweep.plan")
    assert set(plan.counts) == {"hits", "misses"}
    assert [s.seconds for s in log.of("sweep.fetch")] == \
           [c.fetch_s for c in chunks]
    assert [s.seconds for s in log.of("sweep.summarize")] == \
           [c.summarize_s for c in chunks]

    # job stats: sums of the spans, and waits from submission
    assert st["prep_s"] == pytest.approx(log.total("sweep.prep"))
    assert st["device_s"] == pytest.approx(sum(c.device_s
                                               for c in chunks))
    assert st["fetch_s"] == pytest.approx(sum(c.fetch_s for c in chunks))
    assert st["assemble_s"] == pytest.approx(
        log.total("sweep.assemble"))
    assert st["assemble_s"] > 0.0
    assert st["queued_s"] > 0.0            # behind the first request
    assert st["wall_s"] == pytest.approx(st["queued_s"] + root.seconds)
    assert st["queued_s"] < st["ttfr_s"] < st["wall_s"]
    assert cube.prep_s == pytest.approx(st["prep_s"])
    assert cube.device_s == pytest.approx(st["device_s"])
    assert 0.0 < cube.wall_s <= root.seconds


def test_failed_request_still_closes_its_root_span():
    with SweepService(workers=1) as svc:
        job = svc.submit("sweep_configs", nexmark.q2(parallelism=2),
                         range(2), base_spec=SPEC, duration_s=10.0)
        with pytest.raises(KeyError):   # missing configs=
            job.result(600)
    root, = job.spans.of("sweep.request")
    assert root.end is not None and "configs" not in root.counts
    assert job.stats["state"] == "failed"
    assert job.stats["wall_s"] == pytest.approx(job.stats["queued_s"]
                                                + root.seconds)


@pytest.mark.parametrize("mode", ["compact", "dense"])
def test_every_tick_step_is_a_named_scope(mode):
    plan = ConfigGridPlan(nexmark.q2(parallelism=2), [FO], range(2),
                          base_spec=SPEC, duration_s=3.0, n_hosts=4,
                          phase_mode=mode)
    _, _, state, xs, _ = plan.prep_chunk(0, 2)
    with jax.enable_x64(True):
        text = plan.fn.lower(plan.pa, state, xs).as_text(debug_info=True)
    for step in TICK_STEPS:
        assert step in text, step
    # the per-phase steps carry the phase index
    assert "tick_route0" in text and "tick_consume1" in text


def test_spans_are_annotations_in_a_cpu_trace(tmp_path):
    from jax.profiler import ProfileData

    x = jax.numpy.ones((64, 64))
    f = jax.jit(lambda a: a @ a)
    f(x).block_until_ready()
    log = SpanLog(request=11)
    jax.profiler.start_trace(str(tmp_path))
    with log.span("sweep.request"):
        with log.span("sweep.fetch", chunk=2) as sp:
            np.asarray(f(x))
            sp.count(bytes=123)
    jax.profiler.stop_trace()
    pb, = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    found = {}
    for plane in ProfileData.from_file(str(pb)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("sweep."):
                    found[e.name] = (plane.name, dict(e.stats),
                                     e.duration_ns)
    assert set(found) == {"sweep.request", "sweep.fetch"}
    plane, stats, dur = found["sweep.fetch"]
    assert plane.startswith("/host:")
    assert stats == {"request": 11, "chunk": 2, "bytes": 123}
    assert 0 < dur <= found["sweep.request"][2]
    assert math.isclose(dur * 1e-9, log.of("sweep.fetch")[0].seconds,
                        rel_tol=0.5, abs_tol=5e-3)


def test_device_span_counts_the_entries_of_the_padded_seed_axis():
    # 3 seeds in one chunk dispatch as 4 (the next power of two): the
    # pass routes the padded row too
    plan = SeedBatchPlan(nexmark.q2(parallelism=2), range(3),
                         base_spec=SPEC, duration_s=5.0, n_hosts=4,
                         failover=FO)
    log = SpanLog()
    chunk, = run_chunks(plan, None, spans=log)
    per_tick = sum(ph.D for ph in plan.low.tensor.phases)
    assert per_tick > 0
    assert chunk.route_entries == per_tick * plan.n_ticks * 4
    dev, = log.of("sweep.device")
    assert dev.counts == {"chunk": 0, "route_entries": chunk.route_entries}
