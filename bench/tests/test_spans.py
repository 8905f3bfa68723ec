"""The readers of the program's spans: idle gaps named by the ``sweep.*``
leaf span over them, the idle share no span explains and the device
time per tick step, on events with known answers and on a trace
recorded on this CPU; and each new reader's None where a run has
nothing to read (a program that records no spans)."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench.harness import data, phases, spans, trace
from bench.harness.stats import Request
from bench.harness.trace import Event, Report

S = 1e9
NEW = ("fetch_ms_per_chunk.batch", "summarize_ms_per_chunk.batch",
       "assemble_ms_per_request.batch", "history_mb_per_chunk.batch",
       "device_idle_unspanned_frac.batch")


def _report(host_extra=()):
    # one chip over a 10 s window: busy 0-3 s and 6-8 s; idle 3-6 s and
    # 8-10 s. The lane's fetch covers 3-4.5 s, the summary 4.4-5.5 s (a
    # JAX event inside the fetch overlaps it less), the request root
    # covers everything and names nothing.
    ops = {0: [Event("while", 0, 3 * S), Event("fusion", 6 * S, 8 * S)]}
    mods = {0: [Event("jit_run", 0, 3 * S), Event("jit_run", 6 * S, 8 * S)]}
    host = [Event("sweep.request", 0, 10 * S),
            Event("np.asarray(jax.Array)", 3.1 * S, 4.4 * S),
            Event("sweep.fetch", 3 * S, 4.5 * S),
            Event("sweep.summarize", 4.4 * S, 5.5 * S),
            Event("other", 8.5 * S, 9 * S), *host_extra]
    return Report(10.0, ops, mods, host, 0.0, 10 * S)


def test_gaps_are_named_by_the_leaf_span_over_them():
    r = _report()
    leaves = spans.leaf_spans(r)
    assert [h.name for h in leaves] == ["sweep.fetch", "sweep.summarize"]
    assert spans.label(r, leaves, 3 * S, 6 * S) == "sweep.fetch"
    assert spans.label(r, leaves, 5 * S, 6 * S) == "sweep.summarize"
    # no leaf span there: the trace reduction's own rule
    assert spans.label(r, leaves, 8 * S, 10 * S) == "other"
    assert spans.named_gaps(r, leaves) == [
        ["sweep.fetch", pytest.approx(3.0)], ["other", pytest.approx(2.0)]]


def test_unspanned_idle_share_on_known_events():
    # idle 5 s, of which 3-5.5 s lie under leaf spans: 2.5 s unexplained
    r = _report()
    assert spans.unspanned_idle_frac(r, spans.leaf_spans(r)) == \
        pytest.approx(0.25)
    # a second chip, idle all along, under the same spans
    r.ops[1] = []
    assert spans.unspanned_idle_frac(r, spans.leaf_spans(r)) == \
        pytest.approx((2.5 + 10 - 2.5) / 2 / 10)
    # nothing to read without a leaf span
    bare = _report()
    bare.host = [h for h in bare.host if not h.name.startswith("sweep.")
                 or h.name == spans.ROOT]
    assert spans.unspanned_idle_frac(bare, spans.leaf_spans(bare)) is None


class _Span:
    def __init__(self, name, start, end):
        self.name, self.start, self.end = name, start, end


def test_spans_cut_by_the_window_come_from_the_program_log():
    # the host clock runs 100 s behind the trace's. The program's log
    # holds a fetch before the trace began, the two traced spans, and a
    # summary still open when the trace stopped (8.5-11 s on the trace's
    # clock), which the trace dropped
    log = [_Span("sweep.fetch", -104.0, -102.0),
           _Span("sweep.fetch", -97.0, -95.5),
           _Span("sweep.summarize", -95.6, -94.5),
           _Span("sweep.summarize", -91.5, -89.0)]
    run = _Run([], [_request(5.0)])
    run.requests[0].job.spans = log
    r = _report()
    leaves = spans.leaf_spans(r, run)
    assert spans._offset(leaves[:2], [spans.Event(k.name, k.start, k.end)
                                      for k in log]) == pytest.approx(100e9)
    assert [(h.name, h.start / S) for h in leaves[2:]] == [
        ("sweep.fetch", pytest.approx(-4.0)),
        ("sweep.fetch", pytest.approx(3.0)),
        ("sweep.summarize", pytest.approx(4.4)),
        ("sweep.summarize", pytest.approx(8.5))]
    # the end gap (8-10 s) is now spanned from 8.5 s on: of the 5 s
    # idle, 3-5.5 s and 8.5-10 s are spanned
    assert spans.unspanned_idle_frac(r, leaves) == pytest.approx(
        (5 - 2.5 - 1.5) / 10)
    assert spans.named_gaps(r, leaves)[1] == ["sweep.summarize",
                                              pytest.approx(2.0)]
    # without traced spans there is nothing to align the log with
    bare = _report()
    bare.host = []
    assert spans.leaf_spans(bare, run) == []


def test_overlap_of_interval_lists():
    assert spans._overlap([(0, 2), (5, 9)], [(1, 6), (8, 10)]) == 3
    assert spans._overlap([], [(0, 1)]) == 0


class _Chunk:
    def __init__(self, **kw):
        self.__dict__.update(kw)


class _Job:
    def __init__(self, stats):
        self.stats = stats


class _Run:
    def __init__(self, chunks, requests, tr=None):
        self._chunks, self.requests, self.trace = chunks, requests, tr
        self.w0, self.w1 = 0.0, 10.0

    def chunks_in_window(self):
        return self._chunks


def _request(done, **stats):
    r = Request(1, due=0.0)
    r.done, r.job = done, _Job(stats)
    return r


def test_new_readers_on_known_values():
    chunks = [_Chunk(fetch_s=2.0, summarize_s=0.5, history_bytes=800e6),
              _Chunk(fetch_s=4.0, summarize_s=1.5, history_bytes=810e6)]
    reqs = [_request(5.0, assemble_s=3.0), _request(9.0, assemble_s=5.0),
            _request(12.0, assemble_s=99.0), _request(None)]
    run = _Run(chunks, reqs, _report())
    got = {m: data.reader(m)(run) for m in NEW}
    assert got == {"fetch_ms_per_chunk.batch": pytest.approx(3000.0),
                   "summarize_ms_per_chunk.batch": pytest.approx(1000.0),
                   "assemble_ms_per_request.batch": pytest.approx(4000.0),
                   "history_mb_per_chunk.batch": pytest.approx(805.0),
                   "device_idle_unspanned_frac.batch":
                       pytest.approx(0.25)}


@pytest.mark.parametrize("metric", NEW)
def test_new_readers_read_nothing_where_the_program_records_no_spans(
        metric):
    bare = _report()
    bare.host = [h for h in bare.host if not h.name.startswith("sweep.")]
    # chunks and jobs of a program without the fields, a trace without
    # sweep.* events; then no chunk, no finished request and no trace
    old = _Run([_Chunk(prep_s=1.0, device_s=2.0)],
               [_request(5.0, queued_s=0.0)], bare)
    old.requests[0].job.spans = [_Span("sweep.fetch", 1.0, 2.0)]
    empty = _Run([], [_request(None)], None)
    read = data.reader(metric)
    assert read(old) is None
    assert read(empty) is None


def test_steps_of_scope_paths_and_their_device_time():
    assert phases.step_of("jit(run)/while/body/tick_route1/mul") == \
        "tick_route1"
    assert phases.step_of("jit(run)/while/body/tick_setup/ge") == \
        "tick_setup"
    assert phases.step_of("jit(run)/concatenate") is None
    ops = [("a/tick_route1/x", 0, 2 * S), ("a/tick_route1/y", 3 * S, 4 * S),
           ("a/tick_failover/z", 4 * S, 4.5 * S), ("a/copy", 0, S)]
    assert phases.step_seconds(ops) == {"tick_route1": pytest.approx(3.0),
                                        "tick_failover": pytest.approx(0.5),
                                        "unscoped": pytest.approx(1.0)}


def test_sweep_spans_of_a_cpu_trace_name_its_idle(tmp_path):
    from repro.streams.spans import SpanLog

    f = jax.jit(lambda a: jnp.tanh(a @ a) @ a)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    log = SpanLog(request=1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    w0 = time.perf_counter()
    with log.span("sweep.request"):
        for k in range(3):
            with log.span("sweep.device", chunk=k):
                f(x).block_until_ready()
            with log.span("sweep.summarize", chunk=k):
                time.sleep(0.05)
    w1 = time.perf_counter()
    jax.profiler.stop_trace()
    r = trace.read(tmp_path, w0, w1, 1, platform="cpu")
    traced = spans.leaf_spans(r)
    assert sorted({h.name for h in traced}) == \
        ["sweep.device", "sweep.summarize"]
    assert len(traced) == 6
    names = [n for n, t in spans.named_gaps(r, traced) if t > 0.02]
    assert names and set(names) == {"sweep.summarize"}
    frac = spans.unspanned_idle_frac(r, traced)
    assert 0.0 <= frac < 1.0 - r.busy_s / r.window_s
    # the program's log, moved onto the trace's clock, lands on the
    # traced spans to within a millisecond
    run = _Run([], [_request(w1)])
    run.requests[0].job.spans = list(log)
    kept = spans.leaf_spans(r, run)[len(traced):]
    for t, k in zip(sorted(traced, key=lambda e: e.start),
                    sorted(kept, key=lambda e: e.start)):
        assert t.name == k.name and abs(t.start - k.start) < 1e6


def test_device_time_by_tick_step_of_a_cpu_trace(tmp_path):
    from repro.core.chaos import ChaosSpec
    from repro.streams import nexmark
    from repro.streams.engine import FailoverConfig
    from repro.streams.jax_engine import TICK_STEPS, ConfigGridPlan

    plan = ConfigGridPlan(
        nexmark.q2(parallelism=2),
        [FailoverConfig(mode="single_task", detect_s=1.0,
                        single_restart_s=2.0)], range(2),
        base_spec=ChaosSpec(host_kill_prob_per_s=0.01), duration_s=5.0,
        n_hosts=4, phase_mode="compact")
    prepped = plan.prep_chunk(0, 2)
    plan.dispatch(prepped)
    jax.profiler.start_trace(str(tmp_path))
    plan.dispatch(prepped)
    jax.profiler.stop_trace()
    got = phases.steps(tmp_path, platform="cpu")
    steps = {k.rstrip("0123456789") for k in got["steps_s"]}
    assert set(TICK_STEPS) <= steps
    assert got["while_s"] > 0.0
    scoped = sum(v for k, v in got["steps_s"].items() if k != "unscoped")
    assert 0.0 < scoped <= sum(got["steps_s"].values())
