"""The reduction from a profiler trace to busy time, idle share, the
grid program's time per task-tick and the breakdown: on events with
known answers, and on a trace recorded on this CPU."""
import jax
import jax.numpy as jnp
import pytest

from bench.harness import readers, trace
from bench.harness.trace import Event, Report


def test_union_covered_and_gaps():
    iv = [(5, 7), (0, 2), (1, 3), (6, 9), (12, 13)]
    assert trace.union(iv) == [(0, 3), (5, 9), (12, 13)]
    assert trace.covered(iv) == 8
    assert trace.gaps(iv, 0, 15) == [(3, 5), (9, 12), (13, 15)]
    assert trace.gaps([], 0, 4) == [(0, 4)]


def _report():
    # two chips over a 10 s window (ns); the grid program "jit_run" runs
    # twice per chip, a small "jit_copy" once on chip 0
    s = 1e9
    mods = {0: [Event("jit_run", 0, 3 * s), Event("jit_copy", 3 * s, 3.5 * s),
                Event("jit_run", 5 * s, 8 * s)],
            1: [Event("jit_run", 0, 2 * s), Event("jit_run", 6 * s, 8 * s)]}
    ops = {0: [Event("fusion", 0, 2 * s), Event("fusion", 1 * s, 3 * s),
               Event("copy", 3 * s, 3.5 * s), Event("while", 5 * s, 8 * s)],
           1: [Event("fusion", 0, 2 * s), Event("while", 6 * s, 8 * s)]}
    host = [Event("prep_chunk", 3.6 * s, 4.9 * s),
            Event("thread", 0, 10 * s)]
    return Report(10.0, ops, mods, host, 0.0, 10 * s)


def test_busy_idle_grid_time_and_breakdown_on_known_events():
    r = _report()
    assert r.busy_s == pytest.approx((6.5 + 4.0) / 2)
    assert r.grid_runs == 2
    assert r.grid_busy_s == pytest.approx(6.0 + 4.0)
    b = r.breakdown()
    assert b["device_ops"][0] == ["fusion", pytest.approx(6.0)]
    assert b["idle_gaps"][0] == ["no host event", pytest.approx(2.0)]
    assert b["idle_gaps"][1] == ["prep_chunk", pytest.approx(1.5)]


def test_per_task_tick_division_and_idle_share():
    class Chunk:
        summaries = [[0]] * 3                      # three configurations

    class Run:
        trace = _report()
        work = {"n_tasks": 100, "n_ticks": 50, "seed_chunk": 4}

        def chunks_in_window(self):
            return [Chunk()]

    # 10 s of grid time over 2 runs x 3 configs x 4 seeds x 100 x 50
    assert readers.tick_ns_per_task_tick(Run()) == pytest.approx(
        1e9 * 10.0 / (2 * 3 * 4 * 100 * 50))
    assert readers.device_idle_frac(Run()) == pytest.approx(1 - 5.25 / 10)
    Run.trace = None
    assert readers.tick_ns_per_task_tick(Run()) is None
    assert readers.device_idle_frac(Run()) is None


def test_reduction_of_a_trace_recorded_on_the_cpu(tmp_path):
    import time

    def grid(x):
        return jnp.tanh(x @ x) @ x

    def bump(x):
        return x + 1.0

    f, g = jax.jit(grid), jax.jit(bump)
    x = jnp.ones((192, 192))
    f(x).block_until_ready()
    g(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    w0 = time.perf_counter()
    for _ in range(5):
        f(x).block_until_ready()
    g(x).block_until_ready()
    w1 = time.perf_counter()
    jax.profiler.stop_trace()
    r = trace.read(tmp_path, w0, w1, 1, platform="cpu")
    assert 0.0 < r.busy_s <= r.window_s * 1.05
    assert r.grid_runs == 5
    assert 0.0 < r.grid_busy_s <= r.busy_s * 1.0001
    b = r.breakdown()
    assert b["device_ops"] and len(b["device_ops"]) <= 10
    assert all(t > 0 for _, t in b["idle_gaps"])


def test_a_trace_without_tpu_planes_is_refused_not_read_from_the_host(
        tmp_path):
    x = jnp.ones((64, 64))
    f = jax.jit(lambda a: a @ a)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(trace.TraceError, match="XLA Ops"):
        trace.read(tmp_path, 0.0, 1.0, 1)
    with pytest.raises(trace.TraceError, match="no .xplane.pb"):
        trace.read(tmp_path / "empty", 0.0, 1.0, 1)
