"""Sweep-as-a-service: chunked-execution bit-parity, the shared jit
cache across concurrent requests, incremental chunk publishing, the
prep/device timing split."""
import math
import threading

import numpy as np
import pytest

from repro.core.chaos import ChaosSpec, timeline_build_count
from repro.launch.serve import SweepRequest, SweepService
from repro.streams import nexmark
from repro.streams.chaos_sweep import (SweepChunk, deployment_drill, sweep,
                                       sweep_configs)
from repro.streams.engine import (CheckpointConfig, FailoverConfig,
                                  UpgradeConfig)
from repro.streams.jax_engine import (JaxStreamEngine, _Lowered,
                                      get_cached_config_fn, run_batch,
                                      run_config_batch, run_mix_batch,
                                      trace_cache_stats)

SEEDS = list(range(13))                 # deliberately non-pow2
CHUNKS = (1, 4, 5)                      # unit, pow2, ragged-last
SPEC = ChaosSpec(host_kill_prob_per_s=0.01, zk_down=((10.0, 12.0),))
FO = FailoverConfig(mode="single_task", detect_s=1.0,
                    single_restart_s=2.0)
CKPT = CheckpointConfig(interval_s=6.0)   # forces the grid-refit path
POLICIES = {"hot": UpgradeConfig(t_upgrade_s=8.0, wave_stagger_s=1.0)}

SURFACES = ("recovery_surface", "slo_surface", "backlog_surface",
            "lost_surface", "rollback_surface", "thrash_surface",
            "rescale_surface", "cost_surface")


def _drill(**kw):
    """The (C=4, S=13) flagship drill cube: 1 policy × 2 canary fracs ×
    2 rollback thresholds, ckpt-bearing (grid timeline path)."""
    return deployment_drill(
        nexmark.q2(parallelism=2), SEEDS, base_spec=SPEC,
        duration_s=30.0, policies=POLICIES, canary_fracs=(0.25, 0.5),
        rollback_thresholds=(math.inf, 200.0), failover=FO, ckpt=CKPT,
        n_hosts=4, **kw)


@pytest.fixture(scope="module")
def mono():
    before = timeline_build_count()
    cube = _drill()
    return cube, timeline_build_count() - before


# ----------------------------------------------------------------------
# chunked == monolithic, bit for bit, for every chunk-size class
# ----------------------------------------------------------------------
@pytest.mark.parametrize("chunk", CHUNKS)
def test_chunked_drill_bit_parity(mono, chunk):
    mono_cube, mono_builds = mono
    before = timeline_build_count()
    cube = _drill(seed_chunk=chunk)
    builds = timeline_build_count() - before
    # no per-chunk host replays beyond the offset refit: the chunked
    # run builds exactly as many timelines as the monolithic one (zero
    # on the grid path — streams are drawn once, schedules refitted)
    assert builds == mono_builds == 0
    for name in SURFACES:
        a = np.asarray(getattr(mono_cube.grid, name))
        b = np.asarray(getattr(cube.grid, name))
        assert np.array_equal(a, b), f"{name} drifted at chunk={chunk}"
    # the copied per-config series too, not just the derived surfaces
    for m_res, c_res in zip(mono_cube.grid.results, cube.grid.results):
        assert np.array_equal(m_res.batch.source_lag,
                              c_res.batch.source_lag)
        for name in ("down_backlog", "backlog_total"):
            a = getattr(m_res.batch, name)
            assert a is not None
            assert np.array_equal(a, getattr(c_res.batch, name)), \
                f"{name} drifted at chunk={chunk}"
        assert np.array_equal(m_res.batch.ckpt_epoch,
                              c_res.batch.ckpt_epoch)


def test_chunked_plain_sweep_bit_parity():
    g = nexmark.q2(parallelism=2)
    kw = dict(base_spec=SPEC, duration_s=30.0, failover=FO, n_hosts=4)
    mono_res = sweep(g, range(9), **kw)
    for chunk in (1, 4):                # 4 → ragged last chunk of 1
        res = sweep(g, range(9), seed_chunk=chunk, **kw)
        assert np.array_equal(mono_res.batch.source_lag,
                              res.batch.source_lag)
        assert np.array_equal(mono_res.batch.backlog, res.batch.backlog)
        assert [s.recovery_time_s for s in mono_res.summaries] == \
               [s.recovery_time_s for s in res.summaries]


# ----------------------------------------------------------------------
# incremental publishing: partial surfaces are exact column slices
# ----------------------------------------------------------------------
def test_chunk_publishing_slices(mono):
    mono_cube, _ = mono
    chunks: list[SweepChunk] = []
    cube = _drill(seed_chunk=5, on_chunk=chunks.append)
    assert [(c.seed_lo, c.seed_hi) for c in chunks] == \
           [(0, 5), (5, 10), (10, 13)]
    assert [c.index for c in chunks] == [0, 1, 2]
    assert sum(c.n_seeds for c in chunks) == len(SEEDS)
    for c in chunks:
        assert c.prep_s >= 0.0 and c.device_s > 0.0
        for name in SURFACES:
            part = np.asarray(getattr(c, name))
            full = np.asarray(getattr(cube.grid, name))
            assert part.shape == (4, c.n_seeds)
            assert np.array_equal(part, full[:, c.seed_lo:c.seed_hi])
        # chunk summaries carry real per-scenario rows
        assert len(c.summaries) == 4
        assert [s.seed for s in c.summaries[0]] == \
               SEEDS[c.seed_lo:c.seed_hi]
    # and the full cube still matches the monolithic one
    assert np.array_equal(mono_cube.grid.recovery_surface,
                          cube.grid.recovery_surface)


# ----------------------------------------------------------------------
# timing split: prep_s / device_s / total_s, compat scenarios_per_s
# ----------------------------------------------------------------------
def test_timing_split(mono):
    cube = _drill(seed_chunk=5)
    grid = cube.grid
    assert grid.prep_s > 0.0
    assert grid.device_s > 0.0
    assert grid.total_s == grid.wall_s > 0.0
    # compat: the old throughput field stays total-derived
    assert grid.scenarios_per_s == pytest.approx(
        grid.recovery_surface.size / grid.wall_s)
    r = sweep(nexmark.q2(parallelism=2), range(5), base_spec=SPEC,
              duration_s=30.0, failover=FO, n_hosts=4, seed_chunk=2)
    assert r.device_s > 0.0 and r.total_s == r.wall_s
    assert r.scenarios_per_s == pytest.approx(len(r.summaries) /
                                              r.wall_s)


# ----------------------------------------------------------------------
# service: one compiled trace across concurrent requests
# ----------------------------------------------------------------------
def test_one_trace_across_concurrent_requests():
    g = nexmark.q2(parallelism=3)       # fresh plan shape for this test
    kw = dict(base_spec=SPEC, duration_s=30.0, policies=POLICIES,
              canary_fracs=(0.25, 0.5),
              rollback_thresholds=(math.inf, 200.0), failover=FO,
              ckpt=CKPT, n_hosts=4, phase_mode="dense")
    low = _Lowered(g, n_hosts=4, dt=0.5, queue_cap=256.0, failover=FO,
                   ckpt=CKPT, seed=0, phase_mode="dense")
    fn = get_cached_config_fn(low.desc, shared_kills=False)
    before = fn._cache_size()
    with SweepService(workers=2) as svc:
        j1 = svc.submit("deployment_drill", g, range(8), seed_chunk=4,
                        label="drill-a", **kw)
        j2 = svc.submit("deployment_drill", g, range(8), seed_chunk=4,
                        label="drill-b", **kw)
        r1, r2 = j1.result(600), j2.result(600)
        stats = svc.stats()
    # both requests ran every chunk through ONE compiled trace (same
    # plan digest / grid shape / pow2 seed bucket / phase mode)
    assert fn._cache_size() - before == 1
    # per-request counters: the probe above created the cached run fn,
    # so both requests HIT the process-global fn cache
    assert stats["cache_hits"] >= 1
    assert stats["cache_hits"] + stats["cache_misses"] == 2
    assert stats["completed"] == 2
    assert np.array_equal(r1.recovery, r2.recovery)
    assert np.array_equal(r1.rollback_t, r2.rollback_t)
    for jid in (j1.id, j2.id):
        js = stats["jobs"][jid]
        assert js["state"] == "done" and js["chunks"] == 2
        assert js["ttfr_s"] is not None and js["wall_s"] is not None


def test_incremental_results_and_replay(mono):
    # traces for the chunk buckets are warm (fixture + parity tests):
    # first-chunk latency must beat full-cube latency
    with SweepService(workers=1) as svc:
        job = svc.submit("deployment_drill", nexmark.q2(parallelism=2),
                         SEEDS, seed_chunk=5, base_spec=SPEC,
                         duration_s=30.0, policies=POLICIES,
                         canary_fracs=(0.25, 0.5),
                         rollback_thresholds=(math.inf, 200.0),
                         failover=FO, ckpt=CKPT, n_hosts=4)
        seen = []
        for chunk in job.chunks(timeout=600):
            seen.append((chunk.seed_lo, chunk.seed_hi, job.done()))
        cube = job.result(1.0)
    # the first chunk arrived while the job was still running — the
    # whole point of incremental publishing
    assert seen[0][:2] == (0, 5) and seen[0][2] is False
    assert len(seen) == 3
    assert job.stats["ttfr_s"] < job.stats["wall_s"]
    # late subscriber replays the buffered history after completion
    replay = [c.index for c in job.chunks(timeout=1.0)]
    assert replay == [0, 1, 2]
    assert np.array_equal(cube.grid.recovery_surface,
                          mono[0].grid.recovery_surface)


def test_service_error_propagation():
    with SweepService(workers=1) as svc:
        job = svc.submit("sweep_configs", nexmark.q2(parallelism=2),
                         range(2), base_spec=SPEC, duration_s=10.0)
        with pytest.raises(KeyError):   # missing configs=
            job.result(60.0)
        assert job.stats["state"] == "failed"
    with pytest.raises(ValueError, match="unknown request kind"):
        SweepRequest("nope", None, [])


def test_trace_cache_stats_shape():
    s = trace_cache_stats()
    assert set(s) == {"hits", "misses"}
    assert s["hits"] >= 0 and s["misses"] >= 0


def test_concurrent_subscribers_one_job(mono):
    """Two consumer threads over one job each see the full chunk
    stream (multi-consumer buffered publisher)."""
    with SweepService(workers=1) as svc:
        job = svc.submit("deployment_drill", nexmark.q2(parallelism=2),
                         SEEDS, seed_chunk=5, base_spec=SPEC,
                         duration_s=30.0, policies=POLICIES,
                         canary_fracs=(0.25, 0.5),
                         rollback_thresholds=(math.inf, 200.0),
                         failover=FO, ckpt=CKPT, n_hosts=4)
        out = {0: [], 1: []}

        def consume(k):
            for c in job.chunks(timeout=600):
                out[k].append(c.index)

        threads = [threading.Thread(target=consume, args=(k,))
                   for k in out]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        job.result(1.0)
    assert out[0] == out[1] == [0, 1, 2]


# ----------------------------------------------------------------------
# scoped x64 and the persistent compile cache
# ----------------------------------------------------------------------
def test_engine_calls_leave_caller_x64_unchanged():
    """The engine runs in f64 under the thread-local ``jax.enable_x64``
    context: the caller's flag, and the dtype of its own arrays, are
    the same after every entry point as before."""
    import jax
    import jax.numpy as jnp

    from repro.streams.chaos_sweep import sweep_configs

    flag = jax.config.jax_enable_x64
    g = nexmark.q2(parallelism=2)
    kw = dict(base_spec=SPEC, duration_s=10.0, n_hosts=4)
    res = run_config_batch(g, [FO, (FO, CKPT)], range(3), **kw)
    assert res[0].source_lag.dtype == np.float64
    assert jax.config.jax_enable_x64 == flag
    sweep_configs(g, [FO, (FO, CKPT)], range(3), seed_chunk=2, **kw)
    assert jax.config.jax_enable_x64 == flag
    with SweepService(workers=1) as svc:
        job = svc.submit("sweep_configs", g, range(3), seed_chunk=2,
                         configs=[FO, (FO, CKPT)], **kw)
        cube = job.result(600.0)
    assert cube.backlog_surface.dtype == np.float64
    assert jax.config.jax_enable_x64 == flag
    assert jnp.zeros(2).dtype == (jnp.float64 if flag else jnp.float32)


@pytest.mark.parametrize("env_dir", [False, True])
def test_persistent_cache_dir(monkeypatch, tmp_path, env_dir):
    """`JAX_COMPILATION_CACHE_DIR` wins and no other path is set;
    otherwise the cache sits at the checkout's fixed `.jax_cache`. The
    CPU backend never turns the cache on."""
    import jax

    from repro.core import hotupdate

    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    assert hotupdate.enable_persistent_cache() is None      # CPU backend
    assert jax.config.jax_compilation_cache_dir == prev[0]
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        path = hotupdate.enable_persistent_cache()
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        if env_dir:
            assert path == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev[0]
        else:
            assert path == str(hotupdate.REPO_CACHE_DIR)
            assert hotupdate.REPO_CACHE_DIR.name == ".jax_cache"
            assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev[1])


# ----------------------------------------------------------------------
# a phase mode outside dense|compact|auto fails before any trace
# ----------------------------------------------------------------------
_Q2 = nexmark.q2(parallelism=2)
_KW = dict(base_spec=SPEC, duration_s=10.0, n_hosts=4, phase_mode="pallas")
_ENTRIES = {
    "JaxStreamEngine": lambda svc: JaxStreamEngine(
        _Q2, chaos=SPEC, n_hosts=4, phase_mode="pallas"),
    "run_batch": lambda svc: run_batch(_Q2, range(2), **_KW),
    "run_mix_batch": lambda svc: run_mix_batch(_Q2, [[1.0]], range(2),
                                               **_KW),
    "run_config_batch": lambda svc: run_config_batch(_Q2, [FO], range(2),
                                                     **_KW),
    "sweep": lambda svc: sweep(_Q2, range(2), **_KW),
    "sweep_configs": lambda svc: sweep_configs(_Q2, [FO], range(2), **_KW),
    "submit_sweep": lambda svc: svc.submit("sweep", _Q2, range(2), **_KW),
    "submit_sweep_configs": lambda svc: svc.submit(
        "sweep_configs", _Q2, range(2), configs=[FO], **_KW),
}


@pytest.mark.parametrize("entry", list(_ENTRIES))
def test_removed_phase_mode_refused_before_trace(entry):
    with SweepService(workers=1) as svc:
        before = trace_cache_stats()
        with pytest.raises(ValueError, match="dense|compact|auto"):
            _ENTRIES[entry](svc)
        assert trace_cache_stats() == before
        assert svc.jobs() == []
