"""Fused Pallas tick-phase lowering (ISSUE 6 tentpole).

Pillars:

* **Three-way parity at 1e-12** — the fused-kernel pallas mode
  (`repro.kernels.tick_phase` + `jax_engine._build_pallas_run`)
  reproduces BOTH the dense arena-wide tick and the compact row-table
  tick over every partitioner family, kill-heavy seeds that empty
  whole phases, and a 2k-task deep-pipeline mega-arena.
* **Interpret == ref** — the actual Pallas kernel run through the
  interpreter (`REPRO_KERNEL_IMPL=interpret`, the CPU-CI stand-in for
  the compiled TPU kernel) agrees with the jnp reference lowering on
  the raw `ops.tick_phase` contract.
* **One trace per bucket** — the pallas run-fn cache keys on the pow2
  bucket signature + resolved impl, never on table contents.
* **Guards** — ``REPRO_REQUIRE_PHASE_MODE=pallas`` refuses fallbacks;
  pallas is explicit-only (never auto-selected); the seed-width-aware
  auto selector widens the compact region for wide sweeps.

The autouse fixture pins ``REPRO_KERNEL_IMPL=interpret`` so every
engine-level test here exercises the real kernel body, not just the
reference lowering.
"""
import jax
import numpy as np
import pytest

from repro.core.chaos import ChaosSpec
from repro.streams import nexmark
from repro.streams.engine import (FailoverConfig, build_plan,
                                  select_phase_mode)
from repro.streams.jax_engine import (JaxStreamEngine, _FN_CACHE,
                                      _Lowered, get_cached_run_fns,
                                      run_batch)

TOL = dict(rtol=1e-12, atol=1e-9)


@pytest.fixture(autouse=True)
def _interpret_impl(monkeypatch):
    """Route every pallas-mode run through the actual kernel body via
    the Pallas interpreter (CPU CI has no TPU to compile it)."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")


def _triple(graph, duration=120, n_hosts=8, **kw):
    return [JaxStreamEngine(graph, n_hosts=n_hosts, phase_mode=m,
                            **kw).run(duration)
            for m in ("dense", "compact", "pallas")]


def _assert_match(md, mp):
    for n in md.qps:
        np.testing.assert_allclose(md.qps[n], mp.qps[n],
                                   err_msg=f"qps[{n}]", **TOL)
        np.testing.assert_allclose(md.backlog[n], mp.backlog[n],
                                   err_msg=f"backlog[{n}]", **TOL)
    np.testing.assert_allclose(md.source_lag, mp.source_lag, **TOL)
    np.testing.assert_allclose(md.dropped, mp.dropped, **TOL)
    np.testing.assert_allclose(md.emitted, mp.emitted, **TOL)


@pytest.mark.parametrize("partitioner", ["rebalance", "hash", "weakhash",
                                         "backlog", "rescale",
                                         "group_rescale"])
def test_pallas_matches_dense_and_compact(partitioner):
    spec = ChaosSpec(seed=1, host_kill_prob_per_s=0.004,
                     straggler_frac=0.2)
    md, mc, mp = _triple(nexmark.q2(parallelism=16,
                                    partitioner=partitioner, n_groups=4),
                         chaos=spec,
                         failover=FailoverConfig(mode="region",
                                                 region_restart_s=20.0))
    _assert_match(md, mp)
    _assert_match(mc, mp)


def test_pallas_matches_dense_kill_heavy():
    """Kill-heavy seed: whole regions die repeatedly, phases run
    near-empty — fused-kernel masks/pads must keep routing, drops and
    requeues pinned to dense through every outage."""
    spec = ChaosSpec(seed=5, host_kill_prob_per_s=0.05,
                     straggler_frac=0.3)
    md, _, mp = _triple(nexmark.ss(parallelism=8), duration=240,
                        chaos=spec,
                        failover=FailoverConfig(mode="region",
                                                region_restart_s=10.0))
    assert len(mp.recoveries) > 5          # the chaos actually fired
    _assert_match(md, mp)


def test_pallas_matches_dense_2k_arena():
    """Deep-pipeline mega-arena (36 packed SS jobs, 6 phases): one
    jitted short run per mode, 1e-12 parity on the raw ys."""
    arena = nexmark.ss_arena(n_tasks=2016, parallelism=8, n_hosts=32)
    spec = ChaosSpec(seed=0, host_kill_prob_per_s=0.01,
                     straggler_frac=0.2)
    fo = FailoverConfig(mode="region", region_restart_s=15.0)
    outs = {}
    for mode in ("dense", "pallas"):
        low = _Lowered(arena, n_hosts=32, dt=0.5, queue_cap=256.0,
                       failover=fo, ckpt=None, seed=0, phase_mode=mode)
        run_fn, _ = get_cached_run_fns(low.desc)
        with jax.enable_x64(True):
            st, xs, _ = low.prepare(spec, 32)
            _, ys = run_fn(low.arrays, st, xs)
            outs[mode] = {k: np.asarray(v) for k, v in ys.items()}
    for k in outs["dense"]:
        np.testing.assert_allclose(outs["dense"][k], outs["pallas"][k],
                                   err_msg=k, **TOL)


def test_pallas_batch_is_natively_seed_batched():
    """run_batch in pallas mode carries the seed axis natively (kernel
    grid dimension, no outer vmap) and still matches the dense batch."""
    arena = nexmark.ss_arena(n_tasks=168, parallelism=4, n_hosts=8)
    spec = ChaosSpec(host_kill_prob_per_s=0.02, straggler_frac=0.2)
    bd = run_batch(arena, range(5), duration_s=60, base_spec=spec,
                   phase_mode="dense")
    bp = run_batch(arena, range(5), duration_s=60, base_spec=spec,
                   phase_mode="pallas")
    np.testing.assert_allclose(bd.source_lag, bp.source_lag, **TOL)
    np.testing.assert_allclose(bd.qps, bp.qps, **TOL)
    np.testing.assert_allclose(bd.backlog, bp.backlog, **TOL)
    np.testing.assert_allclose(bd.emitted_by_job, bp.emitted_by_job,
                               **TOL)
    np.testing.assert_allclose(bd.dropped_by_job, bp.dropped_by_job,
                               **TOL)


def test_tick_phase_interpret_matches_ref():
    """Raw kernel contract: ops.tick_phase under the interpreter equals
    the jnp reference on a packed SS phase, for every phase."""
    from repro.kernels.tick_phase import pack_phase_tables, tick_phase

    arena = nexmark.ss_arena(n_tasks=168, parallelism=4, n_hosts=8)
    low = _Lowered(arena, n_hosts=8, dt=0.5, queue_cap=256.0,
                   failover=None, ckpt=None, seed=0, phase_mode="pallas")
    rng = np.random.default_rng(7)
    with jax.enable_x64(True):
        import jax.numpy as jnp
        S, T = 8, low.plan.n_tasks
        produced = jnp.asarray(rng.uniform(0, 50.0, (S, T)))
        alive = jnp.asarray((rng.uniform(size=(S, T)) > 0.15)
                            .astype(float))
        free = jnp.asarray(rng.uniform(0, 256.0, (S, T)))
        for fi, ph in enumerate(low.tensor.phases):
            if not ph.D:
                continue
            tb = pack_phase_tables(low.arrays["edges"][fi],
                                   low.arrays["qcap"],
                                   low.arrays["mode_single"])
            ref = tick_phase(produced, alive, free, tb,
                             has_blk=ph.B > 0, has_grp=ph.G > 0,
                             impl="ref")
            ker = tick_phase(produced, alive, free, tb,
                             has_blk=ph.B > 0, has_grp=ph.G > 0,
                             impl="interpret")
            for r, k in zip(ref, ker):
                np.testing.assert_allclose(np.asarray(r), np.asarray(k),
                                           err_msg=f"phase {fi}", **TOL)


def test_one_trace_per_bucket_pallas():
    """Two same-shaped graphs with DIFFERENT partitioner kinds share
    one pallas bucket signature → one compiled run-fn serves both."""
    a = JaxStreamEngine(nexmark.q2(parallelism=8,
                                   partitioner="rebalance"),
                        n_hosts=8, phase_mode="pallas")
    b = JaxStreamEngine(nexmark.q2(parallelism=8, partitioner="backlog"),
                        n_hosts=8, phase_mode="pallas")
    assert a.lowered.desc == b.lowered.desc
    n0 = len(_FN_CACHE)
    ma = a.run(30)
    n1 = len(_FN_CACHE)
    mb = b.run(30)
    assert len(_FN_CACHE) == n1 and n1 <= n0 + 1
    assert ma.qps["filter"].shape == mb.qps["filter"].shape
    # pallas and compact descs differ (separate trace families)
    c = JaxStreamEngine(nexmark.q2(parallelism=8,
                                   partitioner="rebalance"),
                        n_hosts=8, phase_mode="compact")
    assert c.lowered.desc != a.lowered.desc


def test_require_phase_mode_pallas_guard(monkeypatch):
    """REPRO_REQUIRE_PHASE_MODE=pallas makes any fallback loud —
    scripts/ci.sh --pallas-smoke runs under it."""
    monkeypatch.setenv("REPRO_REQUIRE_PHASE_MODE", "pallas")
    with pytest.raises(RuntimeError, match="refusing to fall back"):
        _Lowered(nexmark.q2(parallelism=4), n_hosts=4, dt=0.5,
                 queue_cap=256.0, failover=None, ckpt=None, seed=0,
                 phase_mode="auto")
    low = _Lowered(nexmark.q2(parallelism=4), n_hosts=4, dt=0.5,
                   queue_cap=256.0, failover=None, ckpt=None, seed=0,
                   phase_mode="pallas")
    assert low.tensor.mode == "pallas"


def test_phase_mode_seed_width_selection():
    """pallas is never auto-selected; the seed-width argument widens
    the compact region (wide sweeps amortize row-table overhead)."""
    plan = build_plan(nexmark.ss(parallelism=8), 0.5, 256.0)
    assert select_phase_mode(plan, seed_width=1) == "dense"
    assert select_phase_mode(plan, seed_width=64) == "compact"
    assert select_phase_mode(plan, "pallas") == "pallas"
    for w in (1, 64):
        assert select_phase_mode(plan, seed_width=w) != "pallas"
    # tiny graphs stay dense at any width via the absolute floor
    tiny = build_plan(nexmark.q2(parallelism=2), 0.5, 256.0)
    assert select_phase_mode(tiny, seed_width=1) == "dense"
