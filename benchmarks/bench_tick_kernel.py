"""Fused Pallas tick-phase benchmark (ISSUE 6 acceptance).

Three studies:

* **kernel** — one fused routing phase (`repro.kernels.tick_phase`) in
  isolation on the deepest SS phase: the jnp reference lowering vs the
  actual Pallas kernel through the interpreter, on a seed-batched
  ``(S, n_tasks)`` state block. On TPU the same call compiles the real
  kernel; on this CPU box the interpret number is a correctness-path
  cost, not a perf claim.
* **engine** — end-to-end warm seed-batch runs (`run_batch`) of the SS
  mega-arena, compact vs pallas phase mode. The pallas run is natively
  seed-batched (no outer vmap; the seed axis is the kernel grid
  dimension), so this measures the fused lowering against the
  row-table compact tick it replaces. Headline:
  ``pallas_tick_speedup`` in results/bench_summary.json.
* **mega** (full mode only) — the 100k-task `nexmark.mega_arena`
  ticking end-to-end in pallas mode, plus a (C=4 failover configs ×
  S=64 seeds) grid over it in ONE `run_config_batch` device pass:
  C·S·n_jobs ≈ 1.07M job-scenarios per pass (the ISSUE 6 scale bar).

Emits CSV rows through benchmarks/run.py and writes
``results/bench_tick_kernel.json`` + refreshes
``results/bench_summary.json``. Quick mode shrinks everything and never
overwrites the tracked JSONs.
"""
from __future__ import annotations

import functools
import json
import pathlib
import time

import jax
import numpy as np

try:
    from benchmarks.run import quick_mode
except ImportError:      # standalone: sys.path[0] is benchmarks/
    from run import quick_mode

from repro.core.chaos import ChaosSpec
from repro.streams import nexmark
from repro.streams.engine import FailoverConfig
from repro.streams.jax_engine import (_Lowered, run_batch,
                                      run_config_batch)

SPEC = ChaosSpec(host_kill_prob_per_s=0.004, straggler_frac=0.2)
FAILOVER = FailoverConfig(mode="region", region_restart_s=20.0)
RESULTS = pathlib.Path(__file__).resolve().parent.parent / "results"


def _time(fn, *args, reps: int = 3) -> float:
    """Warm min-of-reps wall seconds of a jitted fn (blocks on result)."""
    jax.block_until_ready(fn(*args))          # compile / warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return min(times)


def kernel_study(n_tasks: int, n_seeds: int, reps: int = 3) -> dict:
    """One fused phase in isolation: ref vs interpret impl on the
    heaviest (largest-D) phase of a packed SS arena."""
    from repro.kernels.tick_phase import (choose_seed_block,
                                          pack_phase_tables, table_bytes,
                                          tick_phase)

    arena = nexmark.ss_arena(n_tasks=n_tasks, parallelism=8, n_hosts=32)
    low = _Lowered(arena, n_hosts=32, dt=0.5, queue_cap=256.0,
                   failover=FAILOVER, ckpt=None, seed=0,
                   phase_mode="pallas")
    fi, ph = max(enumerate(low.tensor.phases), key=lambda p: p[1].D)
    with jax.enable_x64(True):
        tb = pack_phase_tables(low.arrays["edges"][fi],
                               low.arrays["qcap"],
                               low.arrays["mode_single"])
        sb = choose_seed_block(n_seeds, low.plan.n_tasks, ph.D,
                               tb["er_idx"].shape[0], table_bytes(tb))
        rng = np.random.default_rng(0)
        produced = jax.numpy.asarray(
            rng.uniform(0, 50.0, (n_seeds, low.plan.n_tasks)))
        alive = jax.numpy.asarray(
            (rng.uniform(size=(n_seeds, low.plan.n_tasks)) > 0.1)
            .astype(float))
        free = jax.numpy.asarray(
            rng.uniform(0, 256.0, (n_seeds, low.plan.n_tasks)))
        rec = {"n_tasks": low.plan.n_tasks, "S": n_seeds, "D": ph.D,
               "phase": fi, "seed_block": sb,
               "table_kib": round(table_bytes(tb) / 1024, 1)}
        for impl in ("ref", "interpret"):
            fn = jax.jit(functools.partial(
                tick_phase, has_blk=ph.B > 0, has_grp=ph.G > 0,
                impl=impl))
            rec[impl + "_us"] = round(
                _time(fn, produced, alive, free, tb, reps=reps) * 1e6, 1)
    return rec


def engine_study(n_tasks: int, n_seeds: int, duration: float,
                 reps: int = 3) -> dict:
    """Warm end-to-end seed-batch wall, compact vs pallas phase mode,
    on the deep-pipeline SS mega-arena."""
    arena = nexmark.ss_arena(n_tasks=n_tasks, parallelism=8, n_hosts=64)
    seeds = list(range(n_seeds))
    rec = {"arena": f"ss_{arena.plan.n_tasks}t", "S": n_seeds,
           "n_jobs": arena.n_jobs, "duration_s": duration}
    for mode in ("compact", "pallas"):
        run_batch(arena, seeds, duration_s=duration, base_spec=SPEC,
                  failover=FAILOVER, phase_mode=mode)   # compile / warm
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            run_batch(arena, seeds, duration_s=duration, base_spec=SPEC,
                      failover=FAILOVER, phase_mode=mode)
            times.append(time.perf_counter() - t0)
        rec[mode + "_warm_s"] = round(min(times), 3)
    rec["pallas_vs_compact_speedup"] = round(
        rec["compact_warm_s"] / rec["pallas_warm_s"], 2)
    return rec


def mega_study(n_tasks: int, n_configs: int, n_seeds: int,
               duration: float) -> dict:
    """100k-task arena end-to-end in pallas mode + the million-job-
    scenario single-pass config grid."""
    arena = nexmark.mega_arena(n_tasks=n_tasks, workload="q12",
                               parallelism=8, n_hosts=256)
    rec = {"arena": f"q12_mega_{arena.plan.n_tasks}t",
           "n_jobs": arena.n_jobs, "n_tasks": arena.plan.n_tasks}

    t0 = time.perf_counter()
    bm = run_batch(arena, range(4), duration_s=duration, base_spec=SPEC,
                   failover=FAILOVER, phase_mode="pallas")
    rec["e2e_tick"] = {
        "S": 4, "duration_s": duration,
        "wall_s": round(time.perf_counter() - t0, 2),
        "dropped_total": float(np.sum(bm.dropped_by_job))}

    grid = [FailoverConfig(mode="region", region_restart_s=float(r))
            for r in np.linspace(10.0, 60.0, n_configs)]
    t0 = time.perf_counter()
    res = run_config_batch(arena, grid, range(n_seeds),
                           duration_s=duration, base_spec=SPEC,
                           phase_mode="pallas")
    wall = time.perf_counter() - t0
    js = n_configs * n_seeds * arena.n_jobs
    rec["grid"] = {"C": n_configs, "S": n_seeds,
                   "duration_s": duration,
                   "wall_s": round(wall, 2),
                   "job_scenarios": js,
                   "job_scenarios_per_s": round(js / wall, 1),
                   "single_device_pass": True,
                   "n_results": len(res)}
    rec["job_scenarios"] = js
    return rec


def run():
    quick = quick_mode()

    krec = kernel_study(n_tasks=448 if quick else 2016,
                        n_seeds=8 if quick else 32)
    yield (f"phase_kernel_ref_{krec['n_tasks']}t", krec["ref_us"],
           f"D={krec['D']};sb={krec['seed_block']}")
    yield (f"phase_kernel_interp_{krec['n_tasks']}t",
           krec["interpret_us"],
           f"interpret/ref={krec['interpret_us'] / krec['ref_us']:.1f}x")

    erec = engine_study(n_tasks=1008 if quick else 9968,
                        n_seeds=8 if quick else 16,
                        duration=30.0 if quick else 60.0)
    yield (f"tick_pallas_{erec['arena']}", erec["pallas_warm_s"] * 1e6,
           f"S={erec['S']};"
           f"vs_compact={erec['pallas_vs_compact_speedup']}x")

    if not quick:
        mrec = mega_study(n_tasks=100_000, n_configs=4, n_seeds=64,
                          duration=20.0)
        yield (f"mega_grid_{mrec['n_tasks']}t",
               mrec["grid"]["wall_s"] * 1e6,
               f"{mrec['grid']['job_scenarios']}job-scen/pass;"
               f"{mrec['grid']['job_scenarios_per_s']}/s")
        RESULTS.mkdir(exist_ok=True)
        payload = {"kernel": krec, "engine": erec, "mega": mrec,
                   "note": ("kernel: one fused phase, jnp ref vs Pallas "
                            "interpreter (CPU box — compiled Pallas "
                            "needs a TPU); engine: warm run_batch wall, "
                            "compact vs natively-seed-batched pallas "
                            "mode; mega: 100k-task arena, (CxS) grid in "
                            "one run_config_batch device pass")}
        (RESULTS / "bench_tick_kernel.json").write_text(
            json.dumps(payload, indent=2))
        from benchmarks.bench_sweep_scale import write_summary
        write_summary()


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
