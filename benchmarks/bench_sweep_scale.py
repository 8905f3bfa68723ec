"""Sweep-scale benchmark (ISSUE 5 acceptance): sparse-phase tick
throughput and host-free checkpoint-grid sweeps at 10k-task scale.

Three studies:

* **tick** — warm jitted tick throughput of the 10k-task deep-pipeline
  SS mega-arena (6 phases) and the 10k-task Q12 arena, dense vs compact
  lowering (the acceptance bar: >= 2x under compact).
* **ckpt_grid** — a (C=16 restart×interval configs, S=64 seeds)
  checkpoint-bearing resiliency grid through `sweep_configs`, with the
  host-replay baseline (per-(config, seed) `build_chaos_timeline`)
  timed on the same grid; records the `timeline_build_count` delta,
  which MUST be zero on the batched path.
* **shard** — the same config grid on 1 vs all N local devices, in
  this process (skipped where fewer than 2 devices exist).

Emits CSV rows through benchmarks/run.py and writes
``results/bench_sweep_scale.json`` plus the cross-PR aggregate
``results/bench_summary.json``. Quick mode shrinks the arena/grid and
never overwrites the tracked JSONs.
"""
from __future__ import annotations

import json
import pathlib
import time

import jax
import numpy as np

try:
    from benchmarks.run import quick_mode
except ImportError:      # standalone: sys.path[0] is benchmarks/
    from run import quick_mode

from repro.core.chaos import ChaosSpec, timeline_build_count
from repro.streams import nexmark
from repro.streams.chaos_sweep import sweep_configs
from repro.streams.engine import CheckpointConfig, FailoverConfig
from repro.streams.jax_engine import _Lowered, get_cached_run_fns

SPEC = ChaosSpec(host_kill_prob_per_s=0.004, straggler_frac=0.2)
FAILOVER = FailoverConfig(mode="region", region_restart_s=20.0)
RESULTS = pathlib.Path(__file__).resolve().parent.parent / "results"


def tick_study(arena, label: str, n_ticks: int = 64,
               reps: int = 3) -> dict:
    """Warm jitted tick throughput, dense vs compact lowering."""
    rec = {"arena": label, "n_tasks": arena.plan.n_tasks,
           "n_jobs": arena.n_jobs, "n_ticks": n_ticks}
    for mode in ("dense", "compact"):
        low = _Lowered(arena, n_hosts=64, dt=0.5, queue_cap=256.0,
                       failover=FAILOVER, ckpt=None, seed=0,
                       phase_mode=mode)
        rec["n_phases"] = low.tensor.n_phases
        run_fn, _ = get_cached_run_fns(low.desc)
        with jax.enable_x64(True):
            state, xs, _ = low.prepare(SPEC, n_ticks)
            t0 = time.perf_counter()
            out = run_fn(low.arrays, state, xs)
            [np.asarray(v) for v in out[1].values()]
            cold = time.perf_counter() - t0
            times = []
            for _ in range(reps):
                state, xs, _ = low.prepare(SPEC, n_ticks)
                t0 = time.perf_counter()
                out = run_fn(low.arrays, state, xs)
                [np.asarray(v) for v in out[1].values()]
                times.append(time.perf_counter() - t0)
        rec[mode] = {"cold_s": round(cold, 3),
                     "warm_s": round(min(times), 4),
                     "ticks_per_s": round(n_ticks / min(times), 1)}
    rec["warm_speedup"] = round(rec["dense"]["warm_s"]
                                / rec["compact"]["warm_s"], 2)
    return rec


def _ckpt_grid(n_restarts: int, n_intervals: int):
    grid = []
    for r in np.linspace(10.0, 60.0, n_restarts):
        for iv in np.linspace(15.0, 60.0, n_intervals):
            grid.append({"failover": FailoverConfig(
                mode="region", region_restart_s=float(r)),
                "ckpt": CheckpointConfig(interval_s=float(iv),
                                         mode="region"),
                "label": f"r={r:.0f} iv={iv:.0f}"})
    return grid


def ckpt_grid_study(n_restarts: int, n_intervals: int, n_seeds: int,
                    duration: float, n_tasks: int,
                    baseline: bool) -> dict:
    """(C, S) checkpoint-interval grid over a packed Q12 arena: the full
    `sweep_configs` wall (compact tick + batched timeline refit) plus a
    direct timeline-PREP comparison — `core.chaos.build_grid_timelines`
    (one draw stream per seed, vectorized per-config refits) vs the
    pre-ISSUE-5 per-(config, seed) `build_chaos_timeline` host replay
    loop on the identical grid."""
    import dataclasses

    from repro.core.chaos import build_grid_timelines
    from repro.streams.engine import per_task_failover

    arena = nexmark.q12_arena(n_tasks=n_tasks, parallelism=8, n_hosts=32)
    grid = _ckpt_grid(n_restarts, n_intervals)
    spec = ChaosSpec(host_kill_prob_per_s=0.002, straggler_frac=0.2,
                     storage_slow_prob=0.2, storage_slow_factor=12)
    b0 = timeline_build_count()
    res = sweep_configs(arena, grid, range(n_seeds), base_spec=spec,
                        duration_s=duration)
    builds = timeline_build_count() - b0
    rec = {"graph": f"q12_arena_{arena.plan.n_tasks}t",
           "C": len(grid), "S": n_seeds,
           "duration_s": duration, "wall_s": round(res.wall_s, 2),
           "scenarios_per_s": round(res.scenarios_per_s, 1),
           "host_timeline_rebuilds": builds,
           "recovery_p50_s": round(float(np.nanmedian(np.where(
               np.isfinite(res.recovery_surface),
               res.recovery_surface, np.nan))), 2)}
    if baseline:
        low = _Lowered(arena, n_hosts=32, dt=0.5, queue_cap=256.0,
                       failover=FAILOVER, ckpt=None, seed=0)
        n_ticks = int(round(duration / 0.5))
        specs = [dataclasses.replace(spec, seed=s)
                 for s in range(n_seeds)]
        rows = []
        for cfg in grid:
            codes, det, rst_s, rst_r = per_task_failover(
                cfg["failover"], low.plan.n_tasks, low.job_of_task)[:4]
            ck = cfg["ckpt"]
            rows.append(dict(failover_mode=codes, detect_s=det,
                             region_restart_s=rst_r,
                             single_restart_s=rst_s,
                             ckpt_interval_s=ck.interval_s,
                             ckpt_mode=ck.mode,
                             ckpt_upload_s=ck.upload_s,
                             ckpt_retry=ck.retry_failed_region))
        t0 = time.perf_counter()
        build_grid_timelines(specs, rows, n_ticks=n_ticks, dt=0.5,
                             n_hosts=low.n_hosts,
                             task_host=low.task_host,
                             task_region=low.task_region,
                             regions=low.phys.regions,
                             job_of_task=low.job_of_task)
        rec["grid_prep_s"] = round(time.perf_counter() - t0, 2)
        t0 = time.perf_counter()
        for row in rows:
            for sp in specs:
                low.timeline(sp, n_ticks,
                             fo_codes=row["failover_mode"],
                             detect=row["detect_s"],
                             rst_s=row["single_restart_s"],
                             rst_r=row["region_restart_s"],
                             ckpt=CheckpointConfig(
                                 interval_s=row["ckpt_interval_s"],
                                 mode=row["ckpt_mode"],
                                 upload_s=row["ckpt_upload_s"],
                                 retry_failed_region=row["ckpt_retry"]))
        rec["host_replay_baseline_s"] = round(
            time.perf_counter() - t0, 2)
        rec["timeline_refit_speedup"] = round(
            rec["host_replay_baseline_s"]
            / max(rec["grid_prep_s"], 1e-9), 2)
    return rec


def shard_study(n_configs: int, n_seeds: int, duration: float,
                n_tasks: int) -> dict | None:
    """1-vs-N-device sharded (C, S) grid over a packed arena, in this
    process over all N local devices (`devices=` splits the seed axis
    through `jax.shard_map`). None where fewer than 2 devices exist; a
    failing sweep raises and fails the run."""
    n_dev = jax.local_device_count()
    if n_dev < 2:
        return None
    grid = [{"failover": FailoverConfig(mode="region",
                                        region_restart_s=float(r)),
             "ckpt": CheckpointConfig(interval_s=30.0, mode="region")}
            for r in np.linspace(10.0, 60.0, n_configs)]
    spec = ChaosSpec(host_kill_prob_per_s=0.002, straggler_frac=0.2,
                     storage_slow_prob=0.2, storage_slow_factor=12)
    arena = nexmark.q12_arena(n_tasks=n_tasks, parallelism=8, n_hosts=32)
    kw = dict(base_spec=spec, duration_s=duration, n_hosts=32)
    rec = {"C": n_configs, "S": n_seeds, "n_tasks": n_tasks,
           "platform": jax.devices()[0].platform}
    for dev in (None, n_dev):
        sweep_configs(arena, grid, range(n_seeds), devices=dev, **kw)
        res = sweep_configs(arena, grid, range(n_seeds), devices=dev, **kw)
        rec[f"devices_{dev or 1}"] = {
            "wall_s": round(res.wall_s, 2),
            "scenarios_per_s": round(res.scenarios_per_s, 1)}
    rec["shard_speedup"] = round(rec["devices_1"]["wall_s"]
                                 / rec[f"devices_{n_dev}"]["wall_s"], 2)
    return rec


def write_summary() -> dict:
    """Cross-PR perf trajectory: one machine-readable summary pulling
    the headline derived metric out of every tracked results JSON."""
    summary = {}
    for f in sorted(RESULTS.glob("*.json")):
        if f.name == "bench_summary.json":
            continue
        try:
            summary[f.stem] = json.loads(f.read_text())
        except (OSError, json.JSONDecodeError):
            continue
    heads = {}
    c = summary.get("bench_compile", {})
    if c.get("compile"):
        heads["compile_speedup_10k"] = c["compile"][-1].get(
            "compile_speedup")
    s = summary.get("bench_sweep_scale", {})
    for t in s.get("tick", []):
        heads[f"tick_speedup_{t['arena']}"] = t.get("warm_speedup")
    if s.get("ckpt_grid"):
        heads["grid_scenarios_per_s"] = s["ckpt_grid"].get(
            "scenarios_per_s")
        heads["timeline_refit_speedup"] = s["ckpt_grid"].get(
            "timeline_refit_speedup")
    col = summary.get("bench_colocation", {})
    if isinstance(col, dict) and "speedup_vs_separate" in col:
        heads["colocation_speedup"] = col["speedup_vs_separate"]
        heads["colocation_scenarios_per_s"] = col.get("scenarios_per_s")
    tk = summary.get("bench_tick_kernel", {})
    if tk.get("engine"):
        heads["pallas_tick_speedup"] = tk["engine"].get(
            "pallas_vs_compact_speedup")
    if tk.get("mega"):
        heads["mega_job_scenarios_per_pass"] = tk["mega"].get(
            "job_scenarios")
    sv = summary.get("bench_serve", {})
    if isinstance(sv, dict) and "ttfr_speedup" in sv:
        heads["serve_ttfr_speedup"] = sv["ttfr_speedup"]
        heads["serve_overlap_efficiency"] = sv.get("overlap_efficiency")
        heads["serve_requests_per_s"] = sv.get("requests_per_s")
        heads["serve_shared_trace"] = sv.get("shared_trace")
    payload = {"headlines": heads, "sources": sorted(summary)}
    (RESULTS / "bench_summary.json").write_text(
        json.dumps(payload, indent=2))
    return heads


def run():
    quick = quick_mode()
    if quick:
        arenas = [(nexmark.ss_arena(n_tasks=1008, parallelism=8,
                                    n_hosts=32), "ss_1k")]
        grid_dims, n_seeds, duration, grid_tasks = (2, 2), 8, 60.0, 504
    else:
        arenas = [(nexmark.ss_arena(n_tasks=9968, parallelism=8,
                                    n_hosts=64), "ss_10k"),
                  (nexmark.q12_arena(n_tasks=9984, parallelism=8,
                                     n_hosts=64), "q12_10k")]
        grid_dims, n_seeds, duration, grid_tasks = (4, 4), 64, 120.0, 1008

    ticks = []
    for arena, label in arenas:
        rec = tick_study(arena, label)
        ticks.append(rec)
        yield (f"tick_compact_{label}",
               rec["compact"]["warm_s"] * 1e6 / rec["n_ticks"],
               f"{rec['compact']['ticks_per_s']}t/s;"
               f"speedup={rec['warm_speedup']}x")

    grid_rec = ckpt_grid_study(*grid_dims, n_seeds, duration,
                               grid_tasks, baseline=not quick)
    derived = (f"{grid_rec['scenarios_per_s']}scen/s;"
               f"rebuilds={grid_rec['host_timeline_rebuilds']}")
    if "timeline_refit_speedup" in grid_rec:
        derived += f";refit={grid_rec['timeline_refit_speedup']}x"
    yield (f"ckpt_grid_{grid_rec['C']}x{grid_rec['S']}",
           grid_rec["wall_s"] * 1e6, derived)

    shard_rec = None
    if not quick:
        shard_rec = shard_study(4, 64, 120.0, 1008)
        if shard_rec is not None:
            n_dev = jax.local_device_count()
            yield (f"config_shard_{n_dev}dev",
                   shard_rec[f"devices_{n_dev}"]["wall_s"] * 1e6,
                   f"speedup={shard_rec['shard_speedup']}x")
        RESULTS.mkdir(exist_ok=True)
        payload = {"tick": ticks, "ckpt_grid": grid_rec,
                   "shard": shard_rec,
                   "note": ("tick: warm jitted scan of one chaos run, "
                            "dense vs compact phase lowering; ckpt_grid:"
                            " grid_prep_s = build_grid_timelines (one "
                            "draw stream per seed, per-config refits), "
                            "baseline = per-(config,seed) "
                            "build_chaos_timeline host replays; shard: "
                            "1 vs all local devices in one process "
                            "(null with fewer than 2 devices)")}
        (RESULTS / "bench_sweep_scale.json").write_text(
            json.dumps(payload, indent=2))
        write_summary()


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
