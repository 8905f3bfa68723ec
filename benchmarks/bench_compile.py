"""Compile-cost benchmark: trace size and cold-compile time of the
tensorized segment-sum tick vs the legacy unrolled tick, across Q12
mega-arenas of 1k/4k/10k tasks (42/168/416 co-located jobs).

The unrolled tick's jaxpr grows O(ops + edges) — hundreds of jobs make
it untraceable in practice — while the phase-scheduled tensorized tick
keeps a constant op count (the acceptance bar for ISSUE 4). Also runs a
10k-task Q12 (configs × seeds) resiliency sweep through
`chaos_sweep.sweep_configs` to record end-to-end throughput at scale.

Each compiled lowering's record now also carries its XLA cost model —
per-run HLO FLOP/byte estimates through `launch.roofline.kernel_roofline`
(arithmetic intensity + compute/memory bound) and the
`launch.hlo_stats.hlo_op_counts` opcode histogram — alongside the jaxpr
eqn counts, so trace size, emitted op mix, and roofline position travel
together in one record.

Emits the usual CSV rows through benchmarks/run.py and writes
``results/bench_compile.json`` for the perf trajectory. Quick mode
(REPRO_BENCH_QUICK=1) shrinks to one small arena and skips the JSON.
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
from repro.core.chaos import ChaosSpec
from repro.streams import nexmark
from repro.streams.chaos_sweep import sweep_configs
from repro.streams.engine import FailoverConfig
from repro.streams.jax_engine import (_Lowered, _build_run,
                                      build_unrolled_run)

FAILOVER = FailoverConfig(mode="region", region_restart_s=20.0)
SPEC = ChaosSpec(host_kill_prob_per_s=0.004, straggler_frac=0.2)


def count_eqns(jaxpr) -> int:
    """Total equation count of a jaxpr including all sub-jaxprs (scan
    bodies, cond branches, …) — the trace-size metric."""
    from jax.core import ClosedJaxpr, Jaxpr

    def sub(v):
        if isinstance(v, ClosedJaxpr):
            return count_eqns(v.jaxpr)
        if isinstance(v, Jaxpr):
            return count_eqns(v)
        if isinstance(v, (list, tuple)):
            return sum(sub(x) for x in v)
        return 0

    n = 0
    for eq in jaxpr.eqns:
        n += 1
        for v in eq.params.values():
            n += sub(v)
    return n


def _measure(run_fn, arrays, state, xs) -> dict:
    """Trace + cold-compile one run fn AOT; report eqns, seconds, and
    the compiled artifact's cost model: HLO FLOP/byte estimates
    (`launch.hlo_stats.cost_stats`) fed through the chip roofline
    (`launch.roofline.kernel_roofline`) plus the HLO opcode histogram
    (`hlo_op_counts`) — so each lowering's record carries *what XLA
    actually emitted*, not just how long it took."""
    from repro.launch.hlo_stats import cost_stats, hlo_op_counts
    from repro.launch.roofline import V5E, kernel_roofline

    with jax.enable_x64(True):
        t0 = time.perf_counter()
        jaxpr = jax.make_jaxpr(run_fn)(arrays, state, xs)
        trace_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = jax.jit(run_fn).lower(arrays, state, xs).compile()
        compile_s = time.perf_counter() - t0
        cost = cost_stats(compiled)
        roof = kernel_roofline(cost["flops"], cost["bytes_accessed"], V5E)
        ops = hlo_op_counts(compiled.as_text())
        del compiled
    top_ops = dict(sorted(ops.items(), key=lambda kv: -kv[1])[:12])
    return {"eqns": count_eqns(jaxpr.jaxpr),
            "trace_s": round(trace_s, 3),
            "compile_s": round(compile_s, 3),
            "hlo_flops": cost["flops"],
            "hlo_bytes": cost["bytes_accessed"],
            "intensity_flops_per_byte":
                round(roof["intensity_flops_per_byte"], 4),
            "roofline_bound": roof["bound"],
            "hlo_op_total": sum(ops.values()),
            "hlo_top_ops": top_ops}


def compile_study(n_tasks: int, n_ticks: int = 4) -> dict:
    arena = nexmark.q12_arena(n_tasks=n_tasks, parallelism=8, n_hosts=64)
    # pinned to the DENSE lowering: this benchmark's record is the
    # tensorized-vs-unrolled comparison; the compact (sparse-phase)
    # lowering is measured by benchmarks/bench_sweep_scale.py
    low = _Lowered(arena, n_hosts=64, dt=0.5, queue_cap=256.0,
                   failover=FAILOVER, ckpt=None, seed=0,
                   phase_mode="dense")
    state, xs, _ = low.prepare(ChaosSpec(seed=0), n_ticks)
    rec = {"n_tasks": arena.plan.n_tasks, "n_jobs": arena.n_jobs,
           "n_ops": len(arena.plan.ops), "n_phases": low.tensor.n_phases,
           "new": _measure(_build_run(low.desc), low.arrays, state, xs)}
    desc_l, arrays_l = low.legacy()
    rec["old"] = _measure(build_unrolled_run(desc_l), arrays_l, state, xs)
    rec["compile_speedup"] = round(
        (rec["old"]["trace_s"] + rec["old"]["compile_s"])
        / max(rec["new"]["trace_s"] + rec["new"]["compile_s"], 1e-9), 2)
    return rec


def sweep_study(n_tasks: int, n_seeds: int, duration: float) -> dict:
    """10k-task Q12 resiliency sweep: a (configs × seeds) grid in one
    device call on the tensorized tick."""
    arena = nexmark.q12_arena(n_tasks=n_tasks, parallelism=8, n_hosts=64)
    grid = [FailoverConfig(mode="region", region_restart_s=r)
            for r in (15.0, 45.0)]
    res = sweep_configs(arena, grid, range(n_seeds), base_spec=SPEC,
                        duration_s=duration)
    return {"n_tasks": arena.plan.n_tasks, "n_jobs": arena.n_jobs,
            "grid": [f"region_restart={r:g}s" for r in (15.0, 45.0)],
            "n_seeds": n_seeds, "duration_s": duration,
            "wall_s": round(res.wall_s, 2),
            "scenarios_per_s": round(res.scenarios_per_s, 2),
            "recovery_p50_s": [round(r["recovery_p50_s"], 2)
                               for r in res.rows()]}


def run():
    quick = quick_mode()
    sizes = [504] if quick else [1008, 4032, 9984]
    records = []
    for n in sizes:
        rec = compile_study(n)
        records.append(rec)
        yield (f"compile_new_{rec['n_tasks']}t",
               rec["new"]["compile_s"] * 1e6,
               f"eqns={rec['new']['eqns']};"
               f"hlo_ops={rec['new']['hlo_op_total']};"
               f"{rec['new']['roofline_bound']}-bound@"
               f"{rec['new']['intensity_flops_per_byte']}f/B")
        yield (f"compile_old_{rec['n_tasks']}t",
               rec["old"]["compile_s"] * 1e6,
               f"eqns={rec['old']['eqns']};"
               f"hlo_ops={rec['old']['hlo_op_total']};"
               f"speedup={rec['compile_speedup']}x")
    sw = sweep_study(sizes[-1] if quick else 9984,
                     n_seeds=4 if quick else 8,
                     duration=20.0 if quick else 30.0)
    yield (f"q12_sweep_{sw['n_tasks']}t", sw["wall_s"] * 1e6,
           f"{sw['scenarios_per_s']}scen/s")
    if not quick:   # quick smoke must not overwrite the tracked record
        out = pathlib.Path(__file__).resolve().parent.parent / "results"
        out.mkdir(exist_ok=True)
        payload = {"compile": records, "q12_sweep": sw,
                   "note": ("trace+compile of one jitted 4-tick scan; "
                            "eqns = recursive jaxpr equation count")}
        (out / "bench_compile.json").write_text(
            json.dumps(payload, indent=2))


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
