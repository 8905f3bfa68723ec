"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (derived = the reproduced headline
metric of that table/figure).

``--quick`` runs a fast smoke subset (sets REPRO_BENCH_QUICK=1, which
modules may honor to shrink their workloads) — used by scripts/ci.sh.
Quick mode must NOT overwrite the tracked ``results/*.json`` perf
records (they are the full-size measurements of record): modules guard
their JSON writes with `quick_mode()`.
"""
from __future__ import annotations

import os
import sys
import traceback


def quick_mode() -> bool:
    """Shared REPRO_BENCH_QUICK parse — one truthiness rule for every
    benchmark module."""
    return os.environ.get("REPRO_BENCH_QUICK", "") not in ("", "0")

MODULES = [
    "benchmarks.bench_startup",             # Table II + Fig 5
    "benchmarks.bench_adaptive_shuffle",    # Fig 6
    "benchmarks.bench_autoscaling",         # Fig 7
    "benchmarks.bench_region_ckpt",         # Fig 8
    "benchmarks.bench_single_task_recovery",  # Fig 9
    "benchmarks.bench_weakhash",            # §III-A WeakHash
    "benchmarks.bench_hotupdate",           # §III-C HotUpdate
    "benchmarks.bench_lazyload",            # §III-B State LazyLoad
    "benchmarks.bench_engine",              # stream-engine hot path
    "benchmarks.bench_chaos_sweep",         # vmapped jit chaos sweeps
    "benchmarks.bench_colocation",          # multi-job mega-arena sweeps
    "benchmarks.bench_compile",             # tensorized-tick compile cost
    "benchmarks.bench_sweep_scale",         # sparse-phase + sharded grids
    "benchmarks.bench_tick_kernel",         # fused Pallas tick phases
    "benchmarks.bench_replication",         # §IV-A hybrid replication cube
    "benchmarks.bench_deployment",          # canary/rolling deployment drills
    "benchmarks.bench_traffic",             # traffic dynamics + DS2 autoscaling
    "benchmarks.bench_serve",               # sweep-as-a-service TTFR + throughput
    "benchmarks.bench_kernels",             # §V-C micro benchmarking
]

QUICK_MODULES = [
    "benchmarks.bench_engine",              # vectorized vs reference engine
    "benchmarks.bench_chaos_sweep",         # vmapped jit chaos sweeps
    "benchmarks.bench_colocation",          # multi-job mega-arena sweeps
    "benchmarks.bench_compile",             # tensorized-tick compile cost
    "benchmarks.bench_sweep_scale",         # sparse-phase + sharded grids
    "benchmarks.bench_tick_kernel",         # fused Pallas tick phases
    "benchmarks.bench_replication",         # hybrid replication cube
    "benchmarks.bench_deployment",          # canary/rolling deployment drills
    "benchmarks.bench_traffic",             # traffic dynamics + DS2 autoscaling
    "benchmarks.bench_serve",               # sweep-as-a-service TTFR + throughput
    "benchmarks.bench_weakhash",            # WeakHash assignment path
    "benchmarks.bench_hotupdate",           # pure-python, fast
]


def main() -> None:
    import importlib

    from repro.core.hotupdate import enable_persistent_cache

    enable_persistent_cache()
    quick = "--quick" in sys.argv[1:]
    if quick:
        os.environ["REPRO_BENCH_QUICK"] = "1"
    print("name,us_per_call,derived")
    failed: list[tuple[str, str, str]] = []
    for mod_name in (QUICK_MODULES if quick else MODULES):
        # step the generator explicitly: a bench that dies mid-module
        # keeps the rows it already produced, the failure row names the
        # exact bench (module + last completed row), and the remaining
        # modules still run
        last = "<import>"
        try:
            it = iter(importlib.import_module(mod_name).run())
        except Exception:
            failed.append((mod_name, last, traceback.format_exc(limit=2)))
            print(f"{mod_name},ERROR,import/setup failed", flush=True)
            continue
        while True:
            try:
                name, us, derived = next(it)
            except StopIteration:
                break
            except Exception:
                failed.append((mod_name, last,
                               traceback.format_exc(limit=2)))
                print(f"{mod_name},ERROR,failed after row {last!r}",
                      flush=True)
                break
            print(f"{name},{us:.1f},{derived}", flush=True)
            last = name
    if failed:
        print(f"\n{len(failed)} bench module(s) FAILED:", file=sys.stderr)
        for mod_name, last, tb in failed:
            print(f"--- {mod_name} (after row {last!r})\n{tb}",
                  file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
