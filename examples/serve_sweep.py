"""Sweep-as-a-service smoke: boot `repro.launch.serve.SweepService`
in-process, fire two concurrent deployment-drill requests plus a
traffic-dynamics sweep at it, and consume incremental chunk results as
they land.

Demonstrates the service contract end to end:

- **Incremental results** — each request's (C, S_chunk) partial
  surfaces stream out per seed-chunk; the first chunk of the first
  drill lands while the slowest request is still running
  (time-to-first-result instead of time-to-last).
- **One shared jit cache** — the two drill requests have the same plan
  digest / grid shape / pow2 seed bucket, so the second rides the
  first's compiled trace: the script FAILS (non-zero exit) unless the
  requests record at least one trace-cache hit between them.
- **Chunk parity** — the chunked service cube is compared bit-for-bit
  against a monolithic in-process `deployment_drill` call; any drift
  exits non-zero.

    PYTHONPATH=src python examples/serve_sweep.py
    PYTHONPATH=src python examples/serve_sweep.py --seeds 16 --chunk 8

scripts/ci.sh --serve-smoke runs this script.
"""
import argparse


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seeds", type=int, default=8,
                    help="chaos seeds per request")
    ap.add_argument("--chunk", type=int, default=4,
                    help="seeds per device pass (chunk size)")
    ap.add_argument("--duration", type=float, default=60.0,
                    help="simulated horizon per scenario (seconds)")
    args = ap.parse_args()

    import json
    import math
    import sys
    import threading
    import time

    import numpy as np

    from repro.core.chaos import ChaosSpec
    from repro.launch.serve import SweepService
    from repro.streams import nexmark
    from repro.streams.chaos_sweep import deployment_drill
    from repro.streams.engine import (AutoscaleConfig, CheckpointConfig,
                                      FailoverConfig, UpgradeConfig)

    failures: list[str] = []

    def check(ok: bool, msg: str) -> None:
        tag = "ok" if ok else "FAIL"
        print(f"  [{tag}] {msg}")
        if not ok:
            failures.append(msg)

    g = nexmark.q2(parallelism=4)
    seeds = range(args.seeds)
    base = ChaosSpec(host_kill_prob_per_s=0.001,
                     zk_down=((30.0, 34.0),),
                     hdfs_down=((32.0, 38.0),))
    fo = FailoverConfig(mode="single_task", detect_s=1.0,
                        single_restart_s=2.0)
    ckpt = CheckpointConfig(interval_s=10.0)
    drill_kw = dict(
        base_spec=base, duration_s=args.duration,
        policies={"hot": UpgradeConfig(t_upgrade_s=args.duration * 0.4,
                                       wave_stagger_s=2.0),
                  "cold": UpgradeConfig(t_upgrade_s=args.duration * 0.4,
                                        wave_stagger_s=2.0, hot=False)},
        canary_fracs=(0.25, 0.5),
        rollback_thresholds=(math.inf, 200.0),
        failover=fo, ckpt=ckpt, n_hosts=8)
    traffic_kw = dict(
        base_spec=ChaosSpec(host_kill_prob_per_s=0.002),
        duration_s=args.duration,
        scalers={"frozen": None,
                 "ds2": AutoscaleConfig(interval_s=5.0, cooldown_s=10.0)},
        traffics={"diurnal": {"diurnal": ((0.35, 240.0, 0.0),)}},
        failovers={"region": FailoverConfig(mode="region", detect_s=1.0)},
        ckpt=ckpt, n_hosts=8)

    print(f"== monolithic reference: (C=8, S={args.seeds}) drill cube ==")
    ref = deployment_drill(g, seeds, **drill_kw)
    print(f"  wall={ref.grid.wall_s:.2f}s  "
          f"({ref.grid.scenarios_per_s:.1f} scenarios/s)")

    print(f"== service: 2 drill requests + 1 traffic sweep, "
          f"chunk={args.chunk} ==")
    t0 = time.perf_counter()
    first_chunk_s: dict[int, float] = {}
    done_s: dict[int, float] = {}

    with SweepService(workers=2, default_seed_chunk=args.chunk) as svc:
        jobs = [
            svc.submit("deployment_drill", g, seeds, label="drill-a",
                       **drill_kw),
            svc.submit("deployment_drill", g, seeds, label="drill-b",
                       **drill_kw),
            svc.submit("traffic_sweep", nexmark.q3(), seeds,
                       label="traffic", **traffic_kw),
        ]

        def watch(job):
            for chunk in job.chunks(timeout=900):
                now = time.perf_counter() - t0
                first_chunk_s.setdefault(job.id, now)
                print(f"  [{job.request.label}] chunk {chunk.index}: "
                      f"seeds [{chunk.seed_lo}, {chunk.seed_hi}) "
                      f"device={chunk.device_s * 1e3:.0f}ms "
                      f"fetch={chunk.fetch_s * 1e3:.0f}ms  t={now:.2f}s")
            done_s[job.id] = time.perf_counter() - t0

        watchers = [threading.Thread(target=watch, args=(j,))
                    for j in jobs]
        for w in watchers:
            w.start()
        results = [j.result(timeout=900) for j in jobs]
        for w in watchers:
            w.join(900)
        stats = svc.stats()

    print("== assertions ==")
    check(len(first_chunk_s) == len(jobs) == len(done_s),
          "every request streamed at least one chunk")
    first, slowest = min(first_chunk_s.values()), max(done_s.values())
    check(first < slowest,
          f"first chunk ({first:.2f}s) landed before the slowest "
          f"request completed ({slowest:.2f}s)")
    check(stats["cache_hits"] >= 1,
          f"requests shared a compiled trace "
          f"(cache hits={stats['cache_hits']}, "
          f"misses={stats['cache_misses']})")
    drift = [name for name in ("recovery", "slo", "lost", "rollback_t")
             if not np.array_equal(getattr(ref, name),
                                   getattr(results[0], name))]
    check(not drift,
          "chunked service cube is bit-identical to the monolithic "
          f"call{'' if not drift else f' (drifted: {drift})'}")
    check(np.array_equal(results[0].recovery, results[1].recovery),
          "the two drill requests returned identical cubes")
    check(results[2].slo.shape[-1] == args.seeds,
          "traffic sweep returned a full cube")
    for j in jobs:
        js = stats["jobs"][j.id]
        print(f"  [{js['label']}] state={js['state']} "
              f"chunks={js['chunks']} ttfr={js['ttfr_s']:.2f}s "
              f"wall={js['wall_s']:.2f}s prep={js['prep_s'] * 1e3:.0f}ms "
              f"device={js['device_s'] * 1e3:.0f}ms "
              f"fetch={js['fetch_s'] * 1e3:.0f}ms "
              f"hits={js['cache_hits']} misses={js['cache_misses']}")

    print(json.dumps({"trace_cache": stats["trace_cache"],
                      "cache_hits": stats["cache_hits"],
                      "cache_misses": stats["cache_misses"],
                      "completed": stats["completed"]}))
    if failures:
        print(f"SERVE SMOKE FAILED: {failures}")
        sys.exit(1)
    print("serve smoke OK")


if __name__ == "__main__":
    main()
