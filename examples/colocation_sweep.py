"""Multi-job co-location chaos sweep (the paper's cluster perspective):
pack K jobs onto ONE shared host pool and sweep failure seeds over the
whole fleet in a single device call per shard — host kills couple every
co-located job's recovery, and the sweep reports per-job breakdowns.

    PYTHONPATH=src python examples/colocation_sweep.py                # 4 jobs, 256 seeds
    PYTHONPATH=src python examples/colocation_sweep.py --seeds 16 --duration 60
    PYTHONPATH=src python examples/colocation_sweep.py --devices 4    # sharded seed batch

``--devices N`` (> 1) forces N host devices (must be set before jax
initializes, which this script handles) and splits the seed batch across
them via `jax.shard_map` (`repro.dist.sharding`).
"""
import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--jobs", type=int, default=4, choices=range(2, 5),
                    help="co-located jobs packed into the arena")
    ap.add_argument("--seeds", type=int, default=256,
                    help="failure seeds (padded to the next power of two)")
    ap.add_argument("--duration", type=float, default=120.0,
                    help="simulated horizon per scenario (seconds)")
    ap.add_argument("--hosts", type=int, default=8,
                    help="shared host pool size")
    ap.add_argument("--devices", type=int, default=1,
                    help="device shards for the seed batch (>1 forces "
                         "host devices)")
    args = ap.parse_args()

    if args.devices > 1:   # before jax initializes
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.devices}").strip()

    from repro.core.chaos import ChaosSpec
    from repro.dist.sharding import local_shard_count
    from repro.streams import nexmark
    from repro.streams.chaos_sweep import sweep
    from repro.streams.engine import (CheckpointConfig, FailoverConfig,
                                      pack_arena)

    graphs = [nexmark.q2(parallelism=8, partitioner="weakhash",
                         n_groups=4, service_rate=1.1e5),
              nexmark.q12(parallelism=8, service_rate=2.4e5),
              nexmark.ds(parallelism=6),
              nexmark.ss(parallelism=4)][:args.jobs]
    arena = pack_arena(graphs, "shared", n_hosts=args.hosts)
    base = ChaosSpec(host_kill_prob_per_s=0.004, straggler_frac=0.2,
                     storage_slow_prob=0.1)
    res = sweep(arena, range(args.seeds), base_spec=base,
                duration_s=args.duration,
                failover=FailoverConfig(mode="region",
                                        region_restart_s=20.0),
                ckpt=CheckpointConfig(interval_s=30.0, mode="region"),
                devices=(args.devices if args.devices > 1 else None))
    agg = res.aggregate()
    # report the shard count actually used, not the one requested (the
    # device forcing is best-effort when XLA_FLAGS was already set)
    n_dev = local_shard_count(args.devices if args.devices > 1 else None)
    print(f"== {arena.n_jobs} co-located jobs on {args.hosts} hosts: "
          f"{agg['scenarios']} seeds x {res.n_ticks} ticks in "
          f"{res.wall_s:.2f}s ({agg['scenarios_per_s']:.0f} scenarios/s, "
          f"{n_dev} device shard{'s' if n_dev > 1 else ''}) ==")
    print(f"  fleet: failures in {agg['failed_scenarios']} scenarios "
          f"(unrecovered: {agg['unrecovered']}), peak backlog "
          f"{agg['max_backlog']:.2e} rec")
    for name, jr in res.job_results.items():
        ja = jr.aggregate()
        print(f"  {name:<22s} recovery p50/p95 "
              f"{ja['recovery_p50_s']:6.1f}/{ja['recovery_p95_s']:6.1f} s"
              f"  SLO-viol p95 {ja['slo_violation_frac_p95']:.3f}"
              f"  dropped {ja['dropped_total']:.0f}")


if __name__ == "__main__":
    main()
