"""Chip smoke test: the release-gate sweep service on one TPU.

Starts `repro.launch.serve.SweepService` and sends it the paper's
hybrid-replication cube (`chaos_sweep.replication_tradeoff`) over the
10k-task Nexmark Q12 fleet (`nexmark.q12_arena()`: 416 packed Q12 jobs
on 64 hosts) under the HA drill (`nexmark.ha_drill_spec`: a region
burst, a storage brownout and an MQ outage) plus sparse Poisson host
kills, so that seeds differ. The grid is 3 failover modes x 2
checkpoint intervals x 2 brownouts (C=12) over 32 seeds and a 180 s
horizon. The same request is sent twice, cold and warm. Checks:

- the cube has its full (C, S) shape, every scenario saw the drill's
  burst, every surface is finite, and so is the hot-standby recovery
  time of every scenario whose only failure is the drill's burst (a
  standby takes over in under a second and loses no state; the passive
  rows' recovery times may run past the horizon);
- the chunked service cube equals a monolithic in-process call bit for
  bit, over the first 16 seeds (XLA's memory analysis for a v5e puts one
  pass over all 32 at 14.5 GB of the chip's 16 GB, 16 seeds at 12.3 GB
  and a chunk of 8 at 6.4);
- the warm request hits the trace cache, misses nothing and rebuilds
  no host timeline;
- the numpy `StreamEngine` agrees within 1e-5 relative on emitted,
  dropped and max backlog for two (config, seed) cells.

``--four-chips`` runs only the same cube with ``devices=4`` and on one
chip in the same process, and compares them bit for bit.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # four chips

Exits non-zero, printing no result line, where JAX finds no TPU. The
last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import threading
import time

REPO = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

#: the full-size cell: 10k tasks, C=12 configs x S=32 seeds, 180 s
FULL = dict(n_tasks=10_000, n_seeds=32, horizon_s=180.0, seed_chunk=8)
#: seeds of the one-pass reference call
MONO_SEEDS = 16
#: Poisson host kills on top of the drill, so seeds differ: about one
#: kill per 180 s run over 64 hosts, none at all in a third of the seeds
KILL_PROB_PER_S = 1e-4
#: a config-level brownout tent over the drill's burst (t = 60 s)
BROWNOUTS = ((), ((40.0, 120.0, 4.0),))
RTOL = 1e-5
STATE_BYTES = 8 << 30            # 8 GiB of keyed window state per job


def _failovers() -> dict:
    # single_task passive restore (γ=partial: records routed to the dead
    # task are dropped → lost work) vs region passive with lazy-load
    # ready stagger vs hot standby. The 5s region redeploy keeps that
    # row's downtime dominated by the brownout-inflated restore +
    # ckpt-age replay terms the cube sweeps.
    from repro.core.replication import TimingModel
    from repro.streams.engine import FailoverConfig

    timing = TimingModel()
    return {
        "hot_standby": FailoverConfig.from_replication(
            timing, mode="hot_standby"),
        "passive": FailoverConfig.from_replication(
            timing, mode="single_task", state_bytes=STATE_BYTES),
        "passive_lazy": dataclasses.replace(
            FailoverConfig.from_replication(timing, mode="region",
                                            state_bytes=STATE_BYTES),
            region_restart_s=5.0, lazyload_stagger_s=1.0),
    }


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's
    monitoring events (which fire in whichever thread compiles)."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                with self._lock:
                    self.compile_s += secs
                    self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                with self._lock:
                    self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        with self._lock:
            return {"compile_s": self.compile_s, "compiles": self.compiles,
                    "persistent_cache_hits": self.cache_hits}


def request(n_tasks: int, n_seeds: int, horizon_s: float):
    """(arena, seeds, driver kwargs) of the replication cube request."""
    from repro.streams import nexmark

    arena = nexmark.q12_arena(n_tasks=n_tasks)
    kw = dict(base_spec=nexmark.ha_drill_spec(
                  host_kill_prob_per_s=KILL_PROB_PER_S),
              duration_s=horizon_s, failovers=_failovers(),
              ckpt_intervals=(10.0, 30.0), brownouts=BROWNOUTS)
    return arena, list(range(n_seeds)), kw


def surfaces(cube) -> dict:
    g = cube.grid
    return {"recovery": g.recovery_surface, "slo": g.slo_surface,
            "backlog": g.backlog_surface, "lost": g.lost_surface}


def bit_identical(a, b, n_seeds: int | None = None) -> list[str]:
    """Names of the surfaces that differ between cubes `a` and `b` (over
    their first `n_seeds` seeds)."""
    import numpy as np

    sa, sb = surfaces(a), surfaces(b)
    return [k for k in sa
            if not np.array_equal(sa[k][:, :n_seeds], sb[k][:, :n_seeds])]


def served(svc, arena, seeds, kw, log: CompileLog, seed_chunk: int,
           label: str, **extra):
    """Submit one request, wait for it, and print its telemetry."""
    from repro.core.chaos import timeline_build_count

    c0, b0 = log.snapshot(), timeline_build_count()
    t0 = time.perf_counter()
    job = svc.submit("replication_tradeoff", arena, seeds,
                     seed_chunk=seed_chunk, label=label, **kw, **extra)
    cube = job.result()
    wall = time.perf_counter() - t0
    c1 = log.snapshot()
    st = dict(job.stats)
    rec = {"label": label, "phase_mode": st["phase_mode"],
           "wall_s": wall,
           "compile_s": c1["compile_s"] - c0["compile_s"],
           "compiles": c1["compiles"] - c0["compiles"],
           "persistent_cache_hits": (c1["persistent_cache_hits"]
                                     - c0["persistent_cache_hits"]),
           "ttfr_s": st["ttfr_s"], "prep_s": st["prep_s"],
           "device_s": st["device_s"], "chunks": st["chunks"],
           "trace_cache_hits": st["cache_hits"],
           "trace_cache_misses": st["cache_misses"],
           "timeline_builds": timeline_build_count() - b0}
    print(f"request {json.dumps(rec)}", flush=True)
    return cube, rec


def numpy_cell(arena, kw, cfg: dict, seed: int) -> dict:
    """Emitted, dropped and max backlog of one (config, seed) cell on
    the host numpy engine, with the cube's config and spec."""
    import numpy as np

    from repro.core.chaos import ChaosEngine
    from repro.streams.engine import StreamEngine

    spec = dataclasses.replace(kw["base_spec"], seed=seed)
    spec = dataclasses.replace(
        spec, brownout_at=tuple(spec.brownout_at) + tuple(cfg["brownout"]))
    eng = StreamEngine(arena, chaos=ChaosEngine(spec),
                       failover=cfg["failover"], ckpt=cfg["ckpt"])
    m = eng.run(kw["duration_s"])
    backlog = np.sum([np.asarray(v) for v in m.backlog.values()], axis=0)
    return {"emitted": float(m.emitted), "dropped": float(m.dropped),
            "max_backlog": float(backlog.max())}


def smoke(n_tasks: int, n_seeds: int, horizon_s: float,
          seed_chunk: int, cells=((5, 1), (10, 2))) -> list[str]:
    """The one-chip phase; returns the failed checks (empty = pass).
    `cells` are the (config row, seed) pairs checked against numpy."""
    import numpy as np

    from repro.launch.serve import SweepService
    from repro.streams.chaos_sweep import replication_tradeoff

    failures: list[str] = []

    def check(ok: bool, msg: str) -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {msg}", flush=True)
        if not ok:
            failures.append(msg)

    log = CompileLog()
    arena, seeds, kw = request(n_tasks, n_seeds, horizon_s)
    print(f"arena: {arena.plan.n_tasks} tasks, {arena.n_jobs} jobs, "
          f"{arena.n_hosts} hosts; S={n_seeds}, horizon={horizon_s}s, "
          f"seed_chunk={seed_chunk}", flush=True)
    with SweepService(workers=1) as svc:
        print(f"compile cache: {svc.cache_dir}", flush=True)
        cold, rc = served(svc, arena, seeds, kw, log, seed_chunk, "cold")
        warm, rw = served(svc, arena, seeds, kw, log, seed_chunk, "warm")
    n_mono = min(MONO_SEEDS, n_seeds)
    c0 = log.snapshot()
    t0 = time.perf_counter()
    mono = replication_tradeoff(arena, seeds[:n_mono], **kw)
    print(f"monolithic call over {n_mono} seeds: "
          f"{time.perf_counter() - t0:.3f}s, compile "
          f"{log.snapshot()['compile_s'] - c0['compile_s']:.3f}s",
          flush=True)

    g = cold.grid
    n_cfg = len(g.configs)
    check(g.recovery_surface.shape == (n_cfg, n_seeds) and n_cfg == 12
          and cold.recovery.shape == (3, 2, 2, n_seeds),
          f"cube has its full shape (C={n_cfg}, S={n_seeds})")
    n_fail = np.array([[s.n_failures for s in r.summaries]
                       for r in g.results])
    check(bool((n_fail >= 1).all()), "every scenario saw the drill's burst")
    check(all(np.isfinite(v).all() for k, v in surfaces(cold).items()
              if k != "recovery"),
          "slo, backlog and lost surfaces are finite")
    check(not np.isnan(g.recovery_surface).any(), "no NaN recovery time")
    burst_t = kw["base_spec"].burst_at[0][0]
    drill_only = np.array([[all(abs(r["t"] - burst_t) < 1.0 for r in recs)
                            for recs in r.batch.recoveries]
                           for r in g.results])
    hot = [c for c, cfg in enumerate(g.configs)
           if cfg["failover"].mode == "hot_standby"]
    check(drill_only[hot].any() and bool(np.isfinite(
              g.recovery_surface[hot][drill_only[hot]]).all()),
          f"hot-standby recovery is finite in all "
          f"{int(drill_only[hot].sum())} scenarios whose only failure is "
          f"the drill's burst")
    drift = bit_identical(cold, mono, n_mono)
    check(not drift, f"chunked service cube == monolithic call bit for "
                     f"bit over seeds 0..{n_mono - 1}"
          + (f" (drifted: {drift})" if drift else ""))
    check(not bit_identical(cold, warm), "warm request == cold request")
    check(rw["trace_cache_hits"] >= 1 and rw["trace_cache_misses"] == 0,
          f"warm request reused the trace (hits={rw['trace_cache_hits']}, "
          f"misses={rw['trace_cache_misses']})")
    check(rw["timeline_builds"] == 0,
          f"warm request rebuilt no host timeline "
          f"({rw['timeline_builds']} builds)")
    for c, s in cells:
        t0 = time.perf_counter()
        ref = numpy_cell(arena, kw, g.configs[c], seeds[s])
        summ = g.results[c].summaries[s]
        got = {"emitted": summ.emitted, "dropped": summ.dropped,
               "max_backlog": summ.max_backlog}
        bad = {k: (got[k], ref[k]) for k in ref
               if abs(got[k] - ref[k]) > RTOL * max(abs(ref[k]), 1.0)}
        check(not bad, f"numpy engine agrees on cell ({g.labels[c]}, "
                       f"seed {seeds[s]}) within {RTOL:g} "
                       f"({time.perf_counter() - t0:.1f}s)"
              + (f": {bad}" if bad else ""))
    return failures


def four_chips(n_tasks: int, n_seeds: int, horizon_s: float,
               seed_chunk: int, n_devices: int = 4) -> list[str]:
    """The same cube with ``devices=n_devices`` and on one chip, in one
    process; returns the failed checks."""
    from repro.launch.serve import SweepService

    log = CompileLog()
    arena, seeds, kw = request(n_tasks, n_seeds, horizon_s)
    with SweepService(workers=1) as svc:
        one, _ = served(svc, arena, seeds, kw, log, seed_chunk, "1chip")
        many, _ = served(svc, arena, seeds, kw, log, seed_chunk,
                         f"{n_devices}chips", devices=n_devices)
    drift = bit_identical(one, many)
    print(f"  [{'FAIL' if drift else 'ok'}] devices={n_devices} cube == "
          f"one-chip cube bit for bit"
          + (f" (drifted: {drift})" if drift else ""), flush=True)
    return [f"four-chip drift: {drift}"] if drift else []


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="compare the devices=4 cube with the one-chip "
                         "cube, and run nothing else")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    n_dev = len(jax.devices())
    if args.four_chips and n_dev < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found {n_dev}",
              file=sys.stderr)
        return 2

    from repro.core.hotupdate import enable_persistent_cache
    enable_persistent_cache()

    t0 = time.perf_counter()
    if args.four_chips:
        failures = four_chips(**FULL)
    else:
        failures = smoke(**FULL)
    stats = dev.memory_stats() or {}
    print(f"device peak bytes in use: {stats.get('peak_bytes_in_use')}; "
          f"total {time.perf_counter() - t0:.1f}s", flush=True)
    if failures:
        print(f"chip_smoke FAILED: {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
