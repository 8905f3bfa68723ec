"""Chaos-sweep driver: batched failure-scenario screening (paper §V-B).

StreamShield's release pipeline validates resiliency by sweeping *many*
injected-failure configurations, not one drill. This driver turns a seed
batch into per-scenario resiliency summaries in a single vmapped `jit`
call of the JAX engine twin (`streams/jax_engine.py`):

    result = sweep(nexmark.q2(parallelism=8), seeds=range(256),
                   base_spec=ChaosSpec(host_kill_prob_per_s=0.002),
                   duration_s=300.0)
    result.summaries[i].recovery_time_s  # per-scenario
    result.aggregate()                   # fleet percentiles

Per scenario it reports recovery time (first post-failure return of
source lag below the SLO threshold), maximum backlog, SLO-violation
tick counts, dropped/emitted records and checkpoint success — the
metrics the paper uses to gate a release.

Cluster-perspective sweeps: pass a `streams.engine.PackedArena` instead
of a graph and the whole co-located fleet (K jobs, shared host pool)
sweeps in the same device call — `SweepResult.job_results` then carries
per-job recovery/SLO breakdowns next to the fleet-level combined
summaries, with shared-host kills coupling the co-located jobs'
recoveries. ``devices=`` shards the seed batch across local devices
(`jax.shard_map` through `repro.dist.sharding`); seed batches are
padded to the next power of two so varying S reuses one jit trace per
bucket. The numpy-engine baseline replay is
opt-in via ``compare_numpy=True`` — production-size sweeps never pay
the single-core replay by default.

Chunked sweeps: every driver (and the cube wrappers forwarding
``**sweep_kw``) takes ``seed_chunk=`` / ``on_chunk=`` — the seed axis
then streams through the engine's double-buffered prep/compute pipeline
and `SweepChunk` partial surfaces are published as each chunk lands
(the `launch.serve.SweepService` incremental-result path), with the
concatenated result bit-identical to the monolithic call. Results carry
the ``prep_s`` / ``device_s`` split and per-request trace-cache hit/miss
counts next to the compat total-derived ``scenarios_per_s``.

Spans: every driver takes ``spans=`` (a `streams.spans.SpanLog`, one per
request; a fresh one when None) and records ``sweep.plan`` (the plan's
construction, with its trace-cache hits and misses), the engine's
per-chunk ``sweep.prep`` / ``sweep.device`` / ``sweep.fetch``,
``sweep.summarize`` per published chunk and ``sweep.assemble`` (chunk
concatenation and the whole-cube summary) into it; every time a result
or chunk carries is read from those spans.
"""
from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from repro.core.chaos import ChaosSpec, RecoveryLog
from repro.streams.engine import (AutoscaleConfig, CheckpointConfig,
                                  FailoverConfig, PackedArena,
                                  UpgradeConfig)
from repro.streams.graph import LogicalGraph
from repro.streams.jax_engine import (JaxBatchMetrics, SeedBatchPlan,
                                      SummaryGridPlan, concat_batches,
                                      concat_config_batches,
                                      normalize_config, run_chunks)
from repro.streams.spans import SpanLog


@dataclasses.dataclass
class ScenarioSummary:
    seed: int
    n_failures: int              # recovery events (host kills that hit)
    recovery_time_s: float       # inf = never recovered, 0 = no SLO breach
    max_backlog: float           # peak total queued records
    max_lag: float               # peak source lag
    slo_threshold: float
    slo_violation_ticks: int
    slo_violation_frac: float
    dropped: float
    emitted: float
    ckpt_attempts: int
    ckpt_success: int


@dataclasses.dataclass
class SweepResult:
    graph_name: str
    duration_s: float
    n_ticks: int
    summaries: list[ScenarioSummary]
    batch: JaxBatchMetrics
    wall_s: float                # end-to-end sweep wall time
    # packed-arena sweeps: per-job breakdown (job name → its own
    # SweepResult over the job's metric segment); None for single jobs
    job_results: dict[str, "SweepResult"] | None = None
    # opt-in numpy cross-check (see sweep(compare_numpy=...)); None unless
    # requested — production sweeps never pay the single-core replay
    numpy_check: dict | None = None
    # time split of the chunked pipeline, summed over chunks from their
    # spans: host-side timeline prep vs the device wait (their sum can
    # exceed `wall_s` when the double-buffered pipeline overlaps them —
    # that gap IS the overlap win). Zero for `summarize`'s own results.
    prep_s: float = 0.0
    device_s: float = 0.0
    # per-request trace-cache traffic of this sweep's jit-fn lookups
    cache_hits: int = 0
    cache_misses: int = 0
    # tick lowering the engine resolved ("dense" | "compact")
    phase_mode: str = ""

    @property
    def total_s(self) -> float:
        """End-to-end wall time (alias of `wall_s` — the denominator of
        the compat `scenarios_per_s`)."""
        return self.wall_s

    @property
    def scenarios_per_s(self) -> float:
        # compat: total-derived (wall_s == total_s), NOT device-only
        return len(self.summaries) / self.wall_s if self.wall_s else 0.0

    def aggregate(self) -> dict:
        """Fleet-level percentiles across the scenario batch."""
        rec = np.array([s.recovery_time_s for s in self.summaries])
        fin = rec[np.isfinite(rec)]
        frac = np.array([s.slo_violation_frac for s in self.summaries])
        return {
            "scenarios": len(self.summaries),
            "failed_scenarios": int(sum(s.n_failures > 0
                                        for s in self.summaries)),
            "unrecovered": int(np.sum(~np.isfinite(rec))),
            "recovery_p50_s": float(np.median(fin)) if len(fin) else 0.0,
            "recovery_p95_s": float(np.percentile(fin, 95))
            if len(fin) else 0.0,
            "recovery_max_s": float(fin.max()) if len(fin) else 0.0,
            "slo_violation_frac_p50": float(np.median(frac)),
            "slo_violation_frac_p95": float(np.percentile(frac, 95)),
            "max_backlog": float(max(s.max_backlog
                                     for s in self.summaries)),
            "dropped_total": float(sum(s.dropped for s in self.summaries)),
            "scenarios_per_s": self.scenarios_per_s,
        }


def _recovery_time(ts: np.ndarray, lag: np.ndarray, down_bk: np.ndarray,
                   recs) -> float:
    """Time from the first failure until the job is healthy again.

    Source lag in this sim is *retained* backlog (sources never re-emit
    requeued records), so "lag returns below an absolute threshold"
    would read as never-recovered for any single-task drill. Healthy is
    therefore: the failover outage window has passed, the per-tick lag
    growth is back at its pre-failure level, and downstream queues have
    drained. inf = still unhealthy at horizon end. `recs` is a
    `RecoveryLog`, or a list of event dicts with ``t`` and
    ``downtime``."""
    if isinstance(recs, RecoveryLog):
        t, down = recs.t, recs.downtime
    else:
        t = np.array([r["t"] for r in recs])
        down = np.array([r["downtime"] for r in recs])
    t_fail = float(t[0])
    outage_end = float((t + down).max())
    pre = ts < t_fail
    dlag = np.diff(lag, prepend=lag[:1])
    # lag growth back at its pre-failure level must not read as growth
    # through rounding: lag reaches ~3e9 records on a 10k-task fleet,
    # where differences of lags carry ~1e-6 of f64 rounding (~1e-3 where
    # a TPU's emulated f64 departs from IEEE f64 by ~3e-13 relative), so
    # the margin scales with the lag
    grow_thr = (float(np.percentile(dlag[pre], 95)) if pre.any()
                else 0.0) + 1e-9 + 1e-12 * float(np.abs(lag).max())
    bk_thr = max(2.0 * (float(np.median(down_bk[pre])) if pre.any()
                        else 0.0), 1.0)
    breach = (ts < outage_end) | (dlag > grow_thr) | (down_bk > bk_thr)
    breach &= ts >= t_fail
    if not breach.any():
        return 0.0
    last = int(np.nonzero(breach)[0][-1])
    if last == len(ts) - 1:
        return math.inf
    return float(ts[last + 1] - t_fail)


def summarize(batch: JaxBatchMetrics, seeds, *,
              slo_lag: float | None = None,
              wall_s: float = 0.0, graph_name: str = "",
              duration_s: float = 0.0) -> SweepResult:
    """Per-scenario resiliency summaries from stacked batch metrics.

    `slo_lag` is the source-lag SLO threshold (records). When None it is
    derived per scenario as 2× the pre-failure steady-state median lag
    (falling back to the whole-run median for failure-free scenarios).
    Recovery watches the batch's downstream backlog (every op but the
    sources), and the peak backlog is that of its per-tick total; both
    series come with the batch, full-history or series-only alike.
    """
    ts = batch.t
    summaries = []
    for i, seed in enumerate(seeds):
        lag = batch.source_lag[i]
        recs = RecoveryLog.of(batch.recoveries[i])
        t_fail = float(recs.t[0]) if len(recs) else None
        down_bk = batch.down_backlog[i]
        if slo_lag is None:
            pre = lag[ts < t_fail] if t_fail is not None else lag
            steady = float(np.median(pre)) if len(pre) else 0.0
            thr = 2.0 * steady + 1e-9
        else:
            thr = slo_lag
        viol = int(np.sum(lag > thr))
        summaries.append(ScenarioSummary(
            seed=int(getattr(seed, "seed", seed)),   # ChaosSpec or int
            n_failures=len(recs),
            recovery_time_s=(_recovery_time(ts, lag, down_bk, recs)
                             if len(recs) else 0.0),
            max_backlog=float(batch.backlog_total[i].max()),
            max_lag=float(lag.max()),
            slo_threshold=thr,
            slo_violation_ticks=viol,
            slo_violation_frac=viol / max(len(ts), 1),
            dropped=float(batch.dropped[i]),
            emitted=float(batch.emitted[i]),
            ckpt_attempts=int(batch.ckpt_attempts[i]),
            ckpt_success=int(batch.ckpt_success[i]),
        ))
    return SweepResult(graph_name, duration_s, len(ts), summaries, batch,
                       wall_s)


@dataclasses.dataclass
class SweepChunk:
    """One landed seed chunk of a chunked sweep — the incremental unit
    `sweep(on_chunk=...)` / `sweep_configs(on_chunk=...)` publish and
    `launch.serve.SweepService` streams to subscribers. Carries the
    partial ``(C, S_chunk)`` surfaces (C = 1 for plain `sweep`) computed
    with exactly the final result's formulas, so concatenating every
    chunk's columns reproduces the full-cube surfaces bit-for-bit."""
    index: int                     # 0-based landing order == seed order
    seed_lo: int
    seed_hi: int                   # half-open [seed_lo, seed_hi)
    seeds: list
    prep_s: float                  # host timeline prep (sweep.prep)
    device_s: float                # device wait alone (sweep.device)
    fetch_s: float                 # device→host copy (sweep.fetch)
    summarize_s: float             # summaries + surfaces (sweep.summarize)
    history_bytes: int             # bytes the copy fetched
    route_entries: int             # entries the pass routed (sweep.device)
    rollbacks: int                 # scenarios whose rollback fired
    summaries: list[list[ScenarioSummary]]   # [C][S_chunk]
    recovery_surface: np.ndarray   # (C, S_chunk)
    slo_surface: np.ndarray
    backlog_surface: np.ndarray
    lost_surface: np.ndarray
    rollback_surface: np.ndarray
    thrash_surface: np.ndarray
    rescale_surface: np.ndarray
    cost_surface: np.ndarray

    @property
    def n_seeds(self) -> int:
        return self.seed_hi - self.seed_lo

    @property
    def total_s(self) -> float:
        return self.prep_s + self.device_s + self.fetch_s


def _chunk_surfaces(batches, results) -> dict:
    """The dense surfaces of a (partial or full) config × seed block,
    computed from per-config `SweepResult`s + raw batches — ONE formula
    set shared by `sweep_configs`' final assembly and the per-chunk
    publisher, so partial surfaces are exact column slices of the final
    ones."""
    n = len(results[0].summaries)
    return dict(
        recovery_surface=np.array([[s.recovery_time_s for s in r.summaries]
                                   for r in results]),
        slo_surface=np.array([[s.slo_violation_frac for s in r.summaries]
                              for r in results]),
        backlog_surface=np.array([[s.max_backlog for s in r.summaries]
                                  for r in results]),
        lost_surface=np.array([[s.dropped for s in r.summaries]
                               for r in results]),
        rollback_surface=np.array([(bm.rollback_t
                                    if bm.rollback_t is not None
                                    else np.full(n, np.inf))
                                   for bm in batches]),
        thrash_surface=np.array([(bm.thrash_t if bm.thrash_t is not None
                                  else np.full(n, np.inf))
                                 for bm in batches]),
        rescale_surface=np.array([(bm.n_rescale
                                   if bm.n_rescale is not None
                                   else np.zeros(n))
                                  for bm in batches]),
        cost_surface=np.array([(bm.resource_s
                                if bm.resource_s is not None
                                else np.zeros(n))
                               for bm in batches]))


def _publish_chunk(on_chunk, index: int, cr, seeds, *, graph, slo_lag,
                   duration_s, spans: SpanLog) -> None:
    """Summarize one engine `ChunkResult` into a `SweepChunk` (in a
    ``sweep.summarize`` span) and hand it to the caller's `on_chunk`
    subscriber."""
    batches = (cr.batches if isinstance(cr.batches, list)
               else [cr.batches])
    chunk_seeds = seeds[cr.seed_lo:cr.seed_hi]
    with spans.span("sweep.summarize", chunk=index,
                    scenarios=len(batches) * len(chunk_seeds)) as sp:
        results = [summarize(bm, chunk_seeds, slo_lag=slo_lag,
                             wall_s=cr.device_s + cr.fetch_s,
                             graph_name=graph.name,
                             duration_s=duration_s) for bm in batches]
        surfaces = _chunk_surfaces(batches, results)
        rollbacks = int(np.isfinite(surfaces["rollback_surface"]).sum())
        sp.count(rollbacks=rollbacks)
    on_chunk(SweepChunk(index=index, seed_lo=cr.seed_lo,
                        seed_hi=cr.seed_hi, seeds=chunk_seeds,
                        prep_s=cr.prep_s, device_s=cr.device_s,
                        fetch_s=cr.fetch_s, summarize_s=sp.seconds,
                        history_bytes=cr.history_bytes,
                        route_entries=cr.route_entries,
                        rollbacks=rollbacks,
                        summaries=[r.summaries for r in results],
                        **surfaces))


def _run_plan(make_plan, seeds, seed_chunk, on_chunk, spans: SpanLog, *,
              graph, slo_lag, duration_s):
    """Build a chunk plan (in a ``sweep.plan`` span) and run its chunks,
    publishing each to `on_chunk`; returns the plan, its `ChunkResult`s
    and the plan span."""
    publish = None
    if on_chunk is not None:
        counter = iter(range(len(seeds) + 1))
        publish = lambda cr: _publish_chunk(                 # noqa: E731
            on_chunk, next(counter), cr, seeds, graph=graph,
            slo_lag=slo_lag, duration_s=duration_s, spans=spans)
    with spans.span("sweep.plan") as planned:
        plan = make_plan()
        planned.count(**plan.cache_info)
    _check_down_cols(plan.low, graph)
    return plan, run_chunks(plan, seed_chunk, publish, spans), planned


def _check_down_cols(low, graph: LogicalGraph) -> None:
    """The batches' downstream backlog leaves out the lowering's source
    columns; they must be exactly `graph`'s source ops, the columns a
    summary of `graph` treats as upstream."""
    src = {o.name for o in graph.ops if o.is_source}
    down = [j for j, n in enumerate(low.op_names) if n not in src]
    if down != np.setdiff1d(np.arange(len(low.op_names)),
                            low.plan.src_cols).tolist():
        raise AssertionError(
            f"the lowering's source columns {list(low.plan.src_cols)} "
            f"are not the source ops of graph {graph.name!r}")


def sweep(graph: LogicalGraph | PackedArena, seeds, *,
          base_spec: ChaosSpec,
          duration_s: float, n_hosts: int = 8, dt: float = 0.5,
          queue_cap: float = 256.0,
          failover: FailoverConfig | None = None,
          ckpt: CheckpointConfig | None = None,
          slo_lag: float | None = None,
          task_speed_override: dict[int, float] | None = None,
          seed: int = 0, pad_seeds: bool = True,
          devices: int | str | None = None,
          phase_mode: str = "auto",
          seed_chunk: int | None = None,
          on_chunk=None,
          spans: SpanLog | None = None,
          compare_numpy: bool = False) -> SweepResult:
    """Sweep `seeds` chaos scenarios over `graph` in one vmapped jit call
    (one call per device shard when `devices` is set).

    `graph` may be a `PackedArena`: the co-located fleet sweeps in the
    same call and the result carries per-job recovery/SLO breakdowns in
    ``job_results`` (keyed by job name) next to the fleet-level combined
    summaries.

    ``seed_chunk`` streams the seed axis through fixed-size chunks on
    the engine's double-buffered pipeline (bit-identical result, see
    `jax_engine.run_batch`); ``on_chunk`` receives a `SweepChunk` with
    the partial surfaces as each chunk lands. The result's ``prep_s`` /
    ``device_s`` carry the host-prep vs device-wait split either way,
    and ``wall_s`` runs from the plan's start to the last chunk's
    landing; ``spans`` receives the request's spans (module docstring).

    ``compare_numpy`` is OPT-IN (default False): the numpy-engine
    baseline replay costs a single-core scenario per checked seed, which
    production-size sweeps must not pay on every call. When True, up to 3
    seeds are re-run on `StreamEngine` and the max absolute source-lag
    deviation is attached as ``numpy_check``.
    """
    seeds = list(seeds)
    logical = graph.graph if isinstance(graph, PackedArena) else graph
    spans = SpanLog() if spans is None else spans
    plan, chunks, planned = _run_plan(
        lambda: SeedBatchPlan(graph, seeds, base_spec=base_spec,
                              duration_s=duration_s, n_hosts=n_hosts,
                              dt=dt, queue_cap=queue_cap,
                              failover=failover, ckpt=ckpt,
                              task_speed_override=task_speed_override,
                              seed=seed, pad_seeds=pad_seeds,
                              devices=devices, phase_mode=phase_mode),
        seeds, seed_chunk, on_chunk, spans, graph=logical,
        slo_lag=slo_lag, duration_s=duration_s)
    with spans.span("sweep.assemble", scenarios=len(seeds)) as asm:
        wall = asm.start - planned.start
        batch = concat_batches([c.batches for c in chunks])
        res = summarize(batch, seeds, slo_lag=slo_lag,
                        wall_s=wall, graph_name=logical.name,
                        duration_s=duration_s)
        if isinstance(graph, PackedArena) and batch.jobs:
            res.job_results = {
                job.name: summarize(batch.job_view(job), seeds,
                                    slo_lag=slo_lag,
                                    wall_s=wall, graph_name=job.name,
                                    duration_s=duration_s)
                for job in batch.jobs}
    res.prep_s = sum(c.prep_s for c in chunks)
    res.device_s = sum(c.device_s for c in chunks)
    res.cache_hits = plan.cache_info["hits"]
    res.cache_misses = plan.cache_info["misses"]
    res.phase_mode = plan.low.tensor.mode
    if compare_numpy:
        res.numpy_check = _numpy_check(graph, seeds, batch,
                                       base_spec=base_spec,
                                       duration_s=duration_s,
                                       n_hosts=n_hosts, dt=dt,
                                       queue_cap=queue_cap,
                                       failover=failover, ckpt=ckpt,
                                       task_speed_override=
                                       task_speed_override, seed=seed)
    return res


def _numpy_check(graph, seeds, batch: JaxBatchMetrics, *, base_spec,
                 duration_s, n_hosts, dt, queue_cap, failover, ckpt,
                 task_speed_override, seed, n_probe: int = 3) -> dict:
    """Replay up to `n_probe` seeds on the single-core numpy engine and
    report the worst source-lag deviation vs the batched JAX rows. This
    is the sweep driver's opt-in correctness baseline — never run by
    default (the replay is orders of magnitude slower than the sweep)."""
    from repro.core.chaos import ChaosEngine
    from repro.streams.engine import StreamEngine

    checked, max_dev = [], 0.0
    t0 = time.perf_counter()
    for i, s in list(enumerate(seeds))[:n_probe]:
        spec = (dataclasses.replace(base_spec or ChaosSpec(), seed=int(s))
                if isinstance(s, (int, np.integer)) else s)
        kw = {} if isinstance(graph, PackedArena) else \
            {"n_hosts": n_hosts, "dt": dt, "queue_cap": queue_cap}
        eng = StreamEngine(graph, chaos=ChaosEngine(spec),
                           failover=failover, ckpt=ckpt,
                           task_speed_override=task_speed_override,
                           seed=seed, **kw)
        eng.run(duration_s)
        dev = float(np.max(np.abs(np.asarray(eng.metrics.source_lag)
                                  - batch.source_lag[i])))
        scale = float(np.max(np.abs(batch.source_lag[i]))) + 1e-9
        max_dev = max(max_dev, dev / scale)
        checked.append(int(getattr(s, "seed", s)))
    return {"seeds_checked": checked, "max_rel_lag_dev": max_dev,
            "wall_s": time.perf_counter() - t0}


# ----------------------------------------------------------------------
# resiliency-config grid sweeps (recovery-time-vs-budget surfaces)
# ----------------------------------------------------------------------
@dataclasses.dataclass
class ConfigSweepResult:
    """A ``(C, S)`` resiliency-config × chaos-seed sweep, one device
    call: per-config `SweepResult`s plus the dense surfaces the paper's
    tuning methodology wants (recovery time vs restart budget, SLO
    violation vs checkpoint interval)."""
    graph_name: str
    duration_s: float
    configs: list[dict]            # normalized grid entries
    labels: list[str]
    results: list[SweepResult]     # one per config row
    recovery_surface: np.ndarray   # (C, S) recovery_time_s
    slo_surface: np.ndarray        # (C, S) slo_violation_frac
    backlog_surface: np.ndarray    # (C, S) max_backlog
    lost_surface: np.ndarray       # (C, S) dropped records (lost work)
    wall_s: float
    # (C, S) deployment-drill auto-rollback fire times (+inf = canary
    # held / no drill on that config row); None for pre-drill callers
    rollback_surface: np.ndarray | None = None
    # (C, S) autoscaler surfaces (None for pre-autoscaler callers):
    # thrash-guard latch times (+inf = never thrashed), rescale action
    # counts, and integrated resource-seconds (the SLO-vs-cost axis)
    thrash_surface: np.ndarray | None = None
    rescale_surface: np.ndarray | None = None
    cost_surface: np.ndarray | None = None
    # chunked-pipeline wall split (see SweepResult) + per-request
    # trace-cache traffic; zero for legacy callers
    prep_s: float = 0.0
    device_s: float = 0.0
    cache_hits: int = 0
    cache_misses: int = 0
    phase_mode: str = ""           # resolved tick lowering

    @property
    def total_s(self) -> float:
        """End-to-end wall time (alias of `wall_s` — the denominator of
        the compat `scenarios_per_s`)."""
        return self.wall_s

    @property
    def scenarios_per_s(self) -> float:
        # compat: total-derived (wall_s == total_s), NOT device-only
        n = self.recovery_surface.size
        return n / self.wall_s if self.wall_s else 0.0

    def rows(self) -> list[dict]:
        """Per-config aggregate rows (label + fleet percentiles) — the
        recovery-time-vs-config curve in tabular form."""
        out = []
        for lbl, res in zip(self.labels, self.results):
            agg = res.aggregate()
            agg["label"] = lbl
            out.append(agg)
        return out


def _config_label(i: int, cfg: dict) -> str:
    if cfg.get("label"):
        return str(cfg["label"])
    bits = []
    fo, ck = cfg.get("failover"), cfg.get("ckpt")
    if isinstance(fo, FailoverConfig):
        if fo.mode == "hot_standby":
            bits.append(f"hot_standby:switch={fo.standby_switch_s:g}s")
        else:
            bits.append(f"{fo.mode}:restart="
                        f"{fo.single_restart_s if fo.mode == 'single_task' else fo.region_restart_s:g}s")
    elif fo is not None:
        bits.append(f"per-job[{len(list(fo))}]")
    if isinstance(ck, CheckpointConfig):
        bits.append(f"ckpt={ck.interval_s:g}s")
    elif ck is not None:
        bits.append("ckpt=per-job")
    if cfg.get("qcap_scale", 1.0) != 1.0:
        bits.append(f"qcap×{cfg['qcap_scale']:g}")
    if cfg.get("sel_scale", 1.0) != 1.0:
        bits.append(f"sel×{cfg['sel_scale']:g}")
    bro = tuple(cfg.get("brownout", ()))
    if bro:
        bits.append("brownout×" + "/".join(f"{r[2]:g}" for r in bro))
    upg = cfg.get("upgrade")
    if isinstance(upg, UpgradeConfig):
        bits.append(f"drill:{'hot' if upg.hot else 'cold'}"
                    f" canary={upg.canary_frac:g}"
                    f" thr={upg.rollback_threshold:g}")
    sc = cfg.get("scaler")
    if isinstance(sc, AutoscaleConfig):
        bits.append(f"ds2:int={sc.interval_s:g}s"
                    f" tgt={sc.target_utilization:g}"
                    f" hyst={sc.hysteresis:g}")
    tr = cfg.get("traffic", ((), ()))
    if tr and (tr[0] or tr[1]):
        tb = []
        if tr[0]:
            tb.append("diurnal×" + "/".join(f"{d[0]:g}" for d in tr[0]))
        if tr[1]:
            tb.append("flash×" + "/".join(f"{f[3]:g}" for f in tr[1]))
        bits.append(" ".join(tb))
    return " ".join(bits) if bits else f"cfg{i}"


def sweep_configs(graph: LogicalGraph | PackedArena, configs, seeds, *,
                  base_spec: ChaosSpec,
                  duration_s: float, n_hosts: int = 8, dt: float = 0.5,
                  queue_cap: float = 256.0,
                  slo_lag: float | None = None,
                  task_speed_override: dict[int, float] | None = None,
                  seed: int = 0, pad_seeds: bool = True,
                  devices: int | str | None = None,
                  phase_mode: str = "auto",
                  seed_chunk: int | None = None,
                  on_chunk=None,
                  spans: SpanLog | None = None) -> ConfigSweepResult:
    """Sweep a ``(C, S)`` grid of resiliency configs × chaos seeds over
    `graph` in ONE doubly-vmapped jit call (`jax_engine.run_config_batch`
    — the engine's third vmap axis) and summarize each config row.

    `configs` entries follow `jax_engine.normalize_config`: a
    `FailoverConfig`, a `CheckpointConfig`, a ``(failover, ckpt)`` pair,
    a per-job `FailoverConfig` list (packed arenas), or a dict with
    ``failover`` / ``ckpt`` / ``qcap_scale`` / ``sel_scale`` / ``label``.
    The result's `recovery_surface` / `slo_surface` are the dense (C, S)
    curves — recovery time vs restart budget, SLO violation vs
    checkpoint interval — that StreamShield-style release gating and
    Khaos-style checkpoint-interval optimization read off directly.

    ``devices=`` splits the flat seed axis of the (C, S) grid across
    local devices (`jax_engine.get_sharded_config_fn`; rows stay
    bit-identical to the single-device grid); ``phase_mode`` selects the
    dense vs compact (sparse-phase) tick lowering, default auto.

    ``seed_chunk`` streams the grid's seed axis through fixed-size
    chunks on the engine's double-buffered pipeline — one ``(C,
    S_chunk)`` device pass per chunk, host prep overlapping device
    compute, final surfaces bit-identical to the one-pass grid (see
    `jax_engine.run_config_batch`) — and ``on_chunk`` receives a
    `SweepChunk` with each partial ``(C, S_chunk)`` surface as it lands
    (the service layer's time-to-first-result path). The result's
    ``prep_s`` / ``device_s`` / ``cache_hits`` / ``cache_misses`` carry
    the prep / device-wait split + per-request trace-cache traffic
    either way, and ``wall_s`` runs from the plan's start to the last
    chunk's landing; ``spans`` receives the request's spans (module
    docstring).

    Each device pass copies only what the summaries read
    (`jax_engine.SummaryGridPlan`), so the rows' batches carry the
    per-tick lag and backlog series and no per-op ``qps`` / ``backlog``
    histories; `jax_engine.run_config_batch` returns those."""
    seeds = list(seeds)
    norm = [normalize_config(c) for c in configs]
    logical = graph.graph if isinstance(graph, PackedArena) else graph
    spans = SpanLog() if spans is None else spans
    plan, chunks, planned = _run_plan(
        lambda: SummaryGridPlan(graph, norm, seeds, base_spec=base_spec,
                                duration_s=duration_s, n_hosts=n_hosts,
                                dt=dt, queue_cap=queue_cap,
                                task_speed_override=task_speed_override,
                                seed=seed, pad_seeds=pad_seeds,
                                devices=devices, phase_mode=phase_mode),
        seeds, seed_chunk, on_chunk, spans, graph=logical,
        slo_lag=slo_lag, duration_s=duration_s)
    with spans.span("sweep.assemble",
                    scenarios=len(norm) * len(seeds)) as asm:
        wall = asm.start - planned.start
        batches = concat_config_batches([c.batches for c in chunks])
        # each config row gets its share of the one-call wall time, so
        # a row's scenarios_per_s stays comparable with a standalone
        # sweep()
        results = [summarize(bm, seeds, slo_lag=slo_lag,
                             wall_s=wall / len(norm),
                             graph_name=logical.name,
                             duration_s=duration_s)
                   for bm in batches]
        surf = _chunk_surfaces(batches, results)
    labels = [_config_label(i, c) for i, c in enumerate(norm)]
    return ConfigSweepResult(logical.name, duration_s, norm, labels,
                             results, surf["recovery_surface"],
                             surf["slo_surface"],
                             surf["backlog_surface"],
                             surf["lost_surface"], wall,
                             rollback_surface=surf["rollback_surface"],
                             thrash_surface=surf["thrash_surface"],
                             rescale_surface=surf["rescale_surface"],
                             cost_surface=surf["cost_surface"],
                             prep_s=sum(c.prep_s for c in chunks),
                             device_s=sum(c.device_s for c in chunks),
                             cache_hits=plan.cache_info["hits"],
                             cache_misses=plan.cache_info["misses"],
                             phase_mode=plan.low.tensor.mode)


# ----------------------------------------------------------------------
# replication-vs-checkpoint tradeoff cube (paper §IV-A, Fig 9)
# ----------------------------------------------------------------------
@dataclasses.dataclass
class ReplicationTradeoff:
    """The hybrid-replication tuning cube: every surface is shaped
    ``(n_modes, n_intervals, n_brownouts, S)`` — recovery time, SLO
    violation and lost work over replication-mode × checkpoint-interval
    × brownout-severity, all from ONE `sweep_configs` device call."""
    modes: list[str]
    ckpt_intervals: list
    brownout_peaks: list[float]
    recovery: np.ndarray
    slo: np.ndarray
    lost: np.ndarray
    grid: ConfigSweepResult

    def rows(self) -> list[dict]:
        return self.grid.rows()


def replication_tradeoff(graph, seeds, *, base_spec: ChaosSpec,
                         duration_s: float,
                         failovers: dict[str, FailoverConfig],
                         ckpt_intervals=(None, 10.0, 30.0),
                         brownouts=((), ((0.0, 1e9, 4.0),)),
                         ckpt_upload_s: float = 4.0,
                         **sweep_kw) -> ReplicationTradeoff:
    """Sweep the full replication-vs-checkpoint tradeoff cube in ONE
    `sweep_configs` call (hence one traced device pass, flat
    `timeline_build_count`).

    `failovers` maps mode labels (e.g. ``"hot_standby"`` /
    ``"passive"``) to the `FailoverConfig` representing that replication
    strategy; `ckpt_intervals` is a sequence of checkpoint intervals
    (None = no checkpoints → passive restores replay from run start);
    `brownouts` is a sequence of config-level brownout ramp tuples
    (appended to `base_spec`'s own ramps, deterministically). The cube
    axes are ordered (mode, interval, brownout, seed)."""
    mode_names = list(failovers)
    intervals = list(ckpt_intervals)
    bros = [tuple(b) for b in brownouts]
    configs = []
    for m in mode_names:
        for iv in intervals:
            for b in bros:
                peak = max((r[2] for r in b), default=1.0)
                configs.append({
                    "failover": failovers[m],
                    "ckpt": (None if iv is None else CheckpointConfig(
                        interval_s=iv, upload_s=ckpt_upload_s)),
                    "brownout": b,
                    "label": (f"{m} ckpt="
                              f"{'off' if iv is None else f'{iv:g}s'}"
                              f" brownout={peak:g}x")})
    grid = sweep_configs(graph, configs, seeds, base_spec=base_spec,
                         duration_s=duration_s, **sweep_kw)
    shape = (len(mode_names), len(intervals), len(bros), -1)
    return ReplicationTradeoff(
        mode_names, intervals, [max((r[2] for r in b), default=1.0)
                                for b in bros],
        grid.recovery_surface.reshape(shape),
        grid.slo_surface.reshape(shape),
        grid.lost_surface.reshape(shape), grid)


# ----------------------------------------------------------------------
# deployment-drill cube (canary/rolling upgrades + auto-rollback)
# ----------------------------------------------------------------------
@dataclasses.dataclass
class DeploymentDrill:
    """The deployment-drill tuning cube: every surface is shaped
    ``(n_policies, n_fracs, n_thresholds, S)`` — recovery time, SLO
    violation, lost work and auto-rollback fire time over
    upgrade-policy × canary-fraction × rollback-threshold, all from ONE
    `sweep_configs` device call (upgrades are in-trace only, so the
    whole cube shares the drill-free rows' pregenerated timelines and
    `timeline_build_count` stays flat)."""
    policies: list[str]
    canary_fracs: list[float]
    rollback_thresholds: list[float]
    recovery: np.ndarray
    slo: np.ndarray
    lost: np.ndarray
    rollback_t: np.ndarray          # +inf = canary held (no rollback)
    grid: ConfigSweepResult

    @property
    def rollback_frac(self) -> np.ndarray:
        """Fraction of seeds whose drill auto-rolled back, per
        (policy, frac, threshold) cell."""
        return np.isfinite(self.rollback_t).mean(axis=-1)

    def rows(self) -> list[dict]:
        return self.grid.rows()


def deployment_drill(graph, seeds, *, base_spec: ChaosSpec,
                     duration_s: float,
                     policies: dict[str, UpgradeConfig],
                     canary_fracs=(0.25, 0.5),
                     rollback_thresholds=(math.inf, 200.0),
                     failover=None, ckpt=None,
                     **sweep_kw) -> DeploymentDrill:
    """Sweep the full deployment-drill cube in ONE `sweep_configs` call.

    `policies` maps labels (e.g. ``"hot"`` / ``"cold"`` / ``"hot+accel"``)
    to base `UpgradeConfig`s — typically differing in ``hot`` /
    ``startup`` / ``wave_stagger_s`` / canary config deltas; each cube
    cell replaces that policy's ``canary_frac`` and
    ``rollback_threshold`` (``math.inf`` = canary never rolls back — the
    drill-as-control row). `failover` / `ckpt` are the base resiliency
    configs every row shares (per-job lists allowed on packed arenas).

    The cube axes are ordered (policy, canary_frac, threshold, seed);
    `DeploymentDrill.rollback_t` is the per-cell auto-rollback fire-time
    surface and `rollback_frac` the per-cell trigger rate a release
    pipeline gates on."""
    pol_names = list(policies)
    fracs = [float(f) for f in canary_fracs]
    thrs = [float(t) for t in rollback_thresholds]
    configs = []
    for p in pol_names:
        for f in fracs:
            for thr in thrs:
                up = dataclasses.replace(policies[p], canary_frac=f,
                                         rollback_threshold=thr)
                configs.append({
                    "failover": failover, "ckpt": ckpt, "upgrade": up,
                    "label": (f"{p} canary={f:g} thr="
                              f"{'off' if math.isinf(thr) else f'{thr:g}'}")})
    grid = sweep_configs(graph, configs, seeds, base_spec=base_spec,
                         duration_s=duration_s, **sweep_kw)
    shape = (len(pol_names), len(fracs), len(thrs), -1)
    return DeploymentDrill(
        pol_names, fracs, thrs,
        grid.recovery_surface.reshape(shape),
        grid.slo_surface.reshape(shape),
        grid.lost_surface.reshape(shape),
        grid.rollback_surface.reshape(shape), grid)


# ----------------------------------------------------------------------
# traffic-dynamics cube (diurnal/flash load × DS2 autoscaling × failover)
# ----------------------------------------------------------------------
@dataclasses.dataclass
class TrafficSweep:
    """The traffic-dynamics tuning cube: every surface is shaped
    ``(n_scalers, n_traffics, n_failovers, S)`` — recovery time, SLO
    violation, lost work, rescale actions, thrash latch times and
    resource-seconds cost over scaler-config × traffic-pattern ×
    failover-mode, all from ONE `sweep_configs` device call (rate
    schedules are rng-free ``rfac`` curves and scalers are traced
    leaves, so the whole cube shares pregenerated timelines and
    `timeline_build_count` stays flat)."""
    scalers: list[str]
    traffics: list[str]
    failovers: list[str]
    recovery: np.ndarray
    slo: np.ndarray
    lost: np.ndarray
    rescales: np.ndarray
    thrash_t: np.ndarray            # +inf = the thrash guard never fired
    cost: np.ndarray                # Σ speed·dt resource-seconds
    grid: ConfigSweepResult

    @property
    def thrash_frac(self) -> np.ndarray:
        """Fraction of seeds whose autoscaler thrash guard latched, per
        (scaler, traffic, failover) cell — the oscillation rate a
        release pipeline gates on."""
        return np.isfinite(self.thrash_t).mean(axis=-1)

    def rows(self) -> list[dict]:
        return self.grid.rows()


def traffic_sweep(graph, seeds, *, base_spec: ChaosSpec,
                  duration_s: float,
                  scalers: dict[str, AutoscaleConfig | None],
                  traffics: dict[str, tuple] | None = None,
                  failovers: dict[str, FailoverConfig | None] | None = None,
                  ckpt=None, **sweep_kw) -> TrafficSweep:
    """Sweep the full traffic-dynamics cube — scaler-config ×
    traffic-pattern × failover-mode × seeds — in ONE `sweep_configs`
    call, the SLO-vs-cost frontier of in-trace DS2 autoscaling under
    production load dynamics.

    `scalers` maps labels to `AutoscaleConfig`s (None = no autoscaler —
    the fixed-provisioning control rows); `traffics` maps labels to
    config-level traffic patterns (`normalize_config`'s ``traffic``
    forms: a ``(diurnal, flash)`` pair, a ``{"diurnal": ..., "flash":
    ...}`` dict, or a bare flash-event tuple — composed on top of
    `base_spec`'s own schedule); `failovers` maps labels to the base
    `FailoverConfig` per row (rescale-during-recovery and
    autoscaler-vs-failover interactions come from crossing these two
    axes). The cube axes are ordered (scaler, traffic, failover, seed);
    `TrafficSweep.cost` is the resource-seconds surface against which
    `slo` trades, and `thrash_frac` the per-cell oscillation rate."""
    sc_names = list(scalers)
    traffics = dict(traffics) if traffics else {"base": ((), ())}
    fo_names_map = dict(failovers) if failovers else {"base": None}
    tr_names = list(traffics)
    fo_names = list(fo_names_map)
    configs = []
    for s in sc_names:
        for tname in tr_names:
            for fname in fo_names:
                configs.append({
                    "failover": fo_names_map[fname], "ckpt": ckpt,
                    "scaler": scalers[s], "traffic": traffics[tname],
                    "label": f"{s} {tname} {fname}"})
    grid = sweep_configs(graph, configs, seeds, base_spec=base_spec,
                         duration_s=duration_s, **sweep_kw)
    shape = (len(sc_names), len(tr_names), len(fo_names), -1)
    return TrafficSweep(
        sc_names, tr_names, fo_names,
        grid.recovery_surface.reshape(shape),
        grid.slo_surface.reshape(shape),
        grid.lost_surface.reshape(shape),
        grid.rescale_surface.reshape(shape),
        grid.thrash_surface.reshape(shape),
        grid.cost_surface.reshape(shape), grid)
