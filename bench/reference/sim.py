"""Plain reference simulator of one (configuration, seed) scenario over a
packed fleet: the same semantics as the system's tick engine, written
from its documented model, one tick at a time with numpy. Jobs of one
template are simulated side by side as the rows of one array, which is
exact because records never cross jobs.

Every tick of length ``dt`` at time ``t``, in this order:

1. A task is alive when neither a failover (``down``) nor an upgrade
   wave (``up``) holds it past ``t``. Sources emit nothing while the MQ
   is down or while a ZooKeeper outage overlaps an HDFS outage.
2. Operators run in topological order. A source task emits
   ``source_rate * dt / parallelism`` when alive. Any other task takes
   ``min(queue, service_rate * dt * speed)`` when alive and emits that
   times its selectivity (plus the canary delta once its upgrade wave
   is done). Each edge routes the output: ``forward`` pairwise to live
   tasks, ``hash`` by fixed key shares whatever the liveness,
   ``rebalance`` evenly over live tasks. Records sent to a dead task in
   ``single_task`` mode are dropped. A destination accepts at most its
   free room (``qcap - queue`` at the tick's start, used up as records
   land); one full channel throttles the whole exchange by the same
   factor, and what is refused goes back to the sender's queue.
3. Hosts die: scheduled kills (``host_kill_at`` and region bursts) in
   ``(t, t + dt]``, then one Poisson draw per host in host order. Each
   dead host's tasks fail over by mode, region first, then single task,
   then hot standby, and the host is replaced at once. Passive restores
   pay ``restore_base * brownout(t) + age * replay_rate`` plus the lazy
   load stagger of their region's rank; hot standby pays
   ``detect + switch + staleness``. A failed task's queue is lost.
4. The checkpoint coordinator attempts when ``t + dt`` reaches its next
   time: each task's upload takes ``upload_s * storage factor *
   brownout(t)``; a dead task or one slower than the interval fails it,
   and in region mode a failed region retries its uploads once.
5. The deployment drill's controller folds the canary-minus-stable mean
   queue into its moving average after ``up_t0`` and schedules the
   rollback when it passes the threshold; upgrade and rollback waves
   starting within the tick hold their tasks (queues kept) for the wave
   downtime.
6. The tick records its time, the sources' backlog (lag), the total and
   the downstream backlog.

``dtype`` sets the precision of every record count and queue (times stay
float64), so the same code is the benchmark's lower-precision control.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from bench.reference.fleet import Fleet
from bench.reference.model import (ChaosSpec, CheckpointConfig,
                                   FailoverConfig, UpgradeConfig,
                                   deploy_downtime)

MODES = ("region", "single_task", "hot_standby")
#: chaos fields the reference does not simulate; a scenario that sets
#: one is refused rather than compared against the wrong semantics
UNMODELLED = ("storage_fail_prob", "net_delay_factor", "diurnal",
              "flash_at", "rate_phase_s")


@dataclasses.dataclass
class Scenario:
    """One cell of a request's (configuration, seed) grid."""
    spec: ChaosSpec               # seed and config brownouts merged in
    failover: FailoverConfig
    ckpt: CheckpointConfig | None
    upgrade: UpgradeConfig | None
    duration_s: float


@dataclasses.dataclass
class Outcome:
    t: np.ndarray
    lag: np.ndarray               # (T,) backlog of source tasks
    backlog: np.ndarray           # (T,) backlog of all tasks
    down_backlog: np.ndarray      # (T,) backlog of non-source tasks
    emitted: float
    dropped: float
    recoveries: list[dict]
    ckpt_attempts: int
    ckpt_success: int
    rollback_t: float


def _brownout(ramps, t: float) -> float:
    f = 1.0
    for a, b, peak in ramps:
        if a <= t < b:
            f *= 1.0 + (peak - 1.0) * (1.0 - abs(2.0 * (t - a) / (b - a)
                                                 - 1.0))
    return f


def _inside(windows, t: float) -> bool:
    return any(a <= t < b for a, b in windows)


class _Group:
    """State of all jobs of one template: arrays of (jobs, tasks)."""

    def __init__(self, tpl, jobs: np.ndarray, f, fo: FailoverConfig):
        self.tpl, self.jobs, self.f = tpl, jobs, f
        shape = (len(jobs), tpl.n_tasks)
        self.q = np.zeros(shape, f)
        self.down = np.zeros(shape)
        self.up = np.zeros(shape)
        self.speed = np.ones(shape, f)
        self.qcap = np.zeros(tpl.n_tasks, f)
        self.sel = np.zeros(tpl.n_tasks, f)
        self.is_src = tpl.source_mask()
        self.lazy = tpl.region.astype(np.float64) * fo.lazyload_stagger_s


def _drill(up: UpgradeConfig, fleet: Fleet, groups, spec: ChaosSpec,
           fo: FailoverConfig, ckpt, f) -> dict:
    """Per-task drill parameters (canary mask, wave starts, controller
    weights, selectivity delta) and the controller's scalars."""
    if up.canary_failover is not None or up.canary_ckpt is not None:
        raise NotImplementedError("canary failover/checkpoint configs are "
                                  "not in the reference")
    n_jobs = fleet.n_jobs
    if up.canary_jobs is not None:
        cjob = np.zeros(n_jobs, bool)
        cjob[list(up.canary_jobs)] = True
    else:
        k = max(0, min(n_jobs, int(round(up.canary_frac * n_jobs + 1e-9))))
        cjob = np.arange(n_jobs) < k
    t_up = float(spec.upgrade_at[0]) if spec.upgrade_at else float(
        up.t_upgrade_s)
    down = (float(up.wave_down_s) if up.wave_down_s is not None
            else deploy_downtime(up.startup, up.hot))
    n_can = sum(float(cjob[g.jobs].sum()) * g.tpl.n_tasks for g in groups)
    n_st = float(fleet.n_tasks) - n_can
    for g in groups:
        cm = cjob[g.jobs].astype(np.float64)[:, None] * np.ones(
            g.tpl.n_tasks)
        rank = g.tpl.region.astype(np.float64)[None, :]
        g.cmask = cm.astype(f)
        g.up_start = np.where(cm > 0, t_up + rank * up.wave_stagger_s,
                              np.inf)
        g.up_rstag = np.where(cm > 0, rank * up.wave_stagger_s, np.inf)
        g.wdelta = (cm / max(n_can, 1.0)
                    - (1.0 - cm) / max(n_st, 1.0)).astype(f)
        g.d_sel = (cm * g.sel.astype(np.float64)
                   * (float(up.canary_sel_scale) - 1.0)).astype(f)
    return {"down": down, "t0": (t_up + down if cjob.any() else math.inf),
            "thresh": float(up.rollback_threshold),
            "alpha": min(1.0, fleet.dt / max(float(up.rollback_window_s),
                                             fleet.dt))}


def simulate(fleet: Fleet, sc: Scenario, dtype=np.float64) -> Outcome:
    f = np.dtype(dtype).type
    spec, fo, ck, up = sc.spec, sc.failover, sc.ckpt, sc.upgrade
    if fo.mode not in MODES + ("none",):
        raise ValueError(f"unknown failover mode {fo.mode!r}")
    missing = [k for k in UNMODELLED
               if getattr(spec, k) != getattr(ChaosSpec(), k)]
    if missing:
        raise NotImplementedError(f"chaos {missing} is not in the reference")
    dt = fleet.dt
    rng = np.random.default_rng(spec.seed)
    groups = [_Group(fleet.templates[name], jobs, f, fo)
              for name, jobs in fleet.groups.items()]
    for g in groups:
        for o in g.tpl.ops:
            sl = g.tpl.span(o.name)
            g.qcap[sl] = max(o.service_rate * dt * 4.0, fleet.queue_cap)
            if not o.is_source:
                g.sel[sl] = o.selectivity
    # stragglers: one draw per host the first time a task (in fleet task
    # order) is placed on it
    if spec.straggler_frac:
        slow: dict[int, bool] = {}
        by_job = {int(j): (g, r) for g in groups
                  for r, j in enumerate(g.jobs)}
        for j in range(fleet.n_jobs):
            g, r = by_job[j]
            for i, h in enumerate(g.tpl.local_host):
                if int(h) not in slow:
                    slow[int(h)] = bool(rng.random() < spec.straggler_frac)
                if slow[int(h)]:
                    g.speed[r, i] = f(1.0 / spec.straggler_factor)
    # region bursts: every host serving a task of the region dies
    kill_at = [(float(t), int(h)) for t, h in spec.host_kill_at]
    first_region = {}
    reg0 = 0
    for j, name in enumerate(fleet.job_template):
        first_region[j] = reg0
        reg0 += fleet.templates[name].n_regions
    for tb, reg in spec.burst_at:
        j = max(k for k, r0 in first_region.items() if r0 <= int(reg))
        tpl = fleet.templates[fleet.job_template[j]]
        hosts = np.unique(tpl.local_host[tpl.region
                                         == int(reg) - first_region[j]])
        kill_at.extend((float(tb), int(h)) for h in hosts)

    dr = _drill(up, fleet, groups, spec, fo, ck, f) if up else None
    down_s = fo.detect_s + fo.single_restart_s
    down_r = fo.detect_s + fo.region_restart_s
    down_h = fo.detect_s + fo.standby_switch_s + fo.standby_staleness_s
    n_ticks = int(round(sc.duration_s / dt))
    ts = np.zeros(n_ticks)
    lag = np.zeros(n_ticks, f)
    total = np.zeros(n_ticks, f)
    downb = np.zeros(n_ticks, f)
    emitted, dropped = f(0.0), f(0.0)
    recoveries: list[dict] = []
    attempts = success = 0
    next_ckpt = ck.interval_s if ck else math.inf
    last_ckpt = 0.0
    rb_t, dacc = math.inf, 0.0
    t = 0.0
    for i in range(n_ticks):
        gate = 0.0 if (_inside(spec.mq_down, t) or (
            _inside(spec.zk_down, t) and _inside(spec.hdfs_down, t))) \
            else 1.0
        for g in groups:
            tpl = g.tpl
            alive = (g.down <= t) & (g.up <= t)
            alive_f = alive.astype(f)
            act = (g.cmask * ((t >= g.up_start + dr["down"])
                              & (t < rb_t + g.up_rstag))
                   if dr else None)
            free = np.maximum(g.qcap - g.q, f(0.0))
            for name in tpl.topo:
                o = tpl.op(name)
                sl = tpl.span(name)
                if o.is_source:
                    produced = (f(o.source_rate * dt / o.parallelism)
                                * alive_f[:, sl] * f(gate))
                    emitted = emitted + produced.sum()
                else:
                    cap = (f(o.service_rate * dt) * g.speed[:, sl]
                           * alive_f[:, sl])
                    take = np.minimum(g.q[:, sl], cap)
                    g.q[:, sl] -= take
                    sel = g.sel[sl] if dr is None else (
                        g.sel[sl] + act[:, sl] * g.d_sel[:, sl])
                    produced = take * sel
                for e in tpl.out_edges(name):
                    dsl = tpl.span(e.dst)
                    alive_d = alive_f[:, dsl]
                    if e.partitioner == "forward":
                        arriving = produced * alive_d
                    elif e.partitioner == "hash":
                        arriving = (produced.sum(axis=1, keepdims=True)
                                    * tpl.share[(e.src, e.dst)].astype(f))
                    else:                                  # rebalance
                        n_live = alive_d.sum(axis=1, keepdims=True)
                        arriving = np.where(
                            n_live > 0, alive_d * (
                                produced.sum(axis=1, keepdims=True)
                                / np.maximum(n_live, f(1.0))), f(0.0))
                    if fo.mode == "single_task":
                        dead = ~alive[:, dsl]
                        dropped = dropped + arriving[dead].sum()
                        arriving = np.where(dead, f(0.0), arriving)
                    room = free[:, dsl]
                    live = arriving > 1e-9
                    ratio = np.full(arriving.shape, np.inf, f)
                    np.divide(room, arriving, out=ratio, where=live)
                    lam = np.minimum(ratio.min(axis=1, keepdims=True),
                                     f(1.0))
                    accepted = arriving * lam
                    overflow = (arriving - accepted).sum(axis=1,
                                                         keepdims=True)
                    g.q[:, sl] += overflow / f(o.parallelism)
                    g.q[:, dsl] += accepted
                    free[:, dsl] = np.maximum(room - accepted, f(0.0))
        # host kills
        kills = {h for tk, h in kill_at if t < tk <= t + dt}
        if spec.host_kill_prob_per_s:
            p = 1.0 - np.exp(-spec.host_kill_prob_per_s * dt)
            kills.update(int(h) for h in np.nonzero(
                rng.random(fleet.n_hosts) < p)[0])
        for host in sorted(kills):
            _fail_host(groups, host, t, fo, (down_r, down_s, down_h),
                       _brownout(spec.brownout_at, t), t - last_ckpt,
                       recoveries)
        # checkpoint coordinator
        if t + dt >= next_ckpt:
            attempts += 1
            if _checkpoint(fleet, groups, ck, spec, rng, t):
                success += 1
                last_ckpt = t
            next_ckpt += ck.interval_s
        # drill controller and waves
        if dr:
            delta = float(sum((g.q * g.wdelta).sum() for g in groups))
            if t >= dr["t0"]:
                dacc += dr["alpha"] * (delta - dacc)
                if dacc > dr["thresh"] and math.isinf(rb_t):
                    rb_t = t + dt
            for g in groups:
                for start in (g.up_start, rb_t + g.up_rstag):
                    trig = (t <= start) & (start < t + dt)
                    g.up[trig] = np.maximum(g.up[trig],
                                            start[trig] + dr["down"])
        ts[i] = t
        for g in groups:
            src = g.q[:, g.is_src].sum()
            rest = g.q[:, ~g.is_src].sum()
            lag[i] += src
            downb[i] += rest
            total[i] += src + rest
        t = t + dt
    return Outcome(ts, lag, total, downb, float(emitted), float(dropped),
                   recoveries, attempts, success, rb_t)


def _fail_host(groups, host: int, t: float, fo: FailoverConfig, downs,
               bf: float, age: float, recoveries: list) -> None:
    """Fail over every task on `host`: one recovery entry per hit job,
    in job order, for the failover mode of the scenario."""
    if fo.mode == "none":
        return
    down_r, down_s, down_h = downs
    entries = []
    for g in groups:
        victims = g.tpl.local_host == host
        if not victims.any():
            continue
        extra = (fo.restore_base_s * bf + age * fo.replay_rate + g.lazy)
        if fo.mode == "region":
            hit = np.isin(g.tpl.region, g.tpl.region[victims])
            d = down_r + extra
        elif fo.mode == "single_task":
            hit = victims
            d = down_s + extra
        else:
            hit = victims
            d = np.full(g.tpl.n_tasks, down_h)
        g.down[:, hit] = t + d[hit]
        g.q[:, hit] = 0.0
        first = int(np.nonzero(hit)[0][0])
        entries.extend((int(j), int(hit.sum()), float(d[first]))
                       for j in g.jobs)
    entries.sort()
    recoveries.extend({"t": t, "mode": fo.mode, "tasks": n,
                       "downtime": d, "job": j} for j, n, d in entries)


def _checkpoint(fleet: Fleet, groups, ck: CheckpointConfig,
                spec: ChaosSpec, rng, t: float) -> bool:
    """One attempt: per-task upload factors drawn in fleet task order,
    then each region in order, with one retry of a failed region."""
    bf = _brownout(spec.brownout_at, t)
    n = fleet.n_tasks
    if spec.storage_slow_prob:
        factors = np.where(rng.random(n) < spec.storage_slow_prob,
                           spec.storage_slow_factor, 1.0)
    else:
        factors = np.ones(n)
    by_job = {int(j): (g, r) for g in groups for r, j in enumerate(g.jobs)}
    fail = np.zeros(n, bool)
    for j, sl in enumerate(fleet.task_slices()):
        g, r = by_job[j]
        fail[sl] = g.down[r] > t
    fail |= ck.upload_s * factors * bf > ck.interval_s
    if ck.mode == "global":
        return not fail.any()

    def slow_once() -> bool:
        factor = 1.0
        if spec.storage_slow_prob and rng.random() < spec.storage_slow_prob:
            factor = spec.storage_slow_factor
        return ck.upload_s * factor * bf > ck.interval_s

    for j, sl in enumerate(fleet.task_slices()):
        g, _ = by_job[j]
        for reg in range(g.tpl.n_regions):
            tasks = np.nonzero(g.tpl.region == reg)[0]
            bad = bool(fail[sl][tasks].any())
            if bad and ck.retry_failed_region:
                bad = any(slow_once() for _ in tasks)
            if bad:
                return False
    return True
