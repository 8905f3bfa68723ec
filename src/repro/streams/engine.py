"""Credit-based stream engine — precompiled routing plan over a flat task
arena (vectorized micro-tick simulator).

Architecture
------------
`StreamEngine.__init__` lowers the logical graph into a static **routing
plan** so that `tick()` touches no per-task, per-group or per-dst Python
loops:

* **Task arena** — one contiguous float array per state variable
  (`queue`, `speed`, `down_until`, `qcap`), indexed by global task id.
  Tasks of an op occupy a contiguous slice (`expand()` numbers them that
  way), so per-op views are zero-copy slices of the arena.
* **Op plan** — cached topo order plus per-op scalars (service rate,
  selectivity, source rate, arena slice) resolved once.
* **Edge plans** — for every logical edge the per-tick routing weight
  matrix of the reference interpreter collapses analytically:

    - all-to-all hops (rebalance / hash / weakhash / backlog) have
      identical weight rows, so `produced @ W == produced.sum() * w_row`
      — O(n_dst) instead of O(n_src · n_dst);
    - blocky hops (rescale / group_rescale) reduce to CSR-style segment
      sums over precomputed block boundaries (`np.bincount` /
      `np.add.reduceat` / `np.minimum.reduceat`);
    - forward is elementwise.

  Static key-mass shares (Zipf-skewed `keyBy`) and per-group mass sums
  are precomputed into the plan.
* **Metric buffers** — metrics append into preallocated, doubling numpy
  buffers (`EngineMetrics`); per-tick cost is one row write instead of
  O(ops) list appends, and consumers get zero-copy array views.

Semantics are pinned (within float round-off) to the per-edge reference
interpreter preserved in `streams/reference_engine.py`; see
`tests/test_engine_vectorized.py`. Each tick (dt): sources emit, every task
consumes from its bounded input queue at service_rate × host_speed and
pushes downstream according to the edge's partitioner weights. Bounded
queues give credit-based backpressure (paper §III-A). Partitioner weight
policies:

  rebalance / rescale / group_rescale — uniform over connected tasks
  hash      — static weights ∝ hashed key mass (Zipf-skewed when configured)
  weakhash  — key-group mass spread within the group ∝ free capacity
  backlog   — uniform over channels whose backlog < threshold (divert)

Failover (paper §III-B): "region" restarts every task of the failed task's
region (downtime = restore+redeploy); "single_task" restarts only the failed
task while upstream records destined to it are DROPPED (γ=partial, counted).

The checkpoint coordinator implements Fig 8: per-task uploads with
chaos-injected slow factors against the interval timeout; global mode aborts
on any failure, region mode merges + retries the failed region once.

Multi-job mega-arena (paper's cluster perspective)
--------------------------------------------------
`pack_arena(graphs, host_map)` concatenates K co-located job graphs into
ONE flat arena sharing a host pool: ops are namespaced ``j{k}.``, tasks
get arena-global ids (per-job contiguous slices), regions never merge
across jobs, and each job's local round-robin host placement is lifted
into the pool through a per-job host map ("shared" co-locates everything,
"disjoint" reproduces K independent clusters exactly). Both engines accept
the `PackedArena` in place of a graph; a chaos host kill then fans out to
every co-located job on that host while metrics stay segmentable per job
(`job_of_op` / `job_of_task`, per-job emitted/dropped, per-job recovery
events). See the `PackedArena` docstring for the full layout contract.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.core.chaos import (ChaosEngine, burst_kill_schedule,
                              failover_recovery_entries,
                              run_checkpoint_attempt)
from repro.streams.graph import (LogicalGraph, PhysicalGraph, Task, expand,
                                 namespaced)


@dataclasses.dataclass
class FailoverConfig:
    # "region" | "single_task" | "hot_standby" | "none"
    mode: str = "region"
    detect_s: float = 1.0
    region_restart_s: float = 45.0   # restore state + redeploy the region
    single_restart_s: float = 3.0    # redeploy one task, clean state
    # hybrid replication (paper §IV-A): hot_standby pays switch latency +
    # replay of standby staleness INSTEAD of a checkpoint restore
    standby_switch_s: float = 0.05
    standby_staleness_s: float = 0.5
    # passive-restore surcharge (added to region/single downtimes):
    # restore_base_s is scaled by the storage-brownout factor at kill
    # time (restore bandwidth degrades with the ramp), replay_rate is
    # seconds of replay per second of checkpoint age, and
    # lazyload_stagger_s staggers region ready-times — a task blocks
    # until its own region is materialized (State LazyLoad, §III-B)
    restore_base_s: float = 0.0
    replay_rate: float = 0.0
    lazyload_stagger_s: float = 0.0

    @classmethod
    def from_replication(cls, timing, *, mode: str = "hot_standby",
                         state_bytes: float = 0.0,
                         detect_s: float | None = None) -> "FailoverConfig":
        """Lower a `core.replication.TimingModel` into tick-engine
        failover parameters (active replication → `hot_standby`; passive
        → checkpoint restore whose cost scales with state size, restore
        bandwidth, and checkpoint age)."""
        kw = timing.tick_failover_kwargs(nbytes=state_bytes)
        if detect_s is not None:
            kw["detect_s"] = detect_s
        return cls(mode=mode, **kw)


@dataclasses.dataclass
class CheckpointConfig:
    interval_s: float = 30.0
    mode: str = "region"             # "region" | "global"
    upload_s: float = 4.0            # nominal per-task upload duration
    retry_failed_region: bool = True


@dataclasses.dataclass
class UpgradeConfig:
    """Deployment-drill policy (paper §V): the HOW of a canaried rolling
    upgrade. The WHEN comes from ``ChaosSpec.upgrade_at`` (first entry;
    per-job chaos lists schedule per job), falling back to
    ``t_upgrade_s``. Upgrades are deterministic in-trace events: they
    consume NO rng draws and never touch the pregenerated chaos
    timelines — both engines implement waves, canary config divergence
    and auto-rollback as pure time arithmetic inside the tick.

    A drill canaries the first ``round(canary_frac * n_jobs)`` jobs of a
    packed arena (or the explicit ``canary_jobs`` indices). Canaried
    jobs restart region-sized task slices on a ``wave_stagger_s``
    cadence, each wave paying ``wave_down_s`` of downtime — by default
    the hot-vs-cold `core.hotupdate.deploy_downtime` cost lowered from
    ``startup`` (a `core.startup.StartupConfig`; None = its defaults).
    Once a task's wave completes, the task runs ``canary_failover`` /
    ``canary_ckpt`` / ``canary_sel_scale`` instead of the base configs
    (None = unchanged; canary lazyload staggers are ignored). A drill
    controller EWMAs the canary-vs-stable mean-queue delta over
    ``rollback_window_s`` and, once it exceeds ``rollback_threshold``
    (default inf = never), schedules a rollback: the canary slice
    reverts to the base config and pays a second restart wave. Upgrade
    and rollback waves are *graceful* — queues persist, unlike crash
    failover — so an upgrade to an identical config with
    ``wave_down_s=0`` is an exact no-op."""
    t_upgrade_s: float = 30.0
    wave_stagger_s: float = 2.0
    hot: bool = True
    startup: object | None = None    # core.startup.StartupConfig
    wave_down_s: float | None = None  # override deploy_downtime lowering
    canary_frac: float = 0.5
    canary_jobs: tuple | None = None  # explicit job indices (overrides frac)
    rollback_threshold: float = math.inf
    rollback_window_s: float = 5.0
    canary_failover: FailoverConfig | None = None
    canary_ckpt: CheckpointConfig | None = None
    canary_sel_scale: float = 1.0


def inert_upgrade_leaves(n_tasks: int) -> dict:
    """Drill parameter leaves of a drill-free run: the traced arithmetic
    stays structurally present (stable pytree → one trace for drill and
    non-drill configs) but is an exact arithmetic no-op — act masks are
    identically zero, wave starts are +inf, the controller never arms."""
    z = lambda: np.zeros(n_tasks)                      # noqa: E731
    return {
        "up_cmask": z(), "up_start": np.full(n_tasks, np.inf),
        "up_rstag": np.full(n_tasks, np.inf), "up_wdelta": z(),
        "d_down_s": z(), "d_down_r": z(), "d_down_h": z(),
        "d_mode_s": z(), "d_mode_r": z(), "d_mode_h": z(),
        "d_restore": z(), "d_replay": z(), "d_sel": z(), "d_ck": z(),
        "up_t0": np.float64(np.inf), "up_down": np.float64(0.0),
        "up_thresh": np.float64(np.inf), "up_alpha": np.float64(0.0),
    }


def lower_upgrade(upgrade: UpgradeConfig | None, spec, *, n_tasks: int,
                  job_of_task, task_region, dt: float, base_failover,
                  base_ckpt, sel_task) -> dict:
    """Lower an `UpgradeConfig` into the traced drill parameter leaves
    shared by the numpy and JAX engines (identical float arithmetic —
    the parity contract):

    * ``up_cmask`` — 1.0 on tasks of canaried jobs;
    * ``up_start`` — absolute upgrade-wave start per task
      (``t_up(job) + region_rank * wave_stagger_s``; +inf off-canary);
    * ``up_rstag`` — rollback-wave stagger per task (+inf off-canary so
      a fired rollback never restarts stable tasks);
    * ``up_wdelta`` — controller weights: mean-canary minus mean-stable
      queue in one dot product;
    * ``d_down_*`` / ``d_mode_*`` / ``d_restore`` / ``d_replay`` —
      canary-minus-base failover deltas, applied as ``base + act * d``
      with the traced 0/1 activation mask;
    * ``d_sel`` — canary selectivity delta (``sel * (scale - 1)``);
    * ``d_ck`` — canary checkpoint-interval ratio minus one, scaling the
      replay-age term (the shared attempt/draw stream is untouched —
      jobs whose base config never checkpoints ignore ``canary_ckpt``);
    * scalars ``up_t0`` (controller arming time: first canary wave end),
      ``up_down`` (per-wave downtime), ``up_thresh``, ``up_alpha``
      (EWMA coefficient ``dt / rollback_window_s``).

    `spec` is a `ChaosSpec` or per-job list; `base_failover` is the
    `per_task_failover` tuple of the base config; `sel_task` the per-task
    base selectivity vector. ``upgrade=None`` returns the inert leaves."""
    if upgrade is None:
        return inert_upgrade_leaves(n_tasks)
    from repro.core.chaos import ChaosSpec
    from repro.core.hotupdate import deploy_downtime

    jot = (np.zeros(n_tasks, dtype=int) if job_of_task is None
           else np.asarray(job_of_task))
    n_jobs = int(jot.max()) + 1 if n_tasks else 1
    if upgrade.canary_jobs is not None:
        cjob = np.zeros(n_jobs, dtype=bool)
        cjob[np.asarray(list(upgrade.canary_jobs), dtype=int)] = True
    else:
        k = max(0, min(n_jobs,
                       int(round(upgrade.canary_frac * n_jobs + 1e-9))))
        cjob = np.arange(n_jobs) < k
    cmask = cjob[jot].astype(float)

    if isinstance(spec, (list, tuple)):
        specs = list(spec)
        if len(specs) != n_jobs:
            raise ValueError(f"per-job chaos list must have one entry "
                             f"per job ({len(specs)} != {n_jobs})")
    else:
        specs = [spec] * n_jobs

    def _t_up(sp):
        sp = sp.spec if isinstance(sp, ChaosEngine) else (sp or ChaosSpec())
        ups = tuple(sp.upgrade_at)
        return float(ups[0]) if ups else float(upgrade.t_upgrade_s)

    t_up_j = np.array([_t_up(sp) for sp in specs])
    rank = region_rank(task_region, job_of_task)
    stag = float(upgrade.wave_stagger_s)
    up_down = (float(upgrade.wave_down_s)
               if upgrade.wave_down_s is not None
               else deploy_downtime(upgrade.startup, hot=upgrade.hot))
    canary = cmask > 0
    up_start = np.where(canary, t_up_j[jot] + rank * stag, np.inf)
    up_rstag = np.where(canary, rank * stag, np.inf)
    n_can = float(cmask.sum())
    n_st = float(n_tasks) - n_can
    up_wdelta = (cmask / max(n_can, 1.0)
                 - (1.0 - cmask) / max(n_st, 1.0))
    up_t0 = (float(t_up_j[cjob].min()) + up_down if cjob.any()
             else np.inf)

    b_codes, b_det, b_rs, b_rr, b_fx = base_failover
    if upgrade.canary_failover is not None:
        c_codes, c_det, c_rs, c_rr, c_fx = per_task_failover(
            upgrade.canary_failover, n_tasks, job_of_task)
    else:
        c_codes, c_det, c_rs, c_rr, c_fx = (b_codes, b_det, b_rs, b_rr,
                                            b_fx)
    fcode = lambda codes, v: (np.asarray(codes) == v).astype(float)  # noqa: E731
    d_ck = np.zeros(n_tasks)
    if upgrade.canary_ckpt is not None and base_ckpt is not None:
        if isinstance(base_ckpt, CheckpointConfig):
            b_int = np.full(n_tasks, float(base_ckpt.interval_s))
        else:
            b_int = np.array([float(c.interval_s) if c is not None
                              else np.inf for c in base_ckpt])[jot]
        ok = np.isfinite(b_int) & (b_int > 0)
        d_ck = np.where(
            ok, cmask * (float(upgrade.canary_ckpt.interval_s)
                         / np.where(ok, b_int, 1.0) - 1.0), 0.0)
    return {
        "up_cmask": cmask,
        "up_start": up_start,
        "up_rstag": up_rstag,
        "up_wdelta": up_wdelta,
        "d_down_s": cmask * ((c_det + c_rs) - (b_det + b_rs)),
        "d_down_r": cmask * ((c_det + c_rr) - (b_det + b_rr)),
        "d_down_h": cmask * ((c_det + c_fx["switch"] + c_fx["stale"])
                             - (b_det + b_fx["switch"] + b_fx["stale"])),
        "d_mode_s": cmask * (fcode(c_codes, 2) - fcode(b_codes, 2)),
        "d_mode_r": cmask * (fcode(c_codes, 1) - fcode(b_codes, 1)),
        "d_mode_h": cmask * (fcode(c_codes, 3) - fcode(b_codes, 3)),
        "d_restore": cmask * (c_fx["restore_base"] - b_fx["restore_base"]),
        "d_replay": cmask * (c_fx["replay_rate"] - b_fx["replay_rate"]),
        "d_sel": cmask * np.asarray(sel_task, float)
        * (float(upgrade.canary_sel_scale) - 1.0),
        "d_ck": d_ck,
        "up_t0": np.float64(up_t0),
        "up_down": np.float64(up_down),
        "up_thresh": np.float64(upgrade.rollback_threshold),
        "up_alpha": np.float64(min(1.0, dt / max(
            float(upgrade.rollback_window_s), dt))),
    }


@dataclasses.dataclass
class AutoscaleConfig:
    """In-trace DS2 autoscaler policy (paper §III-A), lowered into both
    engines' ticks as a traced windowed controller: per decision
    interval it EWMAs each task's utilization (records consumed plus a
    backlog-drain term over current capacity — the DS2 true-rate ratio),
    targets ``speed * need / target_utilization``, and fires a per-task
    speed rescale guarded by hysteresis, cooldown, a leaky
    actions-per-window rate limit, a failover-aware circuit breaker and
    a thrash latch. Like deployment drills, autoscale events are
    deterministic in-trace time arithmetic: they consume NO rng draws
    and never touch the pregenerated chaos timelines.

    * Rescales are *graceful* (queues persist) but pay downtime on the
      ``up_until`` leaf: ``rescale_down_s`` (default: the hot-vs-cold
      `core.hotupdate.deploy_downtime` lowering) plus
      ``move_cost_s * |delta|`` state-move seconds (default: the
      `repro.train.elastic.resize_move_seconds` reshard model at
      ``state_bytes_per_task`` / ``move_bandwidth_Bps``).
    * The breaker counts, per task, kills landing within
      ``fail_window_s`` of that task's last rescale (a crash right
      after a resize = a failed adjustment); ``breaker_failures`` such
      events open the breaker for ``breaker_reset_s``, during which the
      controller holds and the task gracefully load-sheds: its
      selectivity is scaled by ``shed_factor``.
    * The thrash latch freezes the controller for the rest of the run
      once the leaky direction-flip counter (decaying over
      ``thrash_window_s``) reaches ``thrash_flips`` — the
      autoscaler-vs-failover oscillation guard. The latch time lands in
      ``EngineMetrics.thrash_t``.
    * Source tasks never rescale (and never pay rescale downtime):
      source emission is governed by the traffic curves, not capacity.

    Defaults are sized for tick-scale drills (dt ~0.5 s, minutes-long
    horizons); production-scale values (paper: 120 s cooldowns, 12
    actions/hour, 1800 s breaker) live in `core.autoscaler.ScalerConfig`
    — the host-side decision loop this controller is lowered from. NOT
    lowered: in-trace rollback of a failed resize to the previous
    parallelism (`DS2Scaler.notify_result` keeps that host-side); the
    breaker + load-shed path is the traced graceful-degradation story.
    Queue capacities stay on the config axis (``qcap_scale``): a
    rescale changes a task's service speed, not its input buffer, and
    the numpy tick (`StreamEngine`) keeps each task's qcap fixed, so an
    in-trace qcap mutation would break parity with it."""
    t0_s: float = 0.0
    interval_s: float = 5.0
    ewma_alpha: float = 0.35
    target_utilization: float = 0.8
    backlog_drain_s: float = 60.0
    hysteresis: float = 0.15
    cooldown_s: float = 20.0
    min_scale: float = 0.25
    max_scale: float = 8.0
    max_actions: float = 12.0        # leaky bucket over rate_window_s
    rate_window_s: float = 3600.0
    breaker_failures: float = 3.0
    breaker_reset_s: float = 300.0
    fail_window_s: float = 10.0
    shed_factor: float = 0.5         # breaker-open selectivity scale
    thrash_flips: float = 6.0
    thrash_window_s: float = 60.0
    hot: bool = True
    startup: object | None = None    # core.startup.StartupConfig
    rescale_down_s: float | None = None   # override deploy_downtime
    move_cost_s: float | None = None      # s per |delta| scale unit
    state_bytes_per_task: float = 64e6
    move_bandwidth_Bps: float = 1e9


#: the 21 traced autoscale leaves (see `lower_autoscale`); ordering is
#: shared with jax_engine's axis dicts and run_config_batch's stacker
AUTOSCALE_KEYS = (
    "as_mask", "as_on", "as_t0", "as_int", "as_alpha", "as_tgt",
    "as_drain", "as_hyst", "as_cool", "as_lo", "as_hi", "as_amax",
    "as_adec", "as_bfail", "as_brs", "as_fw", "as_shed", "as_tflip",
    "as_tdec", "as_down", "as_move")


def inert_autoscale_leaves(n_tasks: int) -> dict:
    """Autoscale leaves of an autoscaler-free run: structurally present
    (stable pytree → one trace for scaled and unscaled configs) but an
    exact arithmetic no-op — ``as_on`` gates every action to False, the
    EWMA coefficient is 0.0, the shed factor multiplies by exactly 1.0.
    Large finite sentinels (1e18) stand in for +inf where the traced
    arithmetic divides or subtracts (inf/inf → nan hazards)."""
    big = np.float64(1e18)
    return {
        "as_mask": np.zeros(n_tasks),
        "as_on": np.float64(0.0), "as_t0": np.float64(0.0),
        "as_int": big, "as_alpha": np.float64(0.0),
        "as_tgt": np.float64(1.0), "as_drain": big,
        "as_hyst": big, "as_cool": np.float64(0.0),
        "as_lo": np.float64(0.0), "as_hi": big,
        "as_amax": big, "as_adec": np.float64(0.0),
        "as_bfail": big, "as_brs": np.float64(0.0),
        "as_fw": np.float64(0.0), "as_shed": np.float64(1.0),
        "as_tflip": big, "as_tdec": np.float64(0.0),
        "as_down": np.float64(0.0), "as_move": np.float64(0.0),
    }


def lower_autoscale(auto: AutoscaleConfig | None, *, n_tasks: int,
                    dt: float, is_src_task=None) -> dict:
    """Lower an `AutoscaleConfig` into the traced controller leaves
    shared by the numpy and JAX engines (identical float arithmetic —
    the parity contract). ``is_src_task`` masks source tasks out of
    ``as_mask`` (sources never rescale). ``auto=None`` returns the
    inert leaves."""
    if auto is None:
        return inert_autoscale_leaves(n_tasks)
    from repro.core.hotupdate import deploy_downtime
    from repro.train.elastic import resize_move_seconds

    if is_src_task is not None:
        mask = 1.0 - np.asarray(is_src_task, float)
    else:
        mask = np.ones(n_tasks)
    down = (float(auto.rescale_down_s)
            if auto.rescale_down_s is not None
            else deploy_downtime(auto.startup, hot=auto.hot))
    move = (float(auto.move_cost_s) if auto.move_cost_s is not None
            else resize_move_seconds(
                1.0, state_bytes_per_unit=auto.state_bytes_per_task,
                bandwidth_Bps=auto.move_bandwidth_Bps))
    return {
        "as_mask": mask,
        "as_on": np.float64(1.0),
        "as_t0": np.float64(auto.t0_s),
        "as_int": np.float64(max(float(auto.interval_s), dt)),
        "as_alpha": np.float64(auto.ewma_alpha),
        "as_tgt": np.float64(auto.target_utilization),
        "as_drain": np.float64(max(float(auto.backlog_drain_s), dt)),
        "as_hyst": np.float64(auto.hysteresis),
        "as_cool": np.float64(auto.cooldown_s),
        "as_lo": np.float64(auto.min_scale),
        "as_hi": np.float64(auto.max_scale),
        "as_amax": np.float64(auto.max_actions),
        "as_adec": np.float64(
            math.exp(-dt / max(float(auto.rate_window_s), dt))),
        "as_bfail": np.float64(auto.breaker_failures),
        "as_brs": np.float64(auto.breaker_reset_s),
        "as_fw": np.float64(auto.fail_window_s),
        "as_shed": np.float64(auto.shed_factor),
        "as_tflip": np.float64(auto.thrash_flips),
        "as_tdec": np.float64(
            math.exp(-dt / max(float(auto.thrash_window_s), dt))),
        "as_down": np.float64(down),
        "as_move": np.float64(move),
    }


class _Series(dict):
    """Read-mostly mapping op name → metric column view."""


class EngineMetrics:
    """Preallocated per-tick metric buffers.

    `t`, `source_lag` and the per-op `qps` / `backlog` entries are numpy
    array views (zero-copy, trimmed to the ticks recorded so far) — they
    support the same indexing/aggregation the old list-based metrics did.
    """

    def __init__(self, op_names: list[str], capacity: int = 1024,
                 n_jobs: int | None = None):
        self._ops = list(op_names)
        self._col = {n: j for j, n in enumerate(self._ops)}
        self._n = 0
        cap = max(capacity, 16)
        self._t = np.zeros(cap)
        self._lag = np.zeros(cap)
        self._qps = np.zeros((cap, len(self._ops)))
        self._backlog = np.zeros((cap, len(self._ops)))
        self.dropped = 0.0
        self.emitted = 0.0
        # per-job metric segments (n_jobs=None: plain single-graph engine
        # — skip the per-op accumulation, derive the view from the scalars)
        self._emitted_by_job = (np.zeros(n_jobs) if n_jobs is not None
                                else None)
        self._dropped_by_job = (np.zeros(n_jobs) if n_jobs is not None
                                else None)
        self.ckpt_attempts = 0
        self.ckpt_success = 0
        self.ckpt_failed = 0
        # (n_jobs, 3) attempts/success/failed — filled only by per-job
        # checkpoint coordinators (per-job CheckpointConfig lists)
        self.ckpt_by_job = (np.zeros((n_jobs, 3), int)
                            if n_jobs is not None else None)
        self.recoveries: list[dict] = []
        # deployment drills: wall time the auto-rollback fired (inf =
        # never). Upgrade/rollback waves are NOT recovery entries — the
        # chaos timelines only know crash failovers, and the jax engines
        # reconstruct `recoveries` from those timelines.
        self.rollback_t = math.inf
        # in-trace autoscaler: wall time the thrash latch froze the
        # controller (inf = never), number of rescale actions fired, and
        # integrated resource-seconds (sum of task speeds × dt — the
        # cost axis of the SLO-vs-cost cube; accumulated whether or not
        # an autoscaler is configured so cube rows stay comparable).
        self.thrash_t = math.inf
        self.n_rescale = 0.0
        self.resource_s = 0.0

    @property
    def emitted_by_job(self) -> np.ndarray:
        return (np.array([self.emitted]) if self._emitted_by_job is None
                else self._emitted_by_job)

    @property
    def dropped_by_job(self) -> np.ndarray:
        return (np.array([self.dropped]) if self._dropped_by_job is None
                else self._dropped_by_job)

    # -- recording (engine-internal) -----------------------------------
    def _reserve(self, n_more: int) -> None:
        need = self._n + n_more
        if need <= len(self._t):
            return
        cap = max(need, 2 * len(self._t))
        grow = lambda a: np.concatenate(  # noqa: E731
            [a, np.zeros((cap - len(a),) + a.shape[1:])])
        self._t, self._lag = grow(self._t), grow(self._lag)
        self._qps, self._backlog = grow(self._qps), grow(self._backlog)

    def _record(self, t: float, qps_row: np.ndarray, backlog_row: np.ndarray,
                lag: float) -> None:
        self._reserve(1)
        i = self._n
        self._t[i] = t
        self._lag[i] = lag
        self._qps[i] = qps_row
        self._backlog[i] = backlog_row
        self._n = i + 1

    # -- views ----------------------------------------------------------
    @property
    def t(self) -> np.ndarray:
        return self._t[:self._n]

    @property
    def source_lag(self) -> np.ndarray:
        return self._lag[:self._n]

    @property
    def qps(self) -> _Series:
        return _Series((n, self._qps[:self._n, j])
                       for n, j in self._col.items())

    @property
    def backlog(self) -> _Series:
        return _Series((n, self._backlog[:self._n, j])
                       for n, j in self._col.items())


@dataclasses.dataclass
class _OpPlan:
    name: str
    lo: int
    hi: int
    par: int
    is_source: bool
    service_rate: float
    selectivity: float
    source_rate: float
    out_edges: list["_EdgePlan"] = dataclasses.field(default_factory=list)
    # precomputed all-alive fast-path rows (speed is static per run)
    cap_row: np.ndarray | None = None       # service_rate·dt·speed
    src_row: np.ndarray | None = None       # per-task source emission
    src_sum: float = 0.0


@dataclasses.dataclass
class _EdgePlan:
    kind: str                       # partitioner name
    src: _OpPlan
    dst: _OpPlan
    static: bool                    # head-of-line acceptance family
    share: np.ndarray | None = None         # hash: normalized key mass
    raw_share: np.ndarray | None = None     # weakhash: unnormalized mass
    grp_starts: np.ndarray | None = None    # weakhash/group_rescale segments
    grp_mass: np.ndarray | None = None      # weakhash: per-group mass sums
    grp_of_dst: np.ndarray | None = None    # weakhash/group_rescale: dst→grp
    mass_of_dst: np.ndarray | None = None   # weakhash: grp_mass gathered
    blk_of_src: np.ndarray | None = None    # rescale/group_rescale: src→blk
    blk_of_dst: np.ndarray | None = None    # dst→blk (-1 = unconnected)
    dst_in_blk: np.ndarray | None = None    # bool: dst has a block
    any_unblocked: bool = False             # static: some dst has no block
    blk_idx: np.ndarray | None = None       # blk_of_dst clipped to >= 0
    n_blocks: int = 0
    dst_qcap: float = 0.0                   # backlog threshold base
    # per-edge scratch (reused every tick — avoids small-array allocations)
    ratio_buf: np.ndarray | None = None
    live_buf: np.ndarray | None = None


@dataclasses.dataclass
class RoutingPlan:
    """Speed-independent lowering of a logical graph: arena layout, per-op
    scalars and per-edge routing constants. Built once per engine by
    `build_plan` and shared (code-wise) between the numpy `StreamEngine`
    and the JAX twin in `streams/jax_engine.py` — the twin converts the
    same plan arrays to device constants instead of re-deriving them."""
    graph: LogicalGraph
    dt: float
    queue_cap: float
    offs: dict[str, int]
    n_tasks: int
    qcap: np.ndarray                     # (n_tasks,)
    ops: list[_OpPlan]                   # topo order, out_edges populated
    by_name: dict[str, _OpPlan]
    arena_starts: np.ndarray
    backlog_perm: np.ndarray
    src_cols: np.ndarray


def build_plan(graph: LogicalGraph, dt: float,
               queue_cap: float) -> RoutingPlan:
    """Lower `graph` into a `RoutingPlan` (everything in
    `StreamEngine.__init__` that does not depend on host speeds/chaos)."""
    order = graph.topo_order()
    ops = {o.name: o for o in graph.ops}
    # expand() numbers tasks contiguously per op, in graph.ops order
    offs: dict[str, int] = {}
    off = 0
    for o in graph.ops:
        offs[o.name] = off
        off += o.parallelism
    n_tasks = off

    qcap = np.zeros(n_tasks)
    for o in graph.ops:
        qcap[offs[o.name]:offs[o.name] + o.parallelism] = \
            max(o.service_rate * dt * 4.0, queue_cap)

    plan_ops: list[_OpPlan] = []
    by_name: dict[str, _OpPlan] = {}
    for name in order:
        o = ops[name]
        p = _OpPlan(name, offs[name], offs[name] + o.parallelism,
                    o.parallelism, o.is_source, o.service_rate,
                    o.selectivity, o.source_rate)
        if o.is_source:
            p.src_row = np.full(o.parallelism,
                                o.source_rate * dt / o.parallelism)
            p.src_sum = float(p.src_row.sum())
        plan_ops.append(p)
        by_name[name] = p
    for name in order:
        for e in graph.downstream(name):
            by_name[name].out_edges.append(
                _plan_edge(e, by_name[name], by_name[e.dst],
                           float(qcap[by_name[e.dst].lo])))

    # metric plumbing: one reduceat over the arena gives every op's
    # backlog; permute arena (declaration) order → topo column order
    arena_order = sorted(plan_ops, key=lambda p: p.lo)
    arena_starts = np.array([p.lo for p in arena_order])
    topo_pos = {p.name: j for j, p in enumerate(plan_ops)}
    backlog_perm = np.argsort([topo_pos[p.name] for p in arena_order])
    src_cols = np.array([j for j, p in enumerate(plan_ops) if p.is_source])
    return RoutingPlan(graph, dt, queue_cap, offs, n_tasks, qcap, plan_ops,
                       by_name, arena_starts, backlog_perm, src_cols)


def _plan_edge(e, src: _OpPlan, dst: _OpPlan, dst_qcap: float) -> _EdgePlan:
    nd = dst.par
    ns = src.par
    plan = _EdgePlan(
        kind=e.partitioner, src=src, dst=dst,
        static=e.partitioner in ("rebalance", "rescale", "forward",
                                 "hash"),
        dst_qcap=dst_qcap)
    if e.partitioner in ("hash", "weakhash"):
        # hashed key-mass share (identical construction to the
        # reference engine — same bincount over the same Zipf mass)
        nkeys = max(nd * 64, 1024)
        if e.key_skew_zipf > 0:
            mass = 1.0 / np.arange(1, nkeys + 1) ** e.key_skew_zipf
        else:
            mass = np.ones(nkeys)
        mass /= mass.sum()
        owner = (np.arange(nkeys) * 2654435761 % nd).astype(int)
        share = np.bincount(owner, weights=mass, minlength=nd)
        if e.partitioner == "hash":
            plan.share = share / share.sum()
        else:
            plan.raw_share = share
    if e.partitioner == "weakhash":
        g = max(e.n_groups, 1)
        starts = np.array([grp * nd // g for grp in range(g)])
        bounds = np.append(starts, nd)
        plan.grp_starts = starts
        # per-group mass via the same slice-sum the reference performs
        plan.grp_mass = np.array(
            [plan.raw_share[bounds[i]:bounds[i + 1]].sum()
             for i in range(g)])
        plan.grp_of_dst = np.searchsorted(starts, np.arange(nd),
                                          side="right") - 1
        plan.mass_of_dst = plan.grp_mass[plan.grp_of_dst]
    if e.partitioner == "group_rescale":
        g = max(e.n_groups, 1)
        starts = np.array([grp * nd // g for grp in range(g)])
        plan.grp_starts = starts
        plan.grp_of_dst = np.searchsorted(starts, np.arange(nd),
                                          side="right") - 1
        plan.blk_of_src = np.arange(ns) * g // ns
        plan.blk_of_dst = plan.grp_of_dst
        plan.n_blocks = g
    if e.partitioner == "rescale":
        per = max(1, nd // ns)
        src_lo = (np.arange(ns) * per) % nd
        blocks, blk_of_src = np.unique(src_lo, return_inverse=True)
        plan.blk_of_src = blk_of_src
        plan.n_blocks = len(blocks)
        blk_of_dst = np.full(nd, -1)
        for b, lo in enumerate(blocks):
            blk_of_dst[lo:lo + per] = b
        plan.blk_of_dst = blk_of_dst
    if plan.blk_of_dst is not None:
        plan.dst_in_blk = plan.blk_of_dst >= 0
        plan.any_unblocked = not bool(plan.dst_in_blk.all())
        plan.blk_idx = np.clip(plan.blk_of_dst, 0, None)
    plan.ratio_buf = np.empty(nd)
    plan.live_buf = np.empty(nd, bool)
    return plan


# ----------------------------------------------------------------------
# Per-task failover normalization (per-job configs, paper §III-B)
# ----------------------------------------------------------------------
def per_task_failover(failover, n_tasks: int,
                      job_of_task: np.ndarray | None = None):
    """Normalize a `FailoverConfig` — or a per-job sequence of them — into
    per-task vectors ``(mode_codes i8, detect, restart_single,
    restart_region, extras)`` where ``extras`` is a dict of per-task
    hybrid-replication vectors: ``switch`` / ``stale`` (hot-standby
    failover latency + staleness replay), ``restore_base`` /
    ``replay_rate`` (passive-restore cost model; restore_base is scaled
    by the brownout factor at kill time, replay_rate by checkpoint age)
    and ``stagger`` (per-rank lazy-load region ready-time spacing).

    Mode codes follow `core.chaos.failover_mode_codes` (0 none, 1 region,
    2 single_task, 3 hot_standby). A sequence means one config per job of a packed arena
    (`job_of_task` maps tasks to jobs; `None` entries fall back to the
    default config), which is how per-job failover policies reach both
    engines and the chaos timeline: everything downstream consumes only
    the per-task vectors, so a shared config is just the constant
    vector."""
    from repro.core.chaos import failover_mode_codes

    if failover is None:
        failover = FailoverConfig()
    if isinstance(failover, FailoverConfig):
        c = failover
        extras = {k: np.full(n_tasks, float(getattr(c, a))) for k, a in
                  _EXTRA_FIELDS}
        return (failover_mode_codes(c.mode, n_tasks),
                np.full(n_tasks, float(c.detect_s)),
                np.full(n_tasks, float(c.single_restart_s)),
                np.full(n_tasks, float(c.region_restart_s)),
                extras)
    cfgs = [c if c is not None else FailoverConfig() for c in failover]
    if job_of_task is None:
        if len(cfgs) != 1:
            raise ValueError(
                "a per-job failover list needs a packed arena "
                f"(got {len(cfgs)} configs for a single-job graph)")
        job_of_task = np.zeros(n_tasks, dtype=int)
    job_of_task = np.asarray(job_of_task)
    n_jobs = int(job_of_task.max()) + 1
    if len(cfgs) != n_jobs:
        raise ValueError(f"per-job failover list must have one entry per "
                         f"job ({len(cfgs)} != {n_jobs})")
    code_of_job = np.concatenate(
        [failover_mode_codes(c.mode, 1) for c in cfgs])
    extras = {k: np.array([float(getattr(c, a)) for c in cfgs])[job_of_task]
              for k, a in _EXTRA_FIELDS}
    return (code_of_job[job_of_task].astype(np.int8),
            np.array([c.detect_s for c in cfgs])[job_of_task],
            np.array([c.single_restart_s for c in cfgs])[job_of_task],
            np.array([c.region_restart_s for c in cfgs])[job_of_task],
            extras)


# extras-dict key → FailoverConfig attribute
_EXTRA_FIELDS = (("switch", "standby_switch_s"),
                 ("stale", "standby_staleness_s"),
                 ("restore_base", "restore_base_s"),
                 ("replay_rate", "replay_rate"),
                 ("stagger", "lazyload_stagger_s"))


def region_rank(task_region: np.ndarray,
                job_of_task: np.ndarray | None) -> np.ndarray:
    """Per-task rank of its failure region *within its job* (the job's
    first region is rank 0). This is the deterministic ordering shared by
    lazy-load ready-time schedules (`lazy_ready_extra`) and
    deployment-drill rolling-upgrade waves (`lower_upgrade`): wave /
    ready slot ``i`` covers the job's rank-``i`` region."""
    task_region = np.asarray(task_region)
    if job_of_task is None:
        first = task_region.min() if len(task_region) else 0
    else:
        job_of_task = np.asarray(job_of_task)
        n_jobs = int(job_of_task.max()) + 1
        first_of_job = np.full(n_jobs, np.iinfo(np.int64).max)
        np.minimum.at(first_of_job, job_of_task, task_region)
        first = first_of_job[job_of_task]
    return (task_region - first).astype(float)


def lazy_ready_extra(stagger: np.ndarray, task_region: np.ndarray | None,
                     job_of_task: np.ndarray | None) -> np.ndarray:
    """Per-task lazy-load restore penalty: region ``rank`` within its job
    times the stagger. Models the State-LazyLoad ready-time schedule —
    regions materialize in priority order, and a task blocks only until
    its OWN region is restored, so later-ranked regions pay
    ``rank * stagger`` extra downtime. No regions → rank 0 → zero."""
    stagger = np.asarray(stagger, dtype=float)
    if task_region is None or not np.any(stagger):
        return np.zeros_like(stagger)
    return region_rank(task_region, job_of_task) * stagger


# ----------------------------------------------------------------------
# Tensorized plan lowering (flat edge tensors for the JAX segment-sum
# tick — see streams/jax_engine.py for the consuming kernel)
# ----------------------------------------------------------------------
@dataclasses.dataclass(eq=False)
class PhaseTensors:
    """Flat routing tensors of one tick *phase*.

    A phase is one slot of the tick's static schedule: every op consumes
    (and produces) in exactly one phase, every edge routes in exactly one
    phase, and all of a phase's edges execute as ONE batch of gathers +
    segment reductions over the concatenated destination-channel axis
    (``D`` entries = sum of the phase's edges' dst parallelisms). Blocks
    (rescale families) and key-groups (weakhash) are numbered globally
    within the phase with one trailing dummy segment each, so one
    `segment_sum` covers every edge's blocks/groups at once. `share` /
    `mass` are float routing constants — the JAX engine passes them as
    traced parameters, NOT compile-time constants, so they are excluded
    from the trace-cache key."""
    cons_mask: np.ndarray          # (n_tasks,) f64: ops consuming here
    consumes: bool
    n_edges: int                   # E
    D: int                         # flat dst-channel entries
    dst_task: np.ndarray           # (D,) i32 arena task id per entry
    edge_of: np.ndarray            # (D,) i32 phase-local edge index
    job_of_entry: np.ndarray       # (D,) i32 job of the dst op
    src_op_of_edge: np.ndarray     # (E,) i32 topo op index of the source
    is_fwd: np.ndarray             # (D,) bool  forward
    is_blk: np.ndarray             # (D,) bool  rescale / group_rescale
    is_hash: np.ndarray            # (D,) bool  hash
    is_weakhash: np.ndarray        # (D,) bool
    is_backlog: np.ndarray         # (D,) bool
    is_norm: np.ndarray            # (D,) f64   rebalance|weakhash|backlog
    acc_static: np.ndarray         # (D,) bool  head-of-line accept family
    acc_block: np.ndarray          # (D,) bool  per-block accept
    fwd_src: np.ndarray            # (D,) i32   src task for forward
    B: int                         # blocks in phase (dummy slot = B)
    blk_of: np.ndarray             # (D,) i32
    dst_in_blk: np.ndarray         # (D,) f64
    bsrc_task: np.ndarray          # (Sb,) i32  blocky edges' src tasks
    bsrc_blk: np.ndarray           # (Sb,) i32
    G: int                         # weakhash groups (dummy slot = G)
    grp_of: np.ndarray             # (D,) i32
    share: np.ndarray              # (D,) f64  hash key-mass share (traced)
    mass: np.ndarray               # (D,) f64  weakhash group mass (traced)


@dataclasses.dataclass(eq=False)
class CompactPhase:
    """Sparse twin of `PhaseTensors`: per-phase *active* index sets in
    row-table form.

    Every segment reduction of the dense tick (per-op totals, per-edge
    normalizers, per-group capacities, per-block rates, head-of-line
    minima, per-job metric sums) becomes a small **row table** here: one
    row per segment, holding the segment's member indices padded to a
    pow2 row length with a 0.0 mask column. The JAX tick then reduces
    ``values[idx] * mask`` along the row axis — a vectorized
    gather+reduce whose cost scales with the phase's live entries — in
    place of XLA scatter-based `segment_sum`/`segment_min` over the
    whole arena (the dense tick's dominant cost on deep pipelines).

    Everything except the array *shapes* is a traced parameter of the
    tick (`streams.jax_engine._build_compact_run`), the same pow2
    bucketing discipline as seed-batch padding: the trace-cache key is
    only the shape signature (`sig`), so two plans whose index sets land
    in the same buckets — e.g. same-shape graphs with different
    partitioners, placements or routing tables — share one compiled
    trace."""
    consumes: bool
    D: int                         # flat dst-channel entries (exact)
    E: int                         # edges (exact)
    B: int                         # blocks (+1 dummy row in br/bs)
    G: int                         # weakhash groups (+1 dummy row)
    # consumption (arena-wide elementwise, mask traced)
    cons_mask: np.ndarray          # (n_tasks,) f64
    # qps rows: one row per consuming op (arena indices)
    q_idx: np.ndarray              # (Rq, Lq) i32
    q_mask: np.ndarray             # (Rq, Lq) f64
    q_ops: np.ndarray              # (Rq,) i32 topo op index
    # emitted rows: one row per job with active sources (arena indices)
    e_idx: np.ndarray              # (Re, Le) i32
    e_mask: np.ndarray             # (Re, Le) f64
    e_jobs: np.ndarray             # (Re,) i32
    # per-source-op slots of the phase's edges (arena indices)
    s_idx: np.ndarray              # (Rs, Ls) i32
    s_mask: np.ndarray             # (Rs, Ls) f64
    slot_of_edge: np.ndarray       # (E,) i32
    slot_ops: np.ndarray           # (Rs,) i32 topo op index
    # dst-channel entry arrays (exact D, as in the dense phase)
    dst_task: np.ndarray           # (D,) i32
    fwd_src: np.ndarray            # (D,) i32
    edge_of: np.ndarray            # (D,) i32
    grp_of: np.ndarray             # (D,) i32 (dummy = G)
    blk_of: np.ndarray             # (D,) i32 (dummy = B)
    m_fwd: np.ndarray              # (D,) f64 partitioner masks (traced —
    m_blk: np.ndarray              # (D,) f64  unlike the dense bools,
    m_hash: np.ndarray             # (D,) f64  these are runtime params)
    m_weakhash: np.ndarray         # (D,) f64
    m_backlog: np.ndarray          # (D,) f64
    is_norm: np.ndarray            # (D,) f64
    m_acc_static: np.ndarray       # (D,) f64
    m_acc_block: np.ndarray        # (D,) f64
    dst_in_blk: np.ndarray         # (D,) f64
    share: np.ndarray              # (D,) f64
    mass: np.ndarray               # (D,) f64
    # edge / group / block rows (indices into the D axis)
    er_idx: np.ndarray             # (E, Le2) i32
    er_mask: np.ndarray            # (E, Le2) f64
    gr_idx: np.ndarray             # (G+1, Lg) i32 (last row all-pad)
    gr_mask: np.ndarray            # (G+1, Lg) f64
    br_idx: np.ndarray             # (B+1, Lb) i32 (last row all-pad)
    br_mask: np.ndarray            # (B+1, Lb) f64
    # block-source rows (arena indices of the blocky edges' src tasks)
    bs_idx: np.ndarray             # (B+1, Lbs) i32 (last row all-pad)
    bs_mask: np.ndarray            # (B+1, Lbs) f64
    # dropped rows: one row per dst job (indices into the D axis)
    dj_idx: np.ndarray             # (Rd, Ld) i32
    dj_mask: np.ndarray            # (Rd, Ld) f64
    dj_jobs: np.ndarray            # (Rd,) i32

    @property
    def sig(self) -> tuple:
        shapes = tuple(getattr(self, f.name).shape
                       for f in dataclasses.fields(self)
                       if isinstance(getattr(self, f.name), np.ndarray))
        return (self.consumes, self.D, self.E, self.B, self.G) + shapes

    def traced(self) -> dict:
        """The per-phase traced-parameter dict (everything but `sig`)."""
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)
                if isinstance(getattr(self, f.name), np.ndarray)}


@dataclasses.dataclass(eq=False)
class TensorPlan:
    """Phase-scheduled flat-tensor lowering of a `RoutingPlan`.

    Equality / hashing go through `key` — a digest of every static
    (integer/structure) array — so two same-shaped graphs share one
    compiled trace while float parameters stay traced. The *number of
    phases* is bounded by the longest in-tick pipeline chain of a single
    job (plus head-of-line ordering between same-destination edges), NOT
    by the number of ops/edges: packing K jobs into one arena leaves it
    unchanged, which is what makes the jitted tick O(1) in graph size.

    ``mode`` selects the lowering flavor: ``"dense"`` phases are
    `PhaseTensors` (arena-wide masks, index structure baked into the
    trace), ``"compact"`` phases are `CompactPhase` (pow2-bucketed
    active index sets passed as traced parameters — per-tick compute
    scales with the live edges/tasks of each phase, and the trace key is
    only the bucket signature)."""
    n_tasks: int
    n_ops: int
    n_jobs: int
    n_phases: int
    op_of_task: np.ndarray         # (n_tasks,) i32 topo op index
    is_src_task: np.ndarray        # (n_tasks,) f64
    job_of_task: np.ndarray        # (n_tasks,) i32
    par_of_op: np.ndarray          # (n_ops,) f64  max(parallelism, 1)
    src_mask_ops: np.ndarray       # (n_ops,) f64  1.0 at source columns
    phases: list
    key: tuple = ()
    mode: str = "dense"

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return isinstance(other, TensorPlan) and self.key == other.key


def _phase_schedule(plan: RoutingPlan):
    """Assign every op a consumption phase and every edge a routing phase
    such that executing each phase as one parallel batch reproduces the
    sequential numpy tick exactly:

    * an op consumes only after every in-edge has deposited
      (``cphase(op) > phase(e)`` for in-edges ``e``);
    * an edge routes no earlier than its source op produces
      (``phase(e) >= cphase(src)`` — same phase is fine, consumption runs
      before routing inside a phase, as in the numpy op turn);
    * edges sharing a destination op serialize in their numpy order
      (free-credit reads/writes on the shared destination queue must nest
      exactly), which also guarantees each dst op receives at most ONE
      edge per phase — deposits within a phase are scatter-unique.
    """
    ops = plan.ops
    topo_idx = {p.name: i for i, p in enumerate(ops)}
    in_waves: list[list[int]] = [[] for _ in ops]
    last_wave_into: dict[int, int] = {}
    cphase = [0] * len(ops)
    edges = []                               # (src_oi, dst_oi, ep, phase)
    for oi, p in enumerate(ops):
        cphase[oi] = max((w + 1 for w in in_waves[oi]), default=0)
        for ep in p.out_edges:
            di = topo_idx[ep.dst.name]
            w = cphase[oi]
            if di in last_wave_into:
                w = max(w, last_wave_into[di] + 1)
            last_wave_into[di] = w
            in_waves[di].append(w)
            edges.append((oi, di, ep, w))
    n_phases = max(cphase + [e[3] for e in edges], default=0) + 1
    return cphase, edges, n_phases


def _bucket(n: int) -> int:
    """Pow2 bucket size for a compact index set (0 stays 0)."""
    return 1 << (n - 1).bit_length() if n > 1 else n


def _phase_work_estimate(plan: RoutingPlan, cphase, edges, n_phases):
    """(dense_work, compact_work) rough per-tick reduction-element counts
    of the two lowerings — the auto-mode selector. The dense tick pays
    arena-sized scatter-based segment reductions per phase (per-job
    emitted + per-op qps when the phase consumes, per-op totals when it
    routes); the compact tick pays row gathers over just the phase's
    active / source tasks. Costs the two modes share (elementwise arena
    passes, dst-axis work, deposits) are left out of both sides."""
    ops = plan.ops
    n_tasks = plan.n_tasks
    dense = compact = 0
    for f in range(n_phases):
        act = sum(p.hi - p.lo for oi, p in enumerate(ops)
                  if cphase[oi] == f)
        src_ops = {oi for (oi, _, _, w) in edges if w == f}
        s = sum(ops[oi].par for oi in src_ops)
        dense += (2 * n_tasks if act else 0) + (n_tasks if src_ops else 0)
        compact += 2 * act + s
    return dense, compact


#: the tick lowerings an entry point's ``phase_mode`` may name: the
#: dense parity reference, the compact row-table tick, or the choice
#: between them by `select_phase_mode`'s work estimate
PHASE_MODES = ("dense", "compact", "auto")


def select_phase_mode(plan: RoutingPlan, mode: str = "auto",
                      seed_width: int = 1) -> str:
    """Resolve a ``"auto"`` phase-lowering request: compact when the
    arena-sized segment reductions the sparse lowering eliminates
    clearly dominate its row-gather cost (deep pipelines / big
    multi-job arenas where each phase touches a small slice of the
    arena), dense otherwise. ``mode`` must be one of `PHASE_MODES`.

    ``seed_width`` is the seed-axis batch width the tick will run
    under (S, or S·C for config grids). The work estimate scores one
    tick, but two compact costs amortize across the vmap width: the
    fixed per-tick overhead (index-table loads are shared, not
    batched) and the absolute-size floor (a 256-wide batch over a
    56-task graph is real work even though one tick isn't). So the
    floor scales with ``n_tasks · width`` and the dense-favoring
    margin decays from 2.5x at width 1 (the single-seed calibration)
    toward the asymptotic ~2.1x row-gather penalty — wide batches
    over small deep-pipeline graphs now pick compact, while shallow
    graphs (estimate ratio ≈ 2) stay dense at any width."""
    if mode not in PHASE_MODES:
        raise ValueError(
            f"phase mode must be {'|'.join(PHASE_MODES)}: {mode!r}")
    if mode != "auto":
        return mode
    w = max(int(seed_width), 1)
    if plan.n_tasks * w < 256:
        return "dense"
    cphase, edges, n_phases = _phase_schedule(plan)
    dense, compact = _phase_work_estimate(plan, cphase, edges, n_phases)
    margin = 2.125 + 0.375 / w
    return "compact" if dense >= margin * compact else "dense"


def lower_tensor_plan(plan: RoutingPlan,
                      job_of_op: np.ndarray | None = None,
                      mode: str = "dense",
                      seed_width: int = 1) -> TensorPlan:
    """Lower a `RoutingPlan` into the flat per-phase tensors consumed by
    the JAX segment-sum tick (`streams/jax_engine.py`).

    ``mode`` is ``"dense"`` (arena-wide `PhaseTensors`, the tests'
    parity reference), ``"compact"`` (pow2-bucketed `CompactPhase`
    index sets — per-tick compute scales with the live edges per phase;
    the lowering the chip runs) or ``"auto"`` (`select_phase_mode`
    picks one of the two by the work estimate at the given
    ``seed_width``)."""
    import hashlib

    mode = select_phase_mode(plan, mode, seed_width)
    ops = plan.ops
    n_ops = len(ops)
    n_tasks = plan.n_tasks
    if job_of_op is None:
        job_of_op = np.zeros(n_ops, dtype=int)
    job_of_op = np.asarray(job_of_op)
    n_jobs = int(job_of_op.max()) + 1 if n_ops else 1

    op_of_task = np.zeros(n_tasks, np.int32)
    is_src_task = np.zeros(n_tasks)
    job_of_task = np.zeros(n_tasks, np.int32)
    for oi, p in enumerate(ops):
        op_of_task[p.lo:p.hi] = oi
        job_of_task[p.lo:p.hi] = job_of_op[oi]
        if p.is_source:
            is_src_task[p.lo:p.hi] = 1.0
    par_of_op = np.array([max(p.par, 1) for p in ops], float)
    src_mask_ops = np.array([1.0 if p.is_source else 0.0 for p in ops])

    cphase, edges, n_phases = _phase_schedule(plan)
    phases: list[PhaseTensors] = []
    h = hashlib.sha1()

    def feed(*arrays):
        for a in arrays:
            h.update(np.ascontiguousarray(a).tobytes())

    feed(op_of_task, is_src_task.astype(np.int8), job_of_task,
         np.asarray(cphase, np.int32))
    for f in range(n_phases):
        cons = np.zeros(n_tasks)
        for oi, p in enumerate(ops):
            if cphase[oi] == f:
                cons[p.lo:p.hi] = 1.0
        mine = [(oi, di, ep) for (oi, di, ep, w) in edges if w == f]
        assert len({di for _, di, _ in mine}) == len(mine), \
            "phase schedule must keep destination ops unique per phase"
        E = len(mine)
        cols = {k: [] for k in
                ("dst_task", "edge_of", "job_of_entry", "is_fwd", "is_blk",
                 "is_hash", "is_weakhash", "is_backlog", "acc_static",
                 "acc_block", "fwd_src", "blk_of", "dst_in_blk", "grp_of",
                 "share", "mass")}
        src_op_of_edge = np.array([oi for oi, _, _ in mine], np.int32)
        bsrc_task, bsrc_blk = [], []
        blk_base = grp_base = 0
        n_blocks_total = sum(ep.n_blocks for _, _, ep in mine)
        n_groups_total = sum(len(ep.grp_starts)
                             if ep.kind == "weakhash" else 0
                             for _, _, ep in mine)
        for ei, (oi, di, ep) in enumerate(mine):
            nd = ep.dst.hi - ep.dst.lo
            kind = ep.kind
            blocky = kind in ("rescale", "group_rescale")
            cols["dst_task"].append(np.arange(ep.dst.lo, ep.dst.hi,
                                              dtype=np.int32))
            cols["edge_of"].append(np.full(nd, ei, np.int32))
            cols["job_of_entry"].append(
                np.full(nd, int(job_of_op[di]), np.int32))
            cols["is_fwd"].append(np.full(nd, kind == "forward"))
            cols["is_blk"].append(np.full(nd, blocky))
            cols["is_hash"].append(np.full(nd, kind == "hash"))
            cols["is_weakhash"].append(np.full(nd, kind == "weakhash"))
            cols["is_backlog"].append(np.full(nd, kind == "backlog"))
            cols["acc_static"].append(np.full(nd, ep.static))
            cols["acc_block"].append(np.full(nd, kind == "group_rescale"))
            cols["fwd_src"].append(
                np.arange(ep.src.lo, ep.src.hi, dtype=np.int32)
                if kind == "forward" else np.zeros(nd, np.int32))
            if blocky:
                cols["blk_of"].append(
                    (blk_base + ep.blk_idx).astype(np.int32))
                cols["dst_in_blk"].append(ep.dst_in_blk.astype(float))
                bsrc_task.append(np.arange(ep.src.lo, ep.src.hi,
                                           dtype=np.int32))
                bsrc_blk.append((blk_base + ep.blk_of_src)
                                .astype(np.int32))
                blk_base += ep.n_blocks
            else:
                cols["blk_of"].append(np.full(nd, n_blocks_total, np.int32))
                cols["dst_in_blk"].append(np.zeros(nd))
            if kind == "weakhash":
                cols["grp_of"].append(
                    (grp_base + ep.grp_of_dst).astype(np.int32))
                cols["mass"].append(ep.mass_of_dst.astype(float))
                grp_base += len(ep.grp_starts)
            else:
                cols["grp_of"].append(np.full(nd, n_groups_total, np.int32))
                cols["mass"].append(np.zeros(nd))
            cols["share"].append(ep.share.astype(float)
                                 if kind == "hash" else np.zeros(nd))
        cat = {k: (np.concatenate(v) if v else
                   np.zeros(0, np.int32 if k in
                            ("dst_task", "edge_of", "job_of_entry",
                             "fwd_src", "blk_of", "grp_of") else float))
               for k, v in cols.items()}
        ph = PhaseTensors(
            cons_mask=cons, consumes=bool(cons.any()), n_edges=E,
            D=len(cat["dst_task"]), dst_task=cat["dst_task"],
            edge_of=cat["edge_of"], job_of_entry=cat["job_of_entry"],
            src_op_of_edge=src_op_of_edge,
            is_fwd=cat["is_fwd"].astype(bool),
            is_blk=cat["is_blk"].astype(bool),
            is_hash=cat["is_hash"].astype(bool),
            is_weakhash=cat["is_weakhash"].astype(bool),
            is_backlog=cat["is_backlog"].astype(bool),
            is_norm=(cat["is_weakhash"].astype(bool)
                     | cat["is_backlog"].astype(bool)
                     | ~(cat["is_fwd"].astype(bool)
                         | cat["is_blk"].astype(bool)
                         | cat["is_hash"].astype(bool))).astype(float),
            acc_static=cat["acc_static"].astype(bool),
            acc_block=cat["acc_block"].astype(bool),
            fwd_src=cat["fwd_src"], B=n_blocks_total,
            blk_of=cat["blk_of"], dst_in_blk=cat["dst_in_blk"],
            bsrc_task=(np.concatenate(bsrc_task) if bsrc_task
                       else np.zeros(0, np.int32)),
            bsrc_blk=(np.concatenate(bsrc_blk) if bsrc_blk
                      else np.zeros(0, np.int32)),
            G=n_groups_total, grp_of=cat["grp_of"],
            share=cat["share"], mass=cat["mass"])
        if mode == "compact":
            phases.append(_compact_phase(ph, ops, cphase, f, mine,
                                         job_of_op))
        else:
            phases.append(ph)
            feed(np.int64([f, E, ph.D, ph.B, ph.G]), cons.astype(np.int8),
                 ph.dst_task, ph.edge_of, ph.job_of_entry,
                 ph.src_op_of_edge,
                 ph.is_fwd, ph.is_blk, ph.is_hash, ph.is_weakhash,
                 ph.is_backlog, ph.acc_static, ph.acc_block, ph.fwd_src,
                 ph.blk_of, ph.dst_in_blk.astype(np.int8), ph.bsrc_task,
                 ph.bsrc_blk, ph.grp_of)
    if mode == "compact":
        # only the bucket signature keys the trace: the index contents
        # are traced parameters, so same-bucket plans share one trace
        key = (mode, n_tasks, n_ops, n_jobs, n_phases,
               tuple(p.sig for p in phases))
    else:
        key = (n_tasks, n_ops, n_jobs, n_phases, h.hexdigest())
    return TensorPlan(n_tasks, n_ops, n_jobs, n_phases, op_of_task,
                      is_src_task, job_of_task, par_of_op, src_mask_ops,
                      phases, key, mode=mode)


def _rows(groups, n_rows=None, dtype=np.int32):
    """Row-table builder: `groups` is a list of 1-D index arrays, one
    per segment. Returns ``(idx, mask)`` of shape ``(R, L)`` with ``L``
    the pow2 bucket of the longest group — pads gather index 0 under a
    0.0 mask. `n_rows` appends all-pad rows up to a fixed row count
    (the +1 dummy rows of block/group tables)."""
    R = n_rows if n_rows is not None else len(groups)
    L = _bucket(max((len(g) for g in groups), default=0)) or 1
    idx = np.zeros((R, L), dtype)
    mask = np.zeros((R, L))
    for r, g in enumerate(groups):
        idx[r, :len(g)] = g
        mask[r, :len(g)] = 1.0
    return idx, mask


def _compact_phase(ph: PhaseTensors, ops, cphase, f, mine,
                   job_of_op) -> CompactPhase:
    """Convert one dense phase into its row-table sparse twin. The
    numerics contract vs the dense phase is exact up to the reduction
    order inside a row: rows preserve the dst-axis/arena order of each
    segment's members and pads contribute exact +0.0 (sums) or +inf
    (minima), so compact == dense at 1e-12 over full runs."""
    cons_ops = [(oi, p) for oi, p in enumerate(ops) if cphase[oi] == f]
    q_idx, q_mask = _rows([np.arange(p.lo, p.hi) for _, p in cons_ops])
    q_ops = np.array([oi for oi, _ in cons_ops], np.int32)
    by_job: dict[int, list] = {}
    for oi, p in enumerate(ops):
        if cphase[oi] == f and p.is_source:
            by_job.setdefault(int(job_of_op[oi]), []).append(
                np.arange(p.lo, p.hi))
    e_jobs = sorted(by_job)
    e_idx, e_mask = _rows([np.concatenate(by_job[j]) for j in e_jobs])

    # one slot per distinct source op of the phase's edges
    slot_index: dict[int, int] = {}
    slot_of_edge = np.zeros(ph.n_edges, np.int32)
    for ei, (oi, _, _) in enumerate(mine):
        if oi not in slot_index:
            slot_index[oi] = len(slot_index)
        slot_of_edge[ei] = slot_index[oi]
    slots = sorted(slot_index, key=slot_index.get)
    s_idx, s_mask = _rows([np.arange(ops[oi].lo, ops[oi].hi)
                           for oi in slots])

    # edge / group / block rows index into the D axis; block-source rows
    # index the arena. Dummy rows (B / G) stay all-pad: their sums are
    # 0.0 and their minima inf, matching the dense dummy segments.
    d_pos = np.arange(ph.D)
    er_idx, er_mask = _rows([d_pos[ph.edge_of == ei]
                             for ei in range(ph.n_edges)])
    gr_idx, gr_mask = _rows([d_pos[ph.grp_of == g] for g in range(ph.G)],
                            n_rows=ph.G + 1)
    br_idx, br_mask = _rows([d_pos[ph.blk_of == b] for b in range(ph.B)],
                            n_rows=ph.B + 1)
    bs_idx, bs_mask = _rows([ph.bsrc_task[ph.bsrc_blk == b]
                             for b in range(ph.B)], n_rows=ph.B + 1)
    dj = sorted(set(int(j) for j in ph.job_of_entry))
    dj_idx, dj_mask = _rows([d_pos[ph.job_of_entry == j] for j in dj])

    return CompactPhase(
        consumes=ph.consumes, D=ph.D, E=ph.n_edges, B=ph.B, G=ph.G,
        cons_mask=ph.cons_mask,
        q_idx=q_idx, q_mask=q_mask, q_ops=q_ops,
        e_idx=e_idx, e_mask=e_mask,
        e_jobs=np.array(e_jobs, np.int32),
        s_idx=s_idx, s_mask=s_mask, slot_of_edge=slot_of_edge,
        slot_ops=np.array(slots, np.int32),
        dst_task=ph.dst_task, fwd_src=ph.fwd_src, edge_of=ph.edge_of,
        grp_of=ph.grp_of, blk_of=ph.blk_of,
        m_fwd=ph.is_fwd.astype(float), m_blk=ph.is_blk.astype(float),
        m_hash=ph.is_hash.astype(float),
        m_weakhash=ph.is_weakhash.astype(float),
        m_backlog=ph.is_backlog.astype(float), is_norm=ph.is_norm,
        m_acc_static=ph.acc_static.astype(float),
        m_acc_block=ph.acc_block.astype(float),
        dst_in_blk=ph.dst_in_blk, share=ph.share, mass=ph.mass,
        er_idx=er_idx, er_mask=er_mask, gr_idx=gr_idx, gr_mask=gr_mask,
        br_idx=br_idx, br_mask=br_mask, bs_idx=bs_idx, bs_mask=bs_mask,
        dj_idx=dj_idx, dj_mask=dj_mask,
        dj_jobs=np.array(dj, np.int32))


# ----------------------------------------------------------------------
# Multi-job mega-arena (cluster-perspective co-location, paper §V)
# ----------------------------------------------------------------------
@dataclasses.dataclass
class JobSlice:
    """One job's footprint inside a packed arena.

    ``task_lo:task_hi`` is the job's contiguous task-id slice of the flat
    arena; ``op_cols`` are its columns in the plan's topo op order (also
    contiguous — jobs have no cross edges, so the combined topo order is
    the per-job topo orders concatenated); ``src_cols`` are the subset of
    ``op_cols`` belonging to the job's sources (per-job source lag);
    ``hosts`` maps the job's *local* host ids to the global pool."""
    index: int
    name: str
    graph: LogicalGraph            # the original, un-namespaced graph
    prefix: str
    task_lo: int
    task_hi: int
    op_cols: np.ndarray            # indices into plan.ops (topo order)
    op_names: list[str]            # original names, aligned with op_cols
    src_cols: np.ndarray           # subset of op_cols: the job's sources
    hosts: np.ndarray              # local host id -> global host id
    region_lo: int = 0
    region_hi: int = 0
    # job-local host id per job-local task index (the placement BEFORE
    # lifting through `hosts`) — per-job ChaosSpecs draw stragglers and
    # kills in this local domain, exactly like an independent run
    local_host: np.ndarray | None = None


@dataclasses.dataclass
class PackedArena:
    """K co-located job graphs lowered into ONE flat task arena.

    Arena layout
    ------------
    * Ops of job j are namespaced ``f"j{j}."`` and concatenated in job
      order, so `build_plan` on the combined graph numbers every job's
      tasks contiguously with arena-global offsets — one `RoutingPlan`,
      one task arena, one engine tick for the whole co-located fleet.
      Jobs have no cross edges: records never flow between jobs.
    * Hosts are a single shared pool of size ``n_hosts``. Each job keeps
      its *local* round-robin placement (``local_tid % n_hosts_local``,
      identical to an independent `expand`) and a per-job ``hosts`` map
      lifts local host ids into the pool — overlapping maps co-locate
      jobs on shared hosts, disjoint maps reproduce K independent
      clusters exactly (the parity anchor in tests/test_colocation.py).
    * Failure regions never merge across jobs (no cross-job channels), so
      the arena's region list is the per-job region lists, offset.

    Shared-host kill semantics: a chaos kill of host h downs the tasks of
    EVERY job placed on h — under region failover each affected job's hit
    regions restart; under single_task each affected job drops in-flight
    records routed to its dead tasks. Cross-job interference is therefore
    a first-class swept quantity (one host kill couples many jobs'
    recovery, the paper's cluster-level coupling).

    Per-job metric segments: `job_of_op` / `job_of_task` segment the
    per-op metric columns and the task arena by job; engines use them for
    per-job emitted/dropped accounting and per-job recovery attribution
    (``"job"`` key on recovery events).
    """
    graph: LogicalGraph            # combined, namespaced
    plan: RoutingPlan
    phys: PhysicalGraph
    jobs: list[JobSlice]
    job_of_task: np.ndarray        # (n_tasks,) int
    job_of_op: np.ndarray          # (n_ops,) int, topo order
    n_hosts: int                   # global pool size (kill-draw domain)

    @property
    def n_jobs(self) -> int:
        return len(self.jobs)

    @property
    def dt(self) -> float:
        return self.plan.dt

    @property
    def queue_cap(self) -> float:
        return self.plan.queue_cap

    def job(self, name_or_index) -> JobSlice:
        if isinstance(name_or_index, int):
            return self.jobs[name_or_index]
        return next(j for j in self.jobs if j.name == name_or_index)

    def lift_kills(self, job: int, host_kill_at) -> tuple:
        """Translate a job-local ``host_kill_at`` schedule into the global
        host pool (chaos specs address pool hosts; a drill written against
        one job's local hosts is lifted through that job's host map)."""
        m = self.jobs[job].hosts
        return tuple((t, int(m[h])) for (t, h) in host_kill_at)


def pack_arena(graphs, host_map="shared", *, n_hosts: int = 8,
               dt: float = 0.5, queue_cap: float = 256.0,
               names=None) -> PackedArena:
    """Lower K co-located job graphs into one `PackedArena`.

    `host_map` controls co-location:
      * ``"shared"``   — every job uses the same pool hosts 0..n_hosts-1
                         (full co-location; host kills couple all jobs);
      * ``"disjoint"`` — job j uses hosts ``[j*n_hosts, (j+1)*n_hosts)``
                         (no interference; packed == K independent runs);
      * explicit       — sequence of K int arrays, each mapping the job's
                         local host ids ``0..n_hosts-1`` to pool ids.

    `names` optionally labels jobs (default ``f"j{j}.{graph.name}"``).
    """
    graphs = list(graphs)
    if not graphs:
        raise ValueError("pack_arena requires at least one job graph")
    k = len(graphs)
    if host_map == "shared":
        maps = [np.arange(n_hosts) for _ in range(k)]
    elif host_map == "disjoint":
        maps = [j * n_hosts + np.arange(n_hosts) for j in range(k)]
    else:
        maps = [np.asarray(m, dtype=int) for m in host_map]
        if len(maps) != k:
            raise ValueError(f"host_map has {len(maps)} rows for {k} jobs")
        if any(len(m) != n_hosts for m in maps):
            raise ValueError("each host_map row must map all local hosts")
    n_pool = int(max(m.max() for m in maps)) + 1

    prefixes = [f"j{j}." for j in range(k)]
    parts = [namespaced(g, p) for g, p in zip(graphs, prefixes)]
    combined = LogicalGraph(
        "+".join(g.name for g in graphs),
        ops=tuple(o for g in parts for o in g.ops),
        edges=tuple(e for g in parts for e in g.edges))
    plan = build_plan(combined, dt, queue_cap)

    # physical assembly: per-job expand (regions/channels depend only on
    # connectivity) + manual host lift through the job's host map. Task
    # numbering follows combined-graph op order, which equals per-job
    # expand order with the job's task offset added — the same contract
    # build_plan's offsets assume.
    tasks: list[Task] = []
    channels: dict = {}
    regions: list[set[int]] = []
    task_region: dict[int, int] = {}
    jobs: list[JobSlice] = []
    job_of_task = np.zeros(plan.n_tasks, dtype=int)
    topo_pos = {p.name: i for i, p in enumerate(plan.ops)}
    job_of_op = np.zeros(len(plan.ops), dtype=int)
    task_off = 0
    for j, (g, pre) in enumerate(zip(graphs, prefixes)):
        local = expand(g, n_hosts=n_hosts)
        for tk in local.tasks:
            tasks.append(Task(pre + tk.op, tk.index, task_off + tk.task_id,
                              host=int(maps[j][tk.host])))
        for (s, d), conn in local.channels.items():
            channels[(pre + s, pre + d)] = conn
        region_lo = len(regions)
        for r in local.regions:
            regions.append({task_off + t for t in r})
        for t, r in local.task_region.items():
            task_region[task_off + t] = region_lo + r
        n_local = len(local.tasks)
        job_of_task[task_off:task_off + n_local] = j
        op_cols = np.array(sorted(topo_pos[pre + o.name] for o in g.ops))
        job_of_op[op_cols] = j
        jobs.append(JobSlice(
            index=j,
            name=(names[j] if names is not None else pre + g.name),
            graph=g, prefix=pre, task_lo=task_off,
            task_hi=task_off + n_local, op_cols=op_cols,
            op_names=[plan.ops[c].name[len(pre):] for c in op_cols],
            src_cols=np.array([c for c in op_cols
                               if plan.ops[c].is_source]),
            hosts=maps[j], region_lo=region_lo, region_hi=len(regions),
            local_host=np.array([tk.host for tk in local.tasks])))
        task_off += n_local
    assert task_off == plan.n_tasks
    phys = PhysicalGraph(combined, tasks, channels, regions, task_region)
    return PackedArena(combined, plan, phys, jobs, job_of_task, job_of_op,
                       n_pool)


class StreamEngine:
    def __init__(self, graph: LogicalGraph | PackedArena, *,
                 n_hosts: int = 8,
                 dt: float = 0.5, queue_cap: float = 256.0,
                 chaos: ChaosEngine | None = None,
                 failover: FailoverConfig | None = None,
                 ckpt: CheckpointConfig | None = None,
                 upgrade: UpgradeConfig | None = None,
                 autoscale: AutoscaleConfig | None = None,
                 task_speed_override: dict[int, float] | None = None,
                 seed: int = 0):
        self.arena = graph if isinstance(graph, PackedArena) else None
        if self.arena is not None:
            # packed mega-arena: the lowering (plan + physical placement
            # over the shared host pool) was done by pack_arena; dt and
            # queue_cap come from the arena's plan.
            graph = self.arena.graph
            dt, queue_cap = self.arena.dt, self.arena.queue_cap
        self.g = graph
        self.phys: PhysicalGraph = (
            self.arena.phys if self.arena is not None
            else expand(graph, n_hosts=n_hosts, seed=seed))
        self.dt = dt
        self.queue_cap = queue_cap
        # per-job chaos: one ChaosEngine per co-located job, each drawing
        # in its job's LOCAL host domain and lifted through the job's
        # host map (see build_perjob_chaos_timeline for the contract)
        if isinstance(chaos, (list, tuple)):
            if self.arena is None or len(chaos) != self.arena.n_jobs:
                raise ValueError("a per-job chaos list needs a packed "
                                 "arena with one entry per job")
            self._chaos_list = [
                c if isinstance(c, ChaosEngine)
                else ChaosEngine(c)       # ChaosSpec or None
                for c in chaos]
            self.chaos = self._chaos_list[0]
            # a shared CheckpointConfig has no shared engine to draw
            # from under per-job chaos — lower it onto per-job
            # coordinators, one per job, each on its own stream
            if isinstance(ckpt, CheckpointConfig):
                ckpt = [ckpt] * self.arena.n_jobs
        else:
            self._chaos_list = None
            self.chaos = chaos or ChaosEngine()
        self.failover = (failover if failover is not None
                         else FailoverConfig())
        self.ckpt_cfg = ckpt
        self.rng = np.random.default_rng(seed)
        self.t = 0.0

        # ---- task arena + routing plan --------------------------------
        self.plan = (self.arena.plan if self.arena is not None
                     else build_plan(graph, dt, queue_cap))
        ops = {o.name: o for o in graph.ops}
        offs = self.plan.offs
        n_tasks = self.plan.n_tasks
        assert n_tasks == len(self.phys.tasks)

        self._queue = np.zeros(n_tasks)
        self._down_until = np.zeros(n_tasks)
        self._speed = np.ones(n_tasks)
        self._qcap = self.plan.qcap
        if task_speed_override:
            for tk in self.phys.tasks:
                if tk.task_id in task_speed_override:
                    self._speed[tk.task_id] = task_speed_override[tk.task_id]
        # chaos host stragglers (queried in task order — keeps the chaos rng
        # stream identical to the reference engine). Per-job chaos draws
        # in the job's LOCAL host domain, like an independent run.
        if self._chaos_list is not None:
            jobs_ = self.arena.jobs
            jot = self.arena.job_of_task
            for tk in self.phys.tasks:
                job = jobs_[int(jot[tk.task_id])]
                lh = int(job.local_host[tk.task_id - job.task_lo])
                self._speed[tk.task_id] *= \
                    self._chaos_list[job.index].host_speed(lh)
        else:
            for tk in self.phys.tasks:
                self._speed[tk.task_id] *= self.chaos.host_speed(tk.host)

        self._task_host = np.array([tk.host for tk in self.phys.tasks])
        self._task_region = np.array(
            [self.phys.task_region[tk.task_id] for tk in self.phys.tasks])
        # kill draws cover the whole shared pool for packed arenas (hosts
        # without tasks of SOME job may still host another job's tasks)
        self._n_hosts = (self.arena.n_hosts if self.arena is not None
                         else int(self._task_host.max()) + 1)
        if self.arena is not None:
            self._job_of_op = self.arena.job_of_op
            self._job_of_task = self.arena.job_of_task
        else:
            self._job_of_op = self._job_of_task = None

        # per-task failover vectors (uniform configs are constant vectors;
        # per-job FailoverConfig lists vary by job slice)
        codes, det, rst_s, rst_r, fx = per_task_failover(
            failover, n_tasks, self._job_of_task)
        self._mode_single = codes == 2
        self._mode_region = codes == 1
        self._mode_hot = codes == 3
        self._any_single = bool(self._mode_single.any())
        self._downtime_single = det + rst_s
        self._downtime_region = det + rst_r
        # hot-standby pays switch + staleness replay, never a restore
        self._downtime_hot = det + fx["switch"] + fx["stale"]
        # passive-restore surcharge inputs (zero by default → no-op):
        # extra = restore_base*brownout(t) + ckpt_age(t)*replay + lazy
        self._restore_base = fx["restore_base"]
        self._replay_rate = fx["replay_rate"]
        self._lazy_extra = lazy_ready_extra(
            fx["stagger"], self._task_region, self._job_of_task)
        self._has_extra = bool(self._restore_base.any()
                               or self._replay_rate.any()
                               or self._lazy_extra.any())

        # checkpoint coordinators: one shared (historical semantics, incl.
        # the cross-region short-circuit) or one per job (per-job configs)
        self._last_ckpt_t = 0.0          # shared coordinator
        self._last_ckpt_vec = None       # per-job coordinators
        if ckpt is None or isinstance(ckpt, CheckpointConfig):
            self._ckpt_list = None
            self._next_ckpt = (ckpt.interval_s if ckpt else math.inf)
        else:
            cfgs = list(ckpt)
            if self.arena is None or len(cfgs) != self.arena.n_jobs:
                raise ValueError("per-job ckpt list needs a packed arena "
                                 "with one entry per job")
            self._ckpt_list = cfgs
            self._next_ckpt = math.inf
            self._next_ckpt_j = np.array(
                [c.interval_s if c is not None else math.inf
                 for c in cfgs])
            self._last_ckpt_vec = np.zeros(self.arena.n_jobs)

        # compat: per-op dict views aliasing the arena (tests / tooling)
        self.par = {n: ops[n].parallelism for n in ops}
        self.qcap = {n: float(self._qcap[offs[n]]) for n in ops}
        self.queue = {n: self._queue[offs[n]:offs[n] + self.par[n]]
                      for n in ops}
        self.down_until = {n: self._down_until[offs[n]:offs[n] + self.par[n]]
                           for n in ops}
        self.speed = {n: self._speed[offs[n]:offs[n] + self.par[n]]
                      for n in ops}

        # ---- op + edge plans (speed-dependent fast-path rows) ----------
        self._ops = self.plan.ops
        for p in self._ops:
            if not p.is_source:
                p.cap_row = p.service_rate * dt * self._speed[p.lo:p.hi].copy()
        self._src_ops = [p for p in self._ops if p.is_source]
        self._arena_starts = self.plan.arena_starts
        self._backlog_perm = self.plan.backlog_perm
        self._src_cols = self.plan.src_cols

        # per-tick reusable arena-sized scratch
        self._alive_buf = np.empty(n_tasks, bool)
        self._alive_f_buf = np.empty(n_tasks)
        self._free_buf = np.empty(n_tasks)
        self._qps_buf = np.zeros(len(self._ops))
        self._true_buf = np.ones(n_tasks, bool)
        self._ones_buf = np.ones(n_tasks)
        self._max_down = 0.0          # latest down_until across the arena
        if self._chaos_list is not None:
            self._chaos_kills_possible = any(
                bool(e.spec.host_kill_at or e.spec.host_kill_prob_per_s
                     or e.spec.burst_at)
                for e in self._chaos_list)
            self._gates_possible = any(
                bool(e.spec.mq_down)
                or (bool(e.spec.zk_down) and bool(e.spec.hdfs_down))
                for e in self._chaos_list)
            self._traffic_possible = any(
                bool(e.spec.diurnal or e.spec.flash_at)
                for e in self._chaos_list)
            # region-correlated bursts: lower each job's burst events
            # into scheduled host kills in the job's LOCAL host domain
            for job, eng in zip(self.arena.jobs, self._chaos_list):
                if eng.spec.burst_at:
                    sl = slice(job.task_lo, job.task_hi)
                    eng.schedule_kills(burst_kill_schedule(
                        eng.spec.burst_at, job.local_host,
                        self._task_region[sl]))
        else:
            spec = self.chaos.spec
            self._chaos_kills_possible = bool(
                spec.host_kill_at or spec.host_kill_prob_per_s
                or spec.burst_at)
            self._gates_possible = bool(spec.mq_down) or (
                bool(spec.zk_down) and bool(spec.hdfs_down))
            self._traffic_possible = bool(spec.diurnal or spec.flash_at)
            if spec.burst_at:
                self.chaos.schedule_kills(burst_kill_schedule(
                    spec.burst_at, self._task_host, self._task_region))

        # ---- deployment drill (canaried rolling upgrade) ---------------
        # lowered ONCE into traced per-task leaves; everything below is
        # deterministic time arithmetic — no rng draws, no timeline work
        self.upgrade = upgrade
        if upgrade is not None:
            sel_task = np.zeros(n_tasks)
            for p in self._ops:
                if not p.is_source:
                    sel_task[p.lo:p.hi] = p.selectivity
            dr = lower_upgrade(
                upgrade,
                (self._chaos_list if self._chaos_list is not None
                 else self.chaos.spec),
                n_tasks=n_tasks, job_of_task=self._job_of_task,
                task_region=self._task_region, dt=dt,
                base_failover=(codes, det, rst_s, rst_r, fx),
                base_ckpt=ckpt, sel_task=sel_task)
            self._dr = dr
            self._mode_single_f = self._mode_single.astype(float)
            self._mode_region_f = self._mode_region.astype(float)
            self._mode_hot_f = self._mode_hot.astype(float)
            self._any_single_eff = (self._any_single
                                    or bool((dr["d_mode_s"] > 0).any()))
            self._has_extra_eff = (self._has_extra
                                   or bool(dr["d_restore"].any()
                                           or dr["d_replay"].any()
                                           or dr["d_ck"].any()))
        else:
            self._dr = None
        self._up_until = np.zeros(n_tasks)   # graceful waves: ≠ down_until
        self._rb_t = math.inf                # rollback fire time
        self._dacc = 0.0                     # controller EWMA accumulator
        self._act = np.zeros(n_tasks)        # canary-config activation

        # ---- in-trace DS2 autoscaler (lowered controller leaves) -------
        # mirrors jax_engine's `_finish_tick` controller EXACTLY (same
        # step order, same `where`-gated updates) — the parity contract
        self.autoscale = autoscale
        if autoscale is not None:
            is_src = np.zeros(n_tasks)
            for p in self._ops:
                if p.is_source:
                    is_src[p.lo:p.hi] = 1.0
            self._as = lower_autoscale(autoscale, n_tasks=n_tasks, dt=dt,
                                       is_src_task=is_src)
        else:
            self._as = None
        # capacity base (service_rate·dt on non-source tasks, 0 on
        # sources) — recomputed·speed per tick when the autoscaler
        # mutates speeds (cap_row above is baked with the INITIAL speed)
        self._cap_base = np.zeros(n_tasks)
        for p in self._ops:
            if not p.is_source:
                self._cap_base[p.lo:p.hi] = p.service_rate * dt
        self._rew = np.zeros(n_tasks)        # EWMA'd utilization (need)
        self._lact = np.full(n_tasks, -1e18)  # last rescale time
        self._dirp = np.zeros(n_tasks)       # last rescale direction
        self._failcnt = np.zeros(n_tasks)    # breaker failure counter
        self._brk_until = np.zeros(n_tasks)  # breaker-open-until
        self._used = 0.0                     # leaky action-rate bucket
        self._flip_acc = 0.0                 # leaky direction-flip count
        self._thrash_t = math.inf            # thrash-latch fire time
        self._take_buf = np.zeros(n_tasks)   # records consumed this tick
        self._hit_buf = np.zeros(n_tasks)    # failover-hit this tick

        self.metrics = EngineMetrics(
            [p.name for p in self._ops],
            n_jobs=(self.arena.n_jobs if self.arena is not None else None))
    # ------------------------------------------------------------------
    def _alive(self, op: str) -> np.ndarray:
        return self.down_until[op] <= self.t

    # -- per-edge vectorized routing -----------------------------------
    def _route(self, ep: _EdgePlan, produced: np.ndarray,
               free_down: np.ndarray, alive_d: np.ndarray) -> np.ndarray:
        """arriving (n_dst,) — the collapsed `produced @ W` of the
        reference's row-stochastic weights."""
        kind = ep.kind
        if kind == "forward":
            return produced * alive_d
        if kind in ("rescale", "group_rescale"):
            prod_blk = np.bincount(ep.blk_of_src, weights=produced,
                                   minlength=ep.n_blocks)
            alive_blk = np.bincount(ep.blk_idx[ep.dst_in_blk],
                                    weights=alive_d[ep.dst_in_blk],
                                    minlength=ep.n_blocks)
            prod_blk[alive_blk <= 0] = 0.0
            rate_blk = np.divide(prod_blk, alive_blk, out=prod_blk,
                                 where=alive_blk > 0)
            arriving = rate_blk[ep.blk_idx]
            arriving *= alive_d
            if ep.any_unblocked:
                arriving[~ep.dst_in_blk] = 0.0
            return arriving
        # all-to-all family: identical weight rows → scale a single row
        total = produced.sum()
        if kind == "rebalance":
            val = alive_d
        elif kind == "hash":
            # strict keyBy ignores dst liveness/congestion (γ=partial trade)
            return total * ep.share
        elif kind == "weakhash":
            cap = np.maximum(free_down, 1e-9, out=ep.ratio_buf)
            cap *= alive_d
            capsum = np.add.reduceat(cap, ep.grp_starts)
            # groups with zero capacity fall back to alive-uniform spread
            # (only reachable when a whole group is down — cheap to branch)
            if not capsum.all():
                fall = capsum <= 0
                cap = np.where(fall[ep.grp_of_dst], alive_d + 1e-9, cap)
                capsum = np.where(fall, np.add.reduceat(alive_d + 1e-9,
                                                        ep.grp_starts),
                                  capsum)
                cap *= alive_d   # dead dsts stay weightless (alive² = alive)
            val = cap
            val *= ep.mass_of_dst
            val /= capsum[ep.grp_of_dst]
        elif kind == "backlog":
            open_ = np.greater(free_down, ep.dst_qcap * 0.25,
                               out=ep.live_buf)
            val = np.maximum(free_down, 1e-9, out=ep.ratio_buf)
            val *= alive_d
            val *= np.maximum(open_, 0.05)
        else:
            raise ValueError(kind)
        rs = val.sum()
        return val * (total / rs) if rs > 0 else np.zeros_like(val)

    def _accept(self, ep: _EdgePlan, arriving: np.ndarray,
                room: np.ndarray) -> np.ndarray:
        if ep.static:
            # head-of-line blocking: the most congested live channel
            # throttles the whole exchange (credit-based flow control)
            live = np.greater(arriving, 1e-9, out=ep.live_buf)
            ratio = ep.ratio_buf
            ratio.fill(np.inf)
            np.divide(room, arriving, out=ratio, where=live)
            lam = float(ratio.min())
            if lam >= 1.0:   # includes the no-live-channel case (all inf)
                return arriving
            return arriving * lam
        if ep.kind == "group_rescale":
            # blocking confined to each group (Fig 2c)
            live = np.greater(arriving, 1e-9, out=ep.live_buf)
            ratio = ep.ratio_buf
            ratio.fill(np.inf)
            np.divide(room, arriving, out=ratio, where=live)
            lam_g = np.minimum(np.minimum.reduceat(ratio, ep.grp_starts), 1.0)
            return arriving * lam_g[ep.grp_of_dst]
        # adaptive routing (backlog/weakhash): channels accept up to their
        # credits; remainder re-queues at the source for re-routing
        return np.minimum(arriving, room)

    # ------------------------------------------------------------------
    def tick(self) -> None:
        dt = self.dt
        t = self.t
        q = self._queue
        dr = self._dr
        a = self._as
        if a is not None:
            self._take_buf.fill(0.0)
            self._hit_buf.fill(0.0)
            # breaker-open load shed only multiplies selectivities when
            # some breaker IS open (×1.0 otherwise — exact no-op)
            self._brk_any = bool((self._brk_until > t).any())
        all_alive = t >= self._max_down
        if all_alive:
            alive_all = self._true_buf
            alive_f = self._ones_buf
        else:
            alive_all = np.less_equal(self._down_until, t,
                                      out=self._alive_buf)
            if dr is not None or a is not None:
                # upgrade/rollback waves (and autoscaler rescales) down
                # tasks gracefully (queues persist) on a separate leaf
                # so checkpoint alive masks — and thus the shared rng
                # draw stream — never see them
                np.logical_and(alive_all, self._up_until <= t,
                               out=alive_all)
            np.copyto(self._alive_f_buf, alive_all)   # bool → float cast
            alive_f = self._alive_f_buf
            all_alive = bool(alive_all.all())
        if dr is not None:
            # canary-config activation: 1.0 once a task's upgrade wave
            # completed and its rollback wave (if any) has not yet begun
            np.multiply(
                dr["up_cmask"],
                (t >= dr["up_start"] + dr["up_down"])
                & (t < self._rb_t + dr["up_rstag"]),
                out=self._act)
        act = self._act
        free = np.subtract(self._qcap, q, out=self._free_buf)
        np.maximum(free, 0.0, out=free)
        qps_row = self._qps_buf
        qps_row.fill(0.0)
        drop_tick = 0.0
        any_single = self._any_single if dr is None else self._any_single_eff
        emitted = 0.0

        # MQ/coordinator outage windows gate sources (deterministic, no
        # rng): a down message queue — or a leaderless control plane
        # (ZK quorum AND HDFS metadata both out, paper §IV-B) — means
        # sources emit nothing this tick
        if self._gates_possible:
            if self._chaos_list is not None:
                gate_by_job = np.array(
                    [1.0 if (e.mq_available(t) and e.leader_available(t))
                     else 0.0
                     for e in self._chaos_list])
                gate0 = 1.0
            else:
                gate_by_job = None
                gate0 = (1.0 if (self.chaos.mq_available(t)
                                 and self.chaos.leader_available(t))
                         else 0.0)
        else:
            gate_by_job = None
            gate0 = 1.0

        # traffic dynamics (diurnal curves + flash-crowd ramps) scale
        # source emission — deterministic closed-form curves, NO rng
        if self._traffic_possible:
            if self._chaos_list is not None:
                tf_by_job = np.array(
                    [e.traffic_factor(t) for e in self._chaos_list])
                tf0 = 1.0
            else:
                tf_by_job = None
                tf0 = self.chaos.traffic_factor(t)
        else:
            tf_by_job = None
            tf0 = 1.0

        jobs = self._job_of_op          # per-job segments (packed arenas)
        for oi, op in enumerate(self._ops):
            sl = slice(op.lo, op.hi)
            if op.is_source:
                if all_alive:
                    produced = op.src_row
                    e_op = op.src_sum
                else:
                    produced = op.src_row * alive_f[sl]
                    e_op = produced.sum()
                gate = (gate0 if gate_by_job is None
                        else float(gate_by_job[jobs[oi]]))
                if gate != 1.0:
                    produced = produced * gate
                    e_op = e_op * gate
                tf = (tf0 if tf_by_job is None
                      else float(tf_by_job[jobs[oi]]))
                if tf != 1.0:
                    produced = produced * tf
                    e_op = e_op * tf
                emitted += e_op
                if jobs is not None:
                    self.metrics._emitted_by_job[jobs[oi]] += e_op
            else:
                if a is None:
                    cap = (op.cap_row if all_alive
                           else op.cap_row * alive_f[sl])
                else:
                    # cap_row bakes the INITIAL speed — recompute once
                    # the autoscaler may have rescaled this op's tasks
                    cap = self._cap_base[sl] * self._speed[sl]
                    if not all_alive:
                        cap = cap * alive_f[sl]
                take = np.minimum(q[sl], cap)
                q[sl] -= take
                if dr is None:
                    sel_eff = op.selectivity
                else:
                    # canary slices run their own selectivity vector
                    sel_eff = op.selectivity + act[sl] * dr["d_sel"][sl]
                if a is not None:
                    self._take_buf[sl] = take
                    if self._brk_any:
                        # breaker-open graceful degradation: load-shed
                        # by scaling selectivity (same multiply grouping
                        # as jax's `sel_t * shed_t` — parity contract)
                        sel_eff = sel_eff * np.where(
                            t < self._brk_until[sl], a["as_shed"], 1.0)
                produced = take * sel_eff
                qps_row[oi] = take.sum() / dt

            for ep in op.out_edges:
                dsl = slice(ep.dst.lo, ep.dst.hi)
                arriving = self._route(ep, produced, free[dsl], alive_f[dsl])
                if any_single and not all_alive:
                    # records routed to a dead single_task-mode task drop
                    # (γ=partial); per-job configs scope the mode per dst;
                    # canary slices may flip the mode mask mid-run
                    if dr is None:
                        dead = ~alive_all[dsl] & self._mode_single[dsl]
                    else:
                        ms_eff = (self._mode_single_f[dsl]
                                  + act[dsl] * dr["d_mode_s"][dsl]) > 0.5
                        dead = ~alive_all[dsl] & ms_eff
                    if dead.any():
                        d_edge = arriving[dead].sum()
                        drop_tick += d_edge
                        if jobs is not None:   # edges never cross jobs
                            self.metrics._dropped_by_job[jobs[oi]] += d_edge
                        arriving = np.where(dead, 0.0, arriving)
                accepted = self._accept(ep, arriving, free[dsl])
                if accepted is not arriving:
                    overflow = (arriving - accepted).sum()
                    if overflow != 0.0:
                        q[sl] += overflow / max(op.par, 1)
                q[dsl] += accepted
                free_d = free[dsl]
                free_d -= accepted
                np.maximum(free_d, 0.0, out=free_d)

        # chaos host kills → failover (skip entirely when the chaos spec
        # cannot produce kills — step_kills would draw nothing and return [])
        if self._chaos_kills_possible:
            if self._chaos_list is not None:
                # per-job kill processes: jobs draw in ascending job
                # order over their LOCAL host domains, lifted through
                # the job's host map; a pool host killed by several
                # jobs' processes this tick resolves once
                failed_pool: set[int] = set()
                for job in self.arena.jobs:
                    eng = self._chaos_list[job.index]
                    spec = eng.spec
                    if not (spec.host_kill_at or spec.host_kill_prob_per_s
                            or spec.burst_at):
                        continue
                    m = job.hosts
                    for lh in eng.step_kills(t, t + dt, n_hosts=len(m)):
                        if lh < len(m):
                            pool = int(m[lh])
                            if pool not in failed_pool:
                                failed_pool.add(pool)
                                self._fail_host(pool, revive=False)
                        eng.revive(lh)
            else:
                kills = self.chaos.step_kills(t, t + dt,
                                              n_hosts=self._n_hosts)
                for host in kills:
                    self._fail_host(host)

        # checkpoint coordinator(s): one shared, or one per job
        if t + dt >= self._next_ckpt:
            self._run_checkpoint()
            self._next_ckpt += self.ckpt_cfg.interval_s
        elif self._ckpt_list is not None:
            for j in np.nonzero(t + dt >= self._next_ckpt_j)[0]:
                self._run_checkpoint_job(int(j))
                self._next_ckpt_j[j] += self._ckpt_list[j].interval_s

        # drill controller + wave scheduler (end-of-tick, mirrors the
        # traced order in jax_engine._finish_tick exactly): the EWMA of
        # the canary-vs-stable mean-queue delta updates first, then the
        # rollback decision reads the UPDATED accumulator, then the
        # wave triggers read the UPDATED rollback time
        if dr is not None:
            delta = float(q @ dr["up_wdelta"])
            if t >= dr["up_t0"]:
                self._dacc += dr["up_alpha"] * (delta - self._dacc)
                if self._dacc > dr["up_thresh"] and math.isinf(self._rb_t):
                    self._rb_t = t + dt
                    self.metrics.rollback_t = self._rb_t
            trig = (t <= dr["up_start"]) & (dr["up_start"] < t + dt)
            if trig.any():
                self._up_until[trig] = np.maximum(
                    self._up_until[trig], dr["up_start"][trig]
                    + dr["up_down"])
                self._max_down = max(self._max_down,
                                     float(self._up_until.max()))
            rb_start = self._rb_t + dr["up_rstag"]
            trig = (t <= rb_start) & (rb_start < t + dt)
            if trig.any():
                self._up_until[trig] = np.maximum(
                    self._up_until[trig], rb_start[trig] + dr["up_down"])
                self._max_down = max(self._max_down,
                                     float(self._up_until.max()))

        # autoscale controller (end-of-tick, AFTER kills/ckpt/drill —
        # mirrors jax_engine._finish_tick's traced order exactly): the
        # utilization EWMA updates first, the breaker update reads this
        # tick's failover hits, then the decision reads the UPDATED
        # accumulator and UPDATED breaker state
        if a is not None:
            cap_now = self._cap_base * self._speed
            need = ((self._take_buf + q * (dt / a["as_drain"]))
                    / np.maximum(cap_now, 1e-9))
            self._rew += a["as_alpha"] * (need - self._rew)
            hit = self._hit_buf
            recent = (t - self._lact) <= a["as_fw"]
            failev = (hit > 0.0) & recent
            crossed = (((t - self._lact) > a["as_fw"])
                       & ((t - dt - self._lact) <= a["as_fw"]))
            failcnt = np.where(
                failev, self._failcnt + 1.0,
                np.where(crossed & (hit <= 0.0), 0.0, self._failcnt))
            brk_fire = failcnt >= a["as_bfail"]
            self._brk_until = np.where(brk_fire, t + a["as_brs"],
                                       self._brk_until)
            self._failcnt = np.where(brk_fire, 0.0, failcnt)
            boundary = (math.floor((t + dt - a["as_t0"]) / a["as_int"])
                        > math.floor((t - a["as_t0"]) / a["as_int"]))
            want = np.clip(self._speed * self._rew / a["as_tgt"],
                           a["as_lo"], a["as_hi"])
            rel = (np.abs(want - self._speed)
                   / np.maximum(self._speed, 1e-9))
            fire = (boundary & (a["as_on"] > 0.0) & (a["as_mask"] > 0.0)
                    & (rel >= a["as_hyst"])
                    & ((t - self._lact) >= a["as_cool"])
                    & (t >= self._brk_until)
                    & (self._used < a["as_amax"])
                    & math.isinf(self._thrash_t))
            new_speed = np.where(fire, want, self._speed)
            self._lact = np.where(fire, t, self._lact)
            dirn = np.sign(want - self._speed)
            if fire.any():
                # graceful rescale: queues persist, the task pays
                # deploy downtime + state-move seconds on `up_until`
                downt = (a["as_down"]
                         + a["as_move"] * np.abs(want - self._speed))
                np.maximum(self._up_until, np.where(fire, t + downt, 0.0),
                           out=self._up_until)
                self._max_down = max(self._max_down,
                                     float(self._up_until.max()))
                any_fire = 1.0
            else:
                any_fire = 0.0
            self._used = self._used * a["as_adec"] + any_fire
            flip = fire & (dirn * self._dirp < 0.0)
            self._dirp = np.where(fire, dirn, self._dirp)
            self._flip_acc = (self._flip_acc * a["as_tdec"]
                              + float(flip.sum()))
            if (self._flip_acc >= a["as_tflip"]
                    and math.isinf(self._thrash_t)):
                # thrash latch: freeze the controller for the rest of
                # the run (fire above reads the PRE-latch thrash_t)
                self._thrash_t = t + dt
                self.metrics.thrash_t = self._thrash_t
            np.copyto(self._speed, new_speed)  # keep dict views aliased
            self.metrics.n_rescale += float(fire.sum())
        self.metrics.resource_s += float(self._speed.sum()) * dt

        backlog_row = np.add.reduceat(q, self._arena_starts)[
            self._backlog_perm]
        lag = float(backlog_row[self._src_cols].sum())
        self.metrics._record(t, qps_row, backlog_row, lag)
        self.metrics.emitted += emitted
        self.metrics.dropped += drop_tick
        self.t = t + dt

    def run(self, duration_s: float) -> EngineMetrics:
        n = int(round(duration_s / self.dt))
        self.metrics._reserve(n)
        for _ in range(n):
            self.tick()
        return self.metrics

    # ------------------------------------------------------------------
    def _fail_host(self, host: int, revive: bool = True) -> None:
        """Failover response to one host kill: region-mode victims expand
        to their failure regions, single_task-mode victims restart alone
        (region entries precede single_task entries when a shared-host
        kill hits jobs of both modes — the order the chaos timeline
        replays)."""
        t = self.t
        victims = self._task_host == host
        dr = self._dr
        act = self._act
        # passive-restore surcharge: brownout-inflated restore bandwidth
        # + replay of work since the last successful checkpoint + lazy-
        # load region ready-time (zero vectors → identical old downtimes).
        # Active canary slices pay it under their own config: restore /
        # replay deltas plus the canary-vs-base ckpt-interval ratio
        # scaling the replay-age term (same float arithmetic as the jax
        # engines' _finish_tick — the parity contract).
        has_extra = self._has_extra if dr is None else self._has_extra_eff
        if has_extra:
            if self._chaos_list is not None:
                bfj = np.array([e.brownout_factor(t)
                                for e in self._chaos_list])
                bf_t = bfj[self._job_of_task]
            else:
                bf_t = self.chaos.brownout_factor(t)
            age = t - (self._last_ckpt_vec[self._job_of_task]
                       if self._last_ckpt_vec is not None
                       else self._last_ckpt_t)
            if dr is None:
                extra = (self._restore_base * bf_t
                         + age * self._replay_rate + self._lazy_extra)
            else:
                extra = ((self._restore_base + act * dr["d_restore"])
                         * bf_t
                         + age * (1.0 + act * dr["d_ck"])
                         * (self._replay_rate + act * dr["d_replay"])
                         + self._lazy_extra)
        else:
            extra = None
        if dr is None:
            mr, ms, mh = (self._mode_region, self._mode_single,
                          self._mode_hot)
            dt_r, dt_s, dt_h = (self._downtime_region,
                                self._downtime_single, self._downtime_hot)
        else:
            mr = (self._mode_region_f + act * dr["d_mode_r"]) > 0.5
            ms = (self._mode_single_f + act * dr["d_mode_s"]) > 0.5
            mh = (self._mode_hot_f + act * dr["d_mode_h"]) > 0.5
            dt_r = self._downtime_region + act * dr["d_down_r"]
            dt_s = self._downtime_single + act * dr["d_down_s"]
            dt_h = self._downtime_hot + act * dr["d_down_h"]
        vr = victims & mr
        if vr.any():
            hit = np.isin(self._task_region, self._task_region[vr])
            d = dt_r if extra is None else dt_r + extra
            self._apply_failover(t, "region", hit, d)
        vs = victims & ms
        if vs.any():
            d = dt_s if extra is None else dt_s + extra
            self._apply_failover(t, "single_task", vs, d)
        # hot standby: switch + staleness replay only — no restore, no
        # checkpoint-age replay, no drops (the standby keeps consuming)
        vh = victims & mh
        if vh.any():
            self._apply_failover(t, "hot_standby", vh, dt_h)
        if revive:
            self.chaos.revive(host)  # replacement host

    def _apply_failover(self, t, mode, hit, downtime) -> None:
        until = t + downtime[hit]
        self._max_down = max(self._max_down, float(until.max()))
        self._down_until[hit] = until
        self._queue[hit] = 0.0   # incomplete output / state discarded
        if self._as is not None:
            # autoscaler breaker input: tasks failover-hit this tick
            self._hit_buf[hit] = 1.0
        # packed arenas attribute the event per co-located job hit
        self.metrics.recoveries.extend(failover_recovery_entries(
            t, mode, hit, downtime, self._job_of_task))

    # ------------------------------------------------------------------
    def _run_checkpoint(self) -> None:
        """Whole-arena coordinator — the rng consumption (vectorized
        per-task upload draws, stream-identical to per-task scalar draws
        in task-id order, plus region retries) is the shared
        `core.chaos.run_checkpoint_attempt`, so the pregenerated timeline
        replays it draw-for-draw."""
        cfg = self.ckpt_cfg
        m = self.metrics
        m.ckpt_attempts += 1
        ok = run_checkpoint_attempt(
            self.chaos, self._down_until <= self.t,
            interval_s=cfg.interval_s, mode=cfg.mode,
            upload_s=cfg.upload_s, retry=cfg.retry_failed_region,
            regions=self.phys.regions, t=self.t)
        if ok:
            self._last_ckpt_t = self.t
        m.ckpt_success += int(ok)
        m.ckpt_failed += int(not ok)

    def _run_checkpoint_job(self, j: int) -> None:
        """Per-job coordinator (per-job `CheckpointConfig`s): draws upload
        factors for job j's task slice only and evaluates only its own
        regions, so co-located jobs checkpoint on independent schedules
        and a failing job never short-circuits another job's attempt.
        Shares `core.chaos.run_checkpoint_attempt` with the timeline
        replay (`core.chaos._JobCkpt`), keeping the two draw-for-draw."""
        cfg = self._ckpt_list[j]
        job = self.arena.jobs[j]
        m = self.metrics
        m.ckpt_attempts += 1
        m.ckpt_by_job[j, 0] += 1
        lo = job.task_lo
        eng = (self._chaos_list[j] if self._chaos_list is not None
               else self.chaos)
        ok = run_checkpoint_attempt(
            eng, self._down_until[lo:job.task_hi] <= self.t,
            interval_s=cfg.interval_s, mode=cfg.mode,
            upload_s=cfg.upload_s, retry=cfg.retry_failed_region,
            regions=self.phys.regions[job.region_lo:job.region_hi],
            task_lo=lo, t=self.t)
        if ok:
            self._last_ckpt_vec[j] = self.t
        m.ckpt_success += int(ok)
        m.ckpt_failed += int(not ok)
        m.ckpt_by_job[j, 1 if ok else 2] += 1
