"""Reference (pre-vectorization) stream engine — the per-edge interpreter.

This is the seed implementation of `streams.engine.StreamEngine`, kept
verbatim as the semantic oracle: `tests/test_engine_vectorized.py` pins the
vectorized engine's metrics against it. Do not optimize this file — its whole
point is to stay the slow, obviously-correct baseline.
"""
from __future__ import annotations

import dataclasses
import math
from collections import defaultdict

import numpy as np

from repro.core.chaos import ChaosEngine, burst_kill_schedule
from repro.streams.engine import CheckpointConfig, FailoverConfig
from repro.streams.graph import LogicalGraph, PhysicalGraph, expand


@dataclasses.dataclass
class ReferenceEngineMetrics:
    t: list = dataclasses.field(default_factory=list)
    qps: dict = dataclasses.field(default_factory=lambda: defaultdict(list))
    backlog: dict = dataclasses.field(default_factory=lambda: defaultdict(list))
    source_lag: list = dataclasses.field(default_factory=list)
    dropped: float = 0.0
    emitted: float = 0.0
    ckpt_attempts: int = 0
    ckpt_success: int = 0
    ckpt_failed: int = 0
    recoveries: list = dataclasses.field(default_factory=list)


class ReferenceStreamEngine:
    def __init__(self, graph: LogicalGraph, *, n_hosts: int = 8,
                 dt: float = 0.5, queue_cap: float = 256.0,
                 chaos: ChaosEngine | None = None,
                 failover: FailoverConfig | None = None,
                 ckpt: CheckpointConfig | None = None,
                 task_speed_override: dict[int, float] | None = None,
                 seed: int = 0):
        self.g = graph
        self.phys: PhysicalGraph = expand(graph, n_hosts=n_hosts, seed=seed)
        self.dt = dt
        self.queue_cap = queue_cap
        self.chaos = chaos or ChaosEngine()
        self.failover = failover or FailoverConfig()
        self.ckpt_cfg = ckpt
        self.rng = np.random.default_rng(seed)
        self.metrics = ReferenceEngineMetrics()
        self.t = 0.0
        self._next_ckpt = (self.ckpt_cfg.interval_s if ckpt else math.inf)

        ops = {o.name: o for o in graph.ops}
        self.par = {n: ops[n].parallelism for n in ops}
        # credit budget per task: a few ticks of service capacity (bounded
        # buffers = credit-based flow control)
        self.qcap = {n: max(ops[n].service_rate * dt * 4.0, queue_cap)
                     for n in ops}
        # per-op per-task state
        self.queue = {n: np.zeros(self.par[n]) for n in ops}
        self.down_until = {n: np.zeros(self.par[n]) for n in ops}
        self.speed = {n: np.ones(self.par[n]) for n in ops}
        if task_speed_override:
            for t in self.phys.tasks:
                if t.task_id in task_speed_override:
                    self.speed[t.op][t.index] = task_speed_override[t.task_id]
        # chaos host stragglers
        for t in self.phys.tasks:
            self.speed[t.op][t.index] *= self.chaos.host_speed(t.host)
        # external-system events: region-correlated failure bursts are
        # deterministic scheduled kills (no rng), lazy-load restore
        # staggers a region's ready time by its rank
        task_host = np.array([t.host for t in self.phys.tasks])
        task_region = np.array(
            [self.phys.task_region[t.task_id] for t in self.phys.tasks])
        if self.chaos.spec.burst_at:
            self.chaos.schedule_kills(burst_kill_schedule(
                self.chaos.spec.burst_at, task_host, task_region))
        first = int(task_region.min()) if len(task_region) else 0
        self._lazy = ((task_region - first).astype(float)
                      * self.failover.lazyload_stagger_s)
        self._last_ckpt_t = 0.0
        # hashed key-mass shares per keyed edge (Zipf skew)
        self._key_share: dict[tuple[str, str], np.ndarray] = {}
        for e in graph.edges:
            if e.partitioner in ("hash", "weakhash") or e.key_skew_zipf:
                nd = self.par[e.dst]
                nkeys = max(nd * 64, 1024)
                if e.key_skew_zipf > 0:
                    mass = 1.0 / np.arange(1, nkeys + 1) ** e.key_skew_zipf
                else:
                    mass = np.ones(nkeys)
                mass /= mass.sum()
                owner = (np.arange(nkeys) * 2654435761 % nd).astype(int)
                share = np.bincount(owner, weights=mass, minlength=nd)
                self._key_share[(e.src, e.dst)] = share

    # ------------------------------------------------------------------
    def _alive(self, op: str) -> np.ndarray:
        return self.down_until[op] <= self.t

    def _edge_weights(self, e, free_down: np.ndarray) -> np.ndarray:
        """Row-stochastic (n_src, n_dst) routing weights for this tick."""
        conn = self.phys.channels[(e.src, e.dst)].astype(float)
        ns, nd = conn.shape
        alive_d = self._alive(e.dst).astype(float)
        base = conn * alive_d[None, :]

        if e.partitioner in ("rebalance", "rescale", "group_rescale",
                             "forward"):
            w = base
        elif e.partitioner == "hash":
            # strict keyBy: key→task binding cannot divert around dead or
            # congested tasks (records to a dead task are lost under
            # single-task recovery — the γ=partial trade)
            share = self._key_share[(e.src, e.dst)]
            w = conn * share[None, :]
        elif e.partitioner == "weakhash":
            # key mass per group redistributes within the group ∝ free space
            share = self._key_share[(e.src, e.dst)]
            g = e.n_groups
            w = np.zeros_like(base)
            for grp in range(g):
                lo, hi = grp * nd // g, (grp + 1) * nd // g
                mass = share[lo:hi].sum()
                cap = np.maximum(free_down[lo:hi], 1e-9) * alive_d[lo:hi]
                if cap.sum() <= 0:
                    cap = alive_d[lo:hi] + 1e-9
                w[:, lo:hi] = base[:, lo:hi] * (mass * cap / cap.sum())[None, :]
        elif e.partitioner == "backlog":
            cap = self.qcap[e.dst]
            open_ = (free_down > cap * 0.25).astype(float)
            w = base * np.maximum(free_down, 1e-9)[None, :] * \
                np.maximum(open_, 0.05)[None, :]
        else:
            raise ValueError(e.partitioner)
        rs = w.sum(axis=1, keepdims=True)
        return np.divide(w, rs, out=np.zeros_like(w), where=rs > 0)

    # ------------------------------------------------------------------
    def tick(self) -> None:
        dt = self.dt
        g = self.g
        order = g.topo_order()
        free = {n: np.maximum(self.qcap[n] - self.queue[n], 0.0)
                for n in order}
        qps_tick = {n: 0.0 for n in order}
        drop_tick = 0.0

        # MQ/coordinator outage gate: sources emit nothing while the
        # message queue is down (multiplying by 1.0 is exact, so the
        # no-outage path keeps the historical numbers bit-for-bit)
        gate = 1.0 if self.chaos.mq_available(self.t) else 0.0
        # traffic dynamics: deterministic diurnal/flash-crowd source-rate
        # multiplier (empty schedules → exactly 1.0, so the multiply is
        # skipped and historical numbers stay bit-for-bit)
        tf = self.chaos.traffic_factor(self.t)

        for name in order:
            op = g.op(name)
            alive = self._alive(name)
            if op.is_source:
                produced = np.full(self.par[name],
                                   op.source_rate * dt / self.par[name])
                produced *= alive
                if gate != 1.0:
                    produced = produced * gate
                if tf != 1.0:
                    produced = produced * tf
                self.metrics.emitted += produced.sum()
            else:
                cap = op.service_rate * dt * self.speed[name] * alive
                take = np.minimum(self.queue[name], cap)
                self.queue[name] -= take
                produced = take * op.selectivity
                qps_tick[name] = take.sum() / dt

            outs = g.downstream(name)
            if not outs:
                continue
            for e in outs:
                w = self._edge_weights(e, free[e.dst])
                arriving = produced @ w                  # (n_dst,)
                dead = ~self._alive(e.dst)
                # single-task recovery: records keyed/routed to a dead task
                # are dropped (γ=partial) — they cannot stall the pipeline
                if dead.any() and self.failover.mode == "single_task":
                    drop_tick += arriving[dead].sum()
                    arriving = np.where(dead, 0.0, arriving)
                room = free[e.dst]
                if e.partitioner in ("rebalance", "rescale", "forward",
                                     "hash"):
                    # static routing = head-of-line blocking: the most
                    # congested live channel throttles the whole exchange
                    # (credit-based flow control, paper §III-A)
                    live = arriving > 1e-9
                    lam = float(np.min(room[live] / arriving[live])) \
                        if live.any() else 1.0
                    lam = min(1.0, lam)
                    accepted = arriving * lam
                elif e.partitioner == "group_rescale":
                    # blocking confined to each group (Fig 2c): a straggler
                    # stalls its group only
                    nd = len(arriving)
                    gcount = max(e.n_groups, 1)
                    accepted = np.zeros_like(arriving)
                    for grp in range(gcount):
                        lo, hi = grp * nd // gcount, (grp + 1) * nd // gcount
                        a, r = arriving[lo:hi], room[lo:hi]
                        live = a > 1e-9
                        lam = float(np.min(r[live] / a[live])) \
                            if live.any() else 1.0
                        accepted[lo:hi] = a * min(1.0, lam)
                else:
                    # adaptive routing (backlog/weakhash): channels accept up
                    # to their credits; remainder re-queues for re-routing
                    accepted = np.minimum(arriving, room)
                overflow = (arriving - accepted).sum()
                self.queue[name] += overflow / max(self.par[name], 1)
                self.queue[e.dst] += accepted
                free[e.dst] = np.maximum(free[e.dst] - accepted, 0.0)

        # chaos host kills → failover
        kills = self.chaos.step_kills(self.t, self.t + dt,
                                      n_hosts=max(t.host for t in
                                                  self.phys.tasks) + 1)
        for host in kills:
            self._fail_host(host)

        # checkpoint coordinator
        if self.t + dt >= self._next_ckpt:
            self._run_checkpoint()
            self._next_ckpt += self.ckpt_cfg.interval_s

        self.metrics.t.append(self.t)
        for n in order:
            self.metrics.qps[n].append(qps_tick[n])
            self.metrics.backlog[n].append(float(self.queue[n].sum()))
        src = [n for n in order if g.op(n).is_source]
        self.metrics.source_lag.append(
            float(sum(self.queue[n].sum() for n in src)))
        self.metrics.dropped += drop_tick
        self.t += dt

    def run(self, duration_s: float) -> ReferenceEngineMetrics:
        n = int(round(duration_s / self.dt))
        for _ in range(n):
            self.tick()
        return self.metrics

    # ------------------------------------------------------------------
    def _fail_host(self, host: int) -> None:
        fo = self.failover
        victims = [t for t in self.phys.tasks if t.host == host]
        if not victims or fo.mode == "none":
            self.chaos.revive(host)
            return
        # passive-restore surcharge at kill time: checkpoint re-read
        # stretched by the storage brownout, plus replay of work since
        # the last successful checkpoint, plus the task's own lazy-load
        # region ready-time (hot_standby never touches the checkpoint,
        # so it pays none of this)
        extra = np.zeros(len(self.phys.tasks))
        if fo.restore_base_s or fo.replay_rate or fo.lazyload_stagger_s:
            bf = self.chaos.brownout_factor(self.t)
            age = self.t - self._last_ckpt_t
            extra = (fo.restore_base_s * bf + age * fo.replay_rate
                     + self._lazy)
        if fo.mode == "hot_standby":
            down = (fo.detect_s + fo.standby_switch_s
                    + fo.standby_staleness_s)
            until = self.t + down
            for t in victims:
                self.down_until[t.op][t.index] = until
                self.queue[t.op][t.index] = 0.0
            self.metrics.recoveries.append(
                {"t": self.t, "mode": "hot_standby",
                 "tasks": len(victims), "downtime": down})
        elif fo.mode == "single_task":
            base = fo.detect_s + fo.single_restart_s
            for i, t in enumerate(self.phys.tasks):
                if t.host == host:
                    self.down_until[t.op][t.index] = (
                        self.t + (base + extra[i]))
                    self.queue[t.op][t.index] = 0.0  # output discarded
            self.metrics.recoveries.append(
                {"t": self.t, "mode": "single_task", "tasks": len(victims),
                 "downtime": float(base + extra[0])})
        else:
            regions = {self.phys.task_region[t.task_id] for t in victims}
            base = fo.detect_s + fo.region_restart_s
            n_restart = 0
            for i, t in enumerate(self.phys.tasks):
                if self.phys.task_region[t.task_id] in regions:
                    self.down_until[t.op][t.index] = (
                        self.t + (base + extra[i]))
                    self.queue[t.op][t.index] = 0.0
                    n_restart += 1
            self.metrics.recoveries.append(
                {"t": self.t, "mode": "region", "tasks": n_restart,
                 "downtime": float(base + extra[0])})
        self.chaos.revive(host)  # replacement host

    # ------------------------------------------------------------------
    def _run_checkpoint(self) -> None:
        cfg = self.ckpt_cfg
        m = self.metrics
        m.ckpt_attempts += 1
        timeout = cfg.interval_s
        # deterministic brownout ramp stretches every upload of this
        # attempt (computed BEFORE any rng draw — same order as
        # core.chaos.run_checkpoint_attempt)
        bf = self.chaos.brownout_factor(self.t)
        # per-task upload durations with chaos slow factors
        task_fail: dict[int, bool] = {}
        for t in self.phys.tasks:
            dur = cfg.upload_s * self.chaos.storage_latency_factor() * bf
            task_fail[t.task_id] = dur > timeout or not self._alive(t.op)[t.index]
        if cfg.mode == "global":
            ok = not any(task_fail.values())
        else:
            ok = True
            for region in self.phys.regions:
                bad = any(task_fail[tid] for tid in region)
                if bad and cfg.retry_failed_region:
                    # one in-attempt retry of the region's uploads
                    bad = any(cfg.upload_s
                              * self.chaos.storage_latency_factor() * bf
                              > timeout for _ in region)
                if bad:
                    ok = False  # region keeps previous snapshot; attempt
                    break       # counted failed, job continues (no abort)
        m.ckpt_success += int(ok)
        m.ckpt_failed += int(not ok)
        if ok:
            self._last_ckpt_t = self.t
