"""Batched JAX twin of the vectorized stream engine (`jit`/`scan`/`vmap`).

A functional re-expression of `streams.engine.StreamEngine` for chaos
sweeps: where the numpy engine mutates a flat task arena in place, this
twin threads a single pytree of arena state through a pure
`state -> state` tick lowered from the same `RoutingPlan`
(`streams.engine.build_plan`), runs whole horizons as one
`jax.lax.scan` under `jit`, and `vmap`s the scan over a ``(S,)`` batch
of failure seeds so thousands of chaos scenarios execute in a single
device call.

Lowering pipeline (plan → padded tensors → segment-sum tick)
------------------------------------------------------------
The jitted tick is O(1) in graph size. The pipeline has three stages:

1. `streams.engine.build_plan` lowers the logical graph into the
   `RoutingPlan` both engines share (arena slices, per-op scalars,
   per-edge routing constants).
2. `streams.engine.lower_tensor_plan` flattens the plan into per-*phase*
   edge tensors: src/dst task index vectors, per-entry partitioner
   masks, globally-numbered block/group tables (one trailing dummy
   segment each, so ragged fan-outs become shape-padded segment ids).
   A phase is one slot of a static schedule that reproduces the numpy
   tick's sequential op order exactly: ops consume after all upstream
   deposits, and edges sharing a destination op serialize across phases
   (the head-of-line `free`-credit reads must nest). The number of
   phases is bounded by the longest in-tick pipeline chain of a single
   job — NOT by op/edge count — so packing hundreds of jobs into one
   arena leaves the trace size unchanged.
3. `_build_run` emits, per phase, a constant number of gathers +
   `segment_sum`/`segment_min`/`segment_max` passes over ALL of the
   phase's edges at once (consume → route → accept → deposit), so the
   trace does not grow with the op and edge count.

Dense / compact lowering contract (``phase_mode``)
--------------------------------------------------
`lower_tensor_plan` has two flavors sharing the phase schedule; every
engine/sweep entry point takes ``phase_mode`` (one of
`engine.PHASE_MODES`: "dense" | "compact" | "auto", default auto via
`engine.select_phase_mode`; any other value raises `ValueError` before
anything is lowered or traced):

* **dense** (`engine.PhaseTensors`, `_build_run`) — the parity
  reference the tests hold compact to. Per phase it multiplies
  arena-wide masks and runs arena-sized segment reductions; the
  integer structure (index vectors, partitioner masks, segment tables)
  is BAKED into the trace and digested into `TensorPlan.key`, floats
  are traced. Work per tick is O(n_phases × n_tasks) regardless of how
  few tasks a phase touches.
* **compact** (`engine.CompactPhase`, `_build_compact_run`) — the
  sparse-phase path, and the lowering every chip cell runs. Every
  arena-sized segment reduction becomes a row-table gather+reduce over
  just the phase's active tasks / source ops / dst entries (rows
  pow2-padded with mask columns — the same bucketing discipline as
  seed padding), and ALL index/mask tables ride the params pytree as
  traced leaves: the trace key is only the bucket shape signature, so
  same-bucket plans (e.g. same-shape graphs with different partitioner
  kinds, placements or routing tables) share ONE compiled trace.
  Consumption stays arena-wide elementwise (bit-identical to dense);
  row reductions preserve each segment's member order, so compact ==
  dense at 1e-12 over full runs (tests/test_sparse_phase.py).

"auto" picks compact exactly when the eliminated arena-wide reductions
dominate the row-gather cost (deep packed arenas), scaled by the
seed-axis width of the requesting sweep (`select_phase_mode`'s
``seed_width``: wide batches amortize the row-table overhead, so
shallow-but-wide sweeps go compact too); small single-seed graphs stay
dense. Setting ``REPRO_REQUIRE_PHASE_MODE=compact`` (or ``dense``)
turns a silent fallback into a hard error — scripts/ci.sh's smoke
targets use it.

All resiliency floats are *traced leaves* of the params pytree, never
compile-time constants: per-task failover vectors (detect / restart
budgets / mode masks — per-job `FailoverConfig` lists lower to per-task
vectors via `streams.engine.per_task_failover`), queue capacities,
selectivities, source rates, and the per-phase hash-share / weakhash-
mass tables. Sweeping any of them reuses the compiled trace; only the
integer structure tensors (digested into `TensorPlan.key`) key the
trace cache.

State-pytree layout (`EngineState`, one leaf per arena variable; under
`vmap` every leaf gains a leading ``(S,)`` seed axis):

    queue      (n_tasks,) f64  bounded input queues (records)
    down_until (n_tasks,) f64  failover downtime horizon per task
    speed      (n_tasks,) f64  static host speed (overrides × stragglers)
    ckpt_epoch ()         i32  checkpoint attempts so far
    emitted    (n_jobs,)  f64  source records emitted, per job segment
    dropped    (n_jobs,)  f64  single_task failover drops, per job segment
    up_until   (n_tasks,) f64  upgrade/rollback-wave downtime horizon
                               (separate from down_until so checkpoint
                               alive masks — which must match the
                               pregenerated timelines draw-for-draw —
                               never see deployment downtime)
    rb_t       ()         f64  auto-rollback fire time (+inf = not fired)
    dacc       ()         f64  controller EWMA of canary−stable backlog

Chaos pregeneration semantics (the one intentional delta vs the numpy
engine's *mechanism*, not its numbers): a `jit`-ted scan cannot consume
sequential numpy rng draws, so all chaos is materialized up front by
`core.chaos.build_chaos_timeline` — draw-for-draw in the engine's rng
consumption order — into per-tick event tensors (host-kill masks,
checkpoint attempt counts, straggler speeds). Event times are thereby
quantized to tick boundaries, which is exactly the resolution at which
the tick-driven numpy engine observes them, so metrics stay pinned to
`StreamEngine` at 1e-5 over full runs (`tests/test_jax_engine.py`);
checkpoint outcomes and recovery events ride along as host-side
metadata because they never feed back into queue dynamics.

External-system event tensors + replication recovery modes
----------------------------------------------------------
The per-tick ``xs`` stream carries four deterministic (rng-free)
external-system curves next to the kill masks, always present so the
pytree structure — and hence the trace — is stable:

    bfac  (n_ticks, n_jobs) f64  storage brownout latency factor
                                 (`core.chaos.brownout_curve`: tent
                                 ramps from `ChaosSpec.brownout_at`
                                 plus any config-axis ramps, composed
                                 by tuple concatenation so grid rows
                                 stay bit-identical to rebuilds)
    gate  (n_ticks, n_jobs) f64  MQ/coordinator availability in {0,1}:
                                 `mq_gate_curve` over
                                 `ChaosSpec.mq_down` windows ×
                                 `coordinator_gate_curve` over the
                                 ZK∩HDFS leader-loss overlap (`zk_down`
                                 / `hdfs_down` — leadership survives on
                                 either store, so only overlapping
                                 windows gate); source emission is
                                 multiplied by the gate
    ckage (n_ticks, n_jobs) f64  checkpoint age at tick start
                                 (`ckpt_age_curve`, tick-exclusive:
                                 a success at tick i lowers the age
                                 from tick i+1 on)
    rfac  (n_ticks, n_jobs) f64  traffic-rate factor
                                 (`core.chaos.traffic_curve`: per-job
                                 diurnal sinusoids from
                                 `ChaosSpec.diurnal` — phase-shifted
                                 by `rate_phase_s` — × flash-crowd
                                 trapezoids from `ChaosSpec.flash_at`,
                                 plus config-axis patterns composed by
                                 tuple concatenation exactly like
                                 brownout ramps); source emission is
                                 multiplied by the factor, so a
                                 constant-rate spec yields an exact
                                 all-ones curve and the ``×1.0`` path
                                 is bit-identical to traffic-free runs

All four gather per task through ``pa["job_of_task"]`` inside the
tick. Region-correlated failure bursts (`ChaosSpec.burst_at`) lower as
scheduled kills merged into the same kill scan — none of these events
consume rng draws, preserving the draw-for-draw replay contract.

Failover lowers four recovery modes per task (traced mode masks, so a
config grid can mix them row by row): ``none`` / ``region`` /
``single_task`` pay passive-restore cost — downtime =
``detect + restart + restore_base·bfac(t) + ckage(t)·replay_rate +
lazy_extra`` where ``lazy_extra`` is the lazy-load per-region ready
stagger (`streams.engine.lazy_ready_extra`) — while ``hot_standby``
pays ``detect + standby_switch + standby_staleness`` only (no
brownout/age/drop exposure; the standby assumes execution). The
brownout factor thus stretches both checkpoint attempt durations (in
the timeline build) and passive restores (in the tick), which is what
makes the replication-vs-checkpoint tradeoff surface
(`streams.chaos_sweep.replication_tradeoff`) come out of ONE
`sweep_configs` device pass.

Deployment-event + canary-mask lowering contract (drills)
---------------------------------------------------------
`UpgradeConfig` deployment drills (traced canary/rolling upgrades with
in-trace auto-rollback) lower through `streams.engine.lower_upgrade`
into 18 always-present params leaves (`_DRILL_KEYS`; inert zeros/infs
when no drill is configured, so drill and drill-free runs share one
trace). The contract:

* **Upgrades are in-trace only.** `ChaosSpec.upgrade_at` / the
  `UpgradeConfig` never reach the timeline builders: the kill,
  checkpoint and straggler draw streams are upgrade-free, so the
  draw-for-draw replay contract and a flat ``timeline_build_count``
  hold trivially across the drill axis.
* **Wave downtimes ride a separate state leaf** (``up_until``).
  Routing aliveness is ``(down_until <= t) & (up_until <= t)`` while
  checkpoint alive masks keep reading ``down_until`` alone — matching
  the host-side timelines. Upgrade/rollback restarts are *graceful*:
  queues are NOT zeroed (unlike crash failover), so an
  identical-config upgrade with zero wave downtime is a bit-exact
  no-op.
* **Canary config is a delta, not a branch.** Per-task activation
  ``act = up_cmask · (t >= up_start + up_down) · (t < rb_t +
  up_rstag)`` (a traced float mask) applies every canary override as
  ``base + act · d_*``: failover downtimes/modes, restore/replay
  surcharges, checkpoint-interval age scaling and selectivity. With
  ``act = 0`` each formula reduces to the exact base arithmetic
  (``×1.0`` / ``+0.0``), which is the drill-free parity guarantee.
* **Rollback is a traced scan-carried controller.** Per tick the
  controller EWMAs the mean-canary-minus-mean-stable backlog through
  one dot product (``queue @ up_wdelta``), arms at ``up_t0`` (first
  canary wave's end) and latches ``rb_t`` when the EWMA crosses
  ``up_thresh``; rollback waves then restart only canary tasks
  (``up_rstag`` is +inf off-canary) and ``act`` reverts — no rng, no
  host round-trip, vmappable across (mixes × configs × seeds).

Rate-schedule + scale-event lowering contract (autoscaling)
-----------------------------------------------------------
`engine.AutoscaleConfig` in-trace DS2 autoscalers lower through
`streams.engine.lower_autoscale` into 21 always-present params leaves
(`AUTOSCALE_KEYS`; `engine.inert_autoscale_leaves` no-op values —
finite ``1e18`` sentinels instead of +inf wherever traced arithmetic
divides or subtracts — when no scaler is configured, so scaled and
unscaled runs share one trace). The contract:

* **Rate schedules ride ``xs``, scale events ride the state.** The
  diurnal/flash-crowd curves are pure per-tick tensors (``rfac``
  above, zero rng draws, timeline builders untouched —
  ``timeline_build_count`` stays flat across the traffic axis), while
  the controller's decisions mutate the ``speed`` state leaf inside
  the scan: per decision window (``as_int`` boundaries off ``as_t0``)
  it EWMAs per-task utilization from this tick's consumed records +
  backlog drain demand (DS2's true-rate estimate), proposes
  ``speed · rew / target`` clipped to ``[as_lo, as_hi]``, and fires
  only past hysteresis / cooldown / action-rate / breaker / thrash
  gates. Sources never rescale (``as_mask`` = 0 on source tasks).
* **Rescales are graceful and costed.** A firing task keeps its
  queue and pays ``as_down + as_move · |Δspeed|`` on the ``up_until``
  leaf — deploy downtime from `core.hotupdate.deploy_downtime` plus
  the `train/elastic.resize_move_seconds` state-move model — so
  rescale-during-recovery interactions (both horizons racing) are
  traced, not emulated.
* **Degradation is the breaker path.** ``failcnt`` counts failover
  hits within ``as_fw`` of a rescale; at ``as_bfail`` the breaker
  opens for ``as_brs`` seconds, freezing decisions and load-shedding
  via the ``as_shed`` selectivity factor (the `DS2Scaler` host
  breaker's traced twin). The thrash guard latches ``thrash_t`` when
  the leaky direction-flip counter crosses ``as_tflip``, freezing the
  controller for the rest of the run (autoscaler-vs-failover
  oscillation surfaces as a finite ``thrash_t`` metric).
* **Queue capacities stay fixed.** In-trace rescales do NOT scale
  ``qcap`` (parity with the numpy tick, which keeps each task's buffer;
  see `engine.AutoscaleConfig`). Host-side rollback of failed resizes
  stays in `core.autoscaler.DS2Scaler` — the traced twin models
  breaker + shed instead.

Compiled `run` functions are cached per *plan shape* (the `TensorPlan`
digest + region count — never float parameters, which are traced), so
two engines over same-shaped graphs share one trace; `get_cached_run_fns`
exposes the cache for tests. The state argument is donated, so each
call's arena buffers are reused in place.

Mega-arena sweeps: a `streams.engine.PackedArena` drops in for the
graph everywhere (`JaxStreamEngine`, `run_batch`, `run_mix_batch`,
`run_config_batch`) — K co-located jobs then scan as one arena with
per-job emitted/dropped segment sums (a static job index per op) and
per-job recovery attribution riding the shared-host chaos timeline.
`run_batch` pads the seed axis to the next power of two (retrace-free
batching: one trace per pow2 bucket, pad rows sliced off before
metrics) and can split the padded batch across local devices
(``devices=``) through `jax.shard_map` (`repro.dist.sharding`).
`run_mix_batch` adds a second vmap axis over job-mix configs (per-job
source-rate multipliers); `run_config_batch` adds a third over
resiliency-config grids (`FailoverConfig`/`CheckpointConfig` per grid
row, optionally per job), so a (mixes × configs × seeds) scenario cube
runs as one device call on one trace. `run_config_batch(devices=...)`
splits the grid's flat seed axis across local devices too
(`dist.sharding.sharded_grid_fn`, rows bit-identical to the
single-device grid), and checkpoint-bearing grids refit each config's
attempt schedule onto per-seed draw streams
(`core.chaos.build_grid_timelines`) instead of replaying a host
timeline per (config, seed). ``chaos=`` / ``base_spec=`` accept
per-job `ChaosSpec` lists for packed arenas (per-job kill rates /
straggler intensities drawn in each job's local host domain and lifted
onto the shared pool — `core.chaos.build_perjob_chaos_timeline`).

Chunked execution + shared trace-cache keying (sweep-as-a-service)
------------------------------------------------------------------
Every batch entry point decomposes into a *plan* (`SeedBatchPlan` /
`ConfigGridPlan`: lowering, per-config traced params, timeline-path
selection, trace-cache lookup — all seed-count-independent) plus
`prep_chunk(lo, hi)` / `dispatch` / `fetch` over half-open seed
slices, driven by `run_chunks`' double-buffered pipeline: host timeline
prep for chunk k+1 runs on the caller thread while chunk k's device
pass blocks on a one-slot executor lane (XLA releases the GIL, so prep
and compute genuinely overlap), then copies its history to the host:
per-op rows, or under `SummaryGridPlan` (the summary sweep's plan) only
the per-tick lag and backlog series, reduced on the device first.
Each step is a span of the request's `streams.spans.SpanLog`. The
chunking contract:

* **Bit-parity.** All per-seed grid state is seed-separable (one
  `_SeedStream` per seed, per-seed curves, no cross-seed reductions
  device-side), so the `concat_batches` of any chunk partition is
  bit-identical to the monolithic call — including ragged last chunks,
  which pad to their own pow2 bucket before slicing. Pinned by
  tests/test_sweep_service.py.
* **Build-count flatness.** Each seed's timelines are built exactly
  once across all chunks: the ckpt-grid path shares ONE
  `core.chaos.GridTimelineBuilder` (lazy per-seed streams) across
  chunks, and the no-ckpt/exotic paths touch each seed in exactly one
  chunk. `timeline_build_count()` matches the monolithic call.
* **Shared keying.** The six process-global caches (`_FN_CACHE`,
  `_SHARD_CACHE`, `_CFG_SHARD_CACHE`, `_MIX_CACHE`, `_CFG_CACHE`,
  `_CFG_MIX_CACHE`) key on ``(TickDesc, variant)`` where `TickDesc` =
  (`TensorPlan` digest — the bucket signature under compact — and
  region count) and the variant adds shard count / ``shared_kills``.
  Chunk size, seed count, request identity and every float are absent
  from the key, so concurrent requests over same-shaped plans hit ONE
  compiled trace; only the pow2 seed-bucket of the *padded* chunk
  retraces.
  All lookups funnel through `_cache_get` under one lock:
  `trace_cache_stats()` exposes process-wide hit/miss counters and
  `scoped_cache_stats` thread-local per-request ones (each plan
  records its own lookup in `cache_info`, surfaced per request by
  `launch.serve.SweepService`).

Everything runs in float64 to hold parity with the float64 numpy
engine, under the thread-local ``jax.enable_x64(True)`` context and never
a global config flip: the caller's ``jax_enable_x64`` is untouched. Each
thread that calls a cached run fn enters the context itself (the
`run_chunks` lane and the `launch.serve.SweepService` workers run
`dispatch` and `fetch`, which do), since a fn traced under x64 and
called outside it would retrace in float32.
"""
from __future__ import annotations

import dataclasses
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.chaos import (ChaosEngine, ChaosSpec, ChaosTimeline,
                              GridTimelineBuilder, brownout_curve,
                              build_chaos_timeline,
                              build_perjob_chaos_timeline, ckpt_age_curve,
                              coordinator_gate_curve, mq_gate_curve,
                              refit_failover, traffic_curve)
from repro.dist.sharding import (local_shard_count, sharded_grid_fn,
                                 sharded_seed_fn)
from repro.streams.engine import (AUTOSCALE_KEYS, AutoscaleConfig,
                                  CheckpointConfig, FailoverConfig,
                                  JobSlice, PackedArena, TensorPlan,
                                  UpgradeConfig, build_plan,
                                  lazy_ready_extra, lower_autoscale,
                                  lower_tensor_plan, lower_upgrade,
                                  per_task_failover)
from repro.streams.graph import LogicalGraph, PhysicalGraph, expand
from repro.streams.spans import SpanLog

class EngineState(NamedTuple):
    """All mutable arena state of one scenario (see module docstring).

    ``emitted`` / ``dropped`` are per-job segment totals of shape
    ``(n_jobs,)`` — single-job engines carry ``(1,)`` vectors (same adds,
    same numerics as the former scalars); packed mega-arenas get the
    per-job breakdown for free from a static segment index per op.

    Deployment-drill leaves (inert zeros/infs without an upgrade):
    ``up_until`` is the graceful-wave downtime per task — kept SEPARATE
    from ``down_until`` so the pregenerated checkpoint draw streams
    (which only know crash failovers) replay draw-for-draw; ``rb_t`` is
    the scalar auto-rollback fire time (+inf = not fired); ``dacc`` the
    drill controller's EWMA of the canary-vs-stable queue delta.

    Autoscaler leaves (config-independent inits; the inert
    `engine.inert_autoscale_leaves` params freeze them exactly):
    ``rew`` per-task EWMA'd utilization, ``lact`` last-rescale time
    (-1e18 = never), ``dirp`` last rescale direction, ``failcnt`` /
    ``brk_until`` circuit-breaker state, ``used`` the leaky
    action-rate bucket, ``flip_acc`` the leaky direction-flip counter,
    ``thrash_t`` the thrash-latch fire time (+inf = not latched),
    ``nact`` rescale actions fired, ``rsec`` integrated
    resource-seconds (Σ speed · dt, the cube's cost axis)."""
    queue: jax.Array
    down_until: jax.Array
    speed: jax.Array
    ckpt_epoch: jax.Array
    emitted: jax.Array
    dropped: jax.Array
    up_until: jax.Array
    rb_t: jax.Array
    dacc: jax.Array
    rew: jax.Array
    lact: jax.Array
    dirp: jax.Array
    failcnt: jax.Array
    brk_until: jax.Array
    used: jax.Array
    flip_acc: jax.Array
    thrash_t: jax.Array
    nact: jax.Array
    rsec: jax.Array


class TickDesc(NamedTuple):
    """Static trace-cache key of a compiled tick: the tensor-plan digest
    plus the placement-level region count (a static `segment_max` size).
    Float parameters — including failover mode masks — are traced, so
    descs are mode- and config-independent."""
    tensor: TensorPlan
    n_regions: int


# ----------------------------------------------------------------------
# tensorized tick: constant number of segment passes per phase
# ----------------------------------------------------------------------
#: named scopes of the dense and compact ticks' steps, in tick order:
#: alive/capacity set-up, per phase its consumption, routing of the
#: produced records and their acceptance downstream, then
#: `_finish_tick`'s failover (with the checkpoint counter), drill
#: controller, autoscaler and metric rows
TICK_STEPS = ("tick_setup", "tick_consume", "tick_route", "tick_accept",
              "tick_failover", "tick_drill", "tick_autoscale", "tick_rows")


def _build_compact_run(desc: TickDesc):
    """The chip's tick: `_build_run`'s dense tick with every arena-sized
    segment reduction turned into a row-table gather+reduce over
    just the phase's active entries (`engine.CompactPhase`), and all
    index/mask tables are *traced* parameters (`pa["edges"][fi]`), so
    the trace key is only the pow2 bucket signature — same-bucket plans
    share one compiled trace. Numerics are pinned to the dense tick:
    consumption stays arena-wide elementwise (bit-identical), rows
    preserve each segment's member order, and pads contribute exact
    +0.0 to sums and +inf to head-of-line minima.

    Each step of the tick runs under a `jax.named_scope` (`TICK_STEPS`;
    the per-phase steps carry the phase index, ``tick_route1``). Scopes
    are op metadata only, so a device trace can split the scan's time
    by step while the ops and numerics stay those of the unscoped
    tick."""
    tp, n_regions = desc.tensor, desc.n_regions
    n_ops, n_jobs = tp.n_ops, tp.n_jobs

    def rsum(vals, idx, mask):
        return (vals[idx] * mask).sum(-1)

    def rmin(vals, idx, mask):
        return jnp.where(mask > 0.5, vals[idx], jnp.inf).min(-1)

    def tick(pa, state: EngineState, x):
        with jax.named_scope("tick_setup"):
            t = x["t"]
            q = state.queue
            alive_f = ((state.down_until <= t)
                       & (state.up_until <= t)).astype(q.dtype)
            # canary-config activation: upgrade wave done, rollback wave (if
            # fired) not yet begun — inert leaves make this identically zero
            act = pa["up_cmask"] * ((t >= pa["up_start"] + pa["up_down"])
                                    & (t < state.rb_t + pa["up_rstag"])
                                    ).astype(q.dtype)
            free = jnp.maximum(pa["qcap"] - q, 0.0)
            # breaker-open load shed (graceful degradation): ×1.0 exactly
            # while every breaker is closed — the autoscale-free no-op
            shed_t = jnp.where(t < state.brk_until, pa["as_shed"], 1.0)
            sel_t = (pa["sel"][pa["op_of_task"]] + act * pa["d_sel"]) * shed_t
            ms_eff = pa["mode_single"] + act * pa["d_mode_s"]
            cap_t = pa["cap_base"] * state.speed * alive_f
            emitted, dropped = state.emitted, state.dropped
            produced = jnp.zeros_like(q)
            qps_acc = jnp.zeros((n_ops,), q.dtype)
            take_all = jnp.zeros_like(q)

            gate_t = x["gate"][pa["job_of_task"]]  # MQ source gate (0/1)
            rfac_t = x["rfac"][pa["job_of_task"]]  # traffic-rate factor
        for fi, ph in enumerate(tp.phases):
            eph = pa["edges"][fi]
            if ph.consumes:
                with jax.named_scope(f"tick_consume{fi}"):
                    take = jnp.minimum(q, cap_t * eph["cons_mask"])
                    q = q - take
                    take_all = take_all + take
                    src_emit = (pa["src_row"] * alive_f * eph["cons_mask"]
                                * gate_t * rfac_t)
                    produced = produced + (src_emit + take * sel_t)
                    if len(ph.e_jobs):
                        emitted = emitted.at[eph["e_jobs"]].add(
                            rsum(src_emit, eph["e_idx"], eph["e_mask"]))
                    qps_acc = qps_acc.at[eph["q_ops"]].add(
                        rsum(take, eph["q_idx"], eph["q_mask"]))
            if not ph.D:
                continue
            with jax.named_scope(f"tick_route{fi}"):
                dst = eph["dst_task"]
                edge_of = eph["edge_of"]
                alive_d = alive_f[dst]
                free_d = free[dst]
                # per-source-op slot totals — O(live src tasks)
                tot_slot = rsum(produced, eph["s_idx"], eph["s_mask"])
                tot_e = tot_slot[eph["slot_of_edge"]]
                tot_d = tot_e[edge_of]
                # forward: pointwise src task → dst task
                arr_fwd = produced[eph["fwd_src"]] * alive_d
                # rescale family: per-block rate over alive destinations
                if ph.B:
                    prod_blk = rsum(produced, eph["bs_idx"], eph["bs_mask"])
                    alive_blk = rsum(alive_d * eph["dst_in_blk"],
                                     eph["br_idx"], eph["br_mask"])
                    has = alive_blk > 0.0
                    rate_blk = jnp.where(
                        has, prod_blk / jnp.where(has, alive_blk, 1.0), 0.0)
                    arr_blk = jnp.where(eph["dst_in_blk"] > 0.0,
                                        rate_blk[eph["blk_of"]] * alive_d,
                                        0.0)
                else:
                    arr_blk = jnp.zeros_like(alive_d)
                # weakhash: group mass spread ∝ free capacity (fallback to
                # alive-uniform when a whole group is down)
                if ph.G:
                    wh = eph["m_weakhash"] > 0.5
                    grp_of = eph["grp_of"]
                    cap_w = jnp.maximum(free_d, 1e-9) * alive_d
                    alive_eps = alive_d + 1e-9
                    capsum = rsum(jnp.where(wh, cap_w, 0.0), eph["gr_idx"],
                                  eph["gr_mask"])
                    capsum_fb = rsum(jnp.where(wh, alive_eps, 0.0),
                                     eph["gr_idx"], eph["gr_mask"])
                    fall = capsum <= 0.0
                    cap2 = jnp.where(fall[grp_of], alive_eps, cap_w) * alive_d
                    capsum2 = jnp.where(fall, capsum_fb, capsum)
                    val_wh = cap2 * eph["mass"] / capsum2[grp_of]
                else:
                    val_wh = jnp.zeros_like(alive_d)
                # backlog: divert away from congested channels
                open_ = (free_d > pa["qcap"][dst] * 0.25).astype(q.dtype)
                val_bk = (jnp.maximum(free_d, 1e-9) * alive_d
                          * jnp.maximum(open_, 0.05))
                val_nrm = jnp.where(eph["m_weakhash"] > 0.5, val_wh,
                                    jnp.where(eph["m_backlog"] > 0.5, val_bk,
                                              alive_d)) * eph["is_norm"]
                rs = rsum(val_nrm, eph["er_idx"], eph["er_mask"])
                ratio_e = jnp.where(rs > 0.0, tot_e / rs, 0.0)
                arr_nrm = val_nrm * ratio_e[edge_of]
                arriving = jnp.where(
                    eph["m_fwd"] > 0.5, arr_fwd,
                    jnp.where(eph["m_blk"] > 0.5, arr_blk,
                              jnp.where(eph["m_hash"] > 0.5,
                                        tot_d * eph["share"], arr_nrm)))
                dead_s = (alive_d <= 0.0) & (ms_eff[dst] > 0.5)
                dropped = dropped.at[eph["dj_jobs"]].add(
                    rsum(jnp.where(dead_s, arriving, 0.0), eph["dj_idx"],
                         eph["dj_mask"]))
                arriving = jnp.where(dead_s, 0.0, arriving)
            with jax.named_scope(f"tick_accept{fi}"):
                # acceptance: head-of-line / per-block / adaptive credits
                live = arriving > 1e-9
                ratio = jnp.where(live,
                                  free_d / jnp.maximum(arriving, 1e-300),
                                  jnp.inf)
                lam_e = jnp.minimum(rmin(ratio, eph["er_idx"],
                                         eph["er_mask"]), 1.0)
                if ph.B:
                    lam_b = jnp.minimum(rmin(ratio, eph["br_idx"],
                                             eph["br_mask"]), 1.0)
                    acc_blk = arriving * lam_b[eph["blk_of"]]
                else:
                    acc_blk = arriving
                accepted = jnp.where(
                    eph["m_acc_static"] > 0.5, arriving * lam_e[edge_of],
                    jnp.where(eph["m_acc_block"] > 0.5, acc_blk,
                              jnp.minimum(arriving, free_d)))
                # overflow re-queues uniformly at each source op (dense-style
                # broadcast through a small per-slot scatter)
                ovf_e = rsum(arriving - accepted, eph["er_idx"],
                             eph["er_mask"])
                ovf_slot = jax.ops.segment_sum(ovf_e, eph["slot_of_edge"],
                                               num_segments=len(ph.slot_ops))
                ovf_op = jnp.zeros((n_ops,), q.dtype).at[eph["slot_ops"]].add(
                    ovf_slot)
                q = q + (ovf_op / pa["par_of_op"])[pa["op_of_task"]]
                q = q.at[dst].add(accepted)
                free = jnp.maximum(free.at[dst].add(-accepted), 0.0)

        return _finish_tick(pa, state, x, q, emitted, dropped,
                            qps_acc, n_regions, n_ops, act, take_all)

    def run(pa, state, xs):
        return lax.scan(lambda st, x: tick(pa, st, x), state, xs)

    return run


def _finish_tick(pa, state, x, q, emitted, dropped, qps_acc,
                 n_regions, n_ops, act, take_all):
    """Shared end-of-tick block of the dense and compact ticks: chaos
    host kills → failover (per-task mode masks + passive-restore
    surcharge from the external-event tensors), checkpoint attempt
    counter, per-op metric rows.

    The restore surcharge ``extra = restore_base * brownout(t) +
    ckpt_age(t) * replay_rate + lazy_extra`` rides the per-tick per-job
    event rows (``x["bfac"]`` / ``x["ckage"]``) gathered per task;
    hot-standby victims pay switch + staleness replay instead and never
    touch checkpoint storage. Zero vectors reduce to the historical
    region/single downtimes bit-for-bit."""
    with jax.named_scope("tick_failover"):
        t = x["t"]
        vict = x["kills"][pa["task_host"]]
        # active canary slices crash under the canary config: mode masks and
        # downtimes apply their ``act``-gated deltas (exact no-ops when
        # inert — adding act * 0.0 and comparing 0/1 masks against 0.5)
        ms_eff = pa["mode_single"] + act * pa["d_mode_s"]
        mr_eff = pa["mode_region"] + act * pa["d_mode_r"]
        mh_eff = pa["mode_hot"] + act * pa["d_mode_h"]
        hit_s = (vict > 0.0).astype(q.dtype) * (ms_eff > 0.5)
        reg_hit = jax.ops.segment_max(vict * (mr_eff > 0.5),
                                      pa["task_region"],
                                      num_segments=n_regions)
        hit_r = (reg_hit[pa["task_region"]] > 0.0).astype(q.dtype)
        hit_h = (vict > 0.0).astype(q.dtype) * (mh_eff > 0.5)
        extra = ((pa["restore_base"] + act * pa["d_restore"])
                 * x["bfac"][pa["job_of_task"]]
                 + x["ckage"][pa["job_of_task"]] * (1.0 + act * pa["d_ck"])
                 * (pa["replay_rate"] + act * pa["d_replay"])
                 + pa["lazy_extra"])
        until_s = t + (pa["detect"] + pa["restart_single"]
                       + act * pa["d_down_s"] + extra)
        until_r = t + (pa["detect"] + pa["restart_region"]
                       + act * pa["d_down_r"] + extra)
        until_h = t + (pa["detect"] + pa["standby_switch"]
                       + pa["standby_stale"] + act * pa["d_down_h"])
        down_until = jnp.where(hit_r > 0.0, until_r,
                               jnp.where(hit_s > 0.0, until_s,
                                         jnp.where(hit_h > 0.0, until_h,
                                                   state.down_until)))
        hit_any = jnp.maximum(jnp.maximum(hit_r, hit_s), hit_h)
        q = jnp.where(hit_any > 0.0, 0.0, q)

        ckpt_epoch = state.ckpt_epoch + x["ckpt"].astype(jnp.int32)

    with jax.named_scope("tick_drill"):
        # drill controller + wave scheduler (same order as the numpy tick:
        # EWMA update → rollback decision on the UPDATED accumulator → wave
        # triggers on the UPDATED rollback time). up_rstag is +inf off the
        # canary slice, so a fired rollback never restarts stable tasks.
        delta = q @ pa["up_wdelta"]
        g = (t >= pa["up_t0"]).astype(q.dtype)
        dacc = state.dacc + g * pa["up_alpha"] * (delta - state.dacc)
        fire = ((t >= pa["up_t0"]) & (dacc > pa["up_thresh"])
                & jnp.isinf(state.rb_t))
        rb_t = jnp.where(fire, t + pa["dt"], state.rb_t)
        trig_up = ((t <= pa["up_start"])
                   & (pa["up_start"] < t + pa["dt"]))
        up_until = jnp.maximum(
            state.up_until,
            jnp.where(trig_up, pa["up_start"] + pa["up_down"], 0.0))
        rb_start = rb_t + pa["up_rstag"]
        trig_rb = (t <= rb_start) & (rb_start < t + pa["dt"])
        up_until = jnp.maximum(
            up_until, jnp.where(trig_rb, rb_start + pa["up_down"], 0.0))

    with jax.named_scope("tick_autoscale"):
        # in-trace DS2 autoscaler (end-of-tick, AFTER kills/ckpt/drill —
        # same order as the numpy tick): utilization EWMA first, breaker
        # update on this tick's failover hits, then the decision reads the
        # UPDATED accumulator and UPDATED breaker. Inert autoscale leaves
        # make every update an exact arithmetic no-op.
        dt_ = pa["dt"]
        cap_now = pa["cap_base"] * state.speed
        need = ((take_all + q * (dt_ / pa["as_drain"]))
                / jnp.maximum(cap_now, 1e-9))
        rew = state.rew + pa["as_alpha"] * (need - state.rew)
        recent = (t - state.lact) <= pa["as_fw"]
        failev = (hit_any > 0.0) & recent
        crossed = (((t - state.lact) > pa["as_fw"])
                   & ((t - dt_ - state.lact) <= pa["as_fw"]))
        failcnt = jnp.where(
            failev, state.failcnt + 1.0,
            jnp.where(crossed & (hit_any <= 0.0), 0.0, state.failcnt))
        brk_fire = failcnt >= pa["as_bfail"]
        brk_until = jnp.where(brk_fire, t + pa["as_brs"], state.brk_until)
        failcnt = jnp.where(brk_fire, 0.0, failcnt)
        boundary = (jnp.floor((t + dt_ - pa["as_t0"]) / pa["as_int"])
                    > jnp.floor((t - pa["as_t0"]) / pa["as_int"]))
        want = jnp.clip(state.speed * rew / pa["as_tgt"],
                        pa["as_lo"], pa["as_hi"])
        rel = jnp.abs(want - state.speed) / jnp.maximum(state.speed, 1e-9)
        as_fire = (boundary & (pa["as_on"] > 0.0) & (pa["as_mask"] > 0.0)
                   & (rel >= pa["as_hyst"])
                   & ((t - state.lact) >= pa["as_cool"])
                   & (t >= brk_until) & (state.used < pa["as_amax"])
                   & jnp.isinf(state.thrash_t))
        fire_f = as_fire.astype(q.dtype)
        speed = jnp.where(as_fire, want, state.speed)
        lact = jnp.where(as_fire, t, state.lact)
        # graceful rescale: queues persist, the task pays deploy downtime +
        # state-move seconds on the up_until leaf
        downt = pa["as_down"] + pa["as_move"] * jnp.abs(want - state.speed)
        up_until = jnp.maximum(up_until,
                               jnp.where(as_fire, t + downt, 0.0))
        any_fire = (fire_f.sum() > 0.0).astype(q.dtype)
        used = state.used * pa["as_adec"] + any_fire
        dirn = jnp.sign(want - state.speed)
        flip = as_fire & (dirn * state.dirp < 0.0)
        dirp = jnp.where(as_fire, dirn, state.dirp)
        flip_acc = (state.flip_acc * pa["as_tdec"]
                    + flip.astype(q.dtype).sum())
        # thrash latch: freezes the controller from the NEXT tick on (the
        # fire gate above read the PRE-latch thrash_t)
        thrash_t = jnp.where((flip_acc >= pa["as_tflip"])
                             & jnp.isinf(state.thrash_t),
                             t + dt_, state.thrash_t)
        nact = state.nact + fire_f.sum()
        rsec = state.rsec + speed.sum() * dt_

    with jax.named_scope("tick_rows"):
        backlog_row = jax.ops.segment_sum(q, pa["op_of_task"],
                                          num_segments=n_ops)
        qps_row = qps_acc / pa["dt"]
        lag = jnp.dot(backlog_row, pa["src_mask_ops"])
        new_state = EngineState(q, down_until, speed, ckpt_epoch,
                                emitted, dropped, up_until, rb_t, dacc,
                                rew, lact, dirp, failcnt, brk_until, used,
                                flip_acc, thrash_t, nact, rsec)
    return new_state, {"qps": qps_row, "backlog": backlog_row,
                       "lag": lag}


def _build_run(desc: TickDesc):
    if desc.tensor.mode == "compact":
        return _build_compact_run(desc)
    tp, n_regions = desc.tensor, desc.n_regions
    n_ops, n_jobs = tp.n_ops, tp.n_jobs
    op_of_task = tp.op_of_task
    job_of_task = tp.job_of_task
    is_src = tp.is_src_task
    par_of_op = tp.par_of_op
    seg = jax.ops.segment_sum

    def tick(pa, state: EngineState, x):
        with jax.named_scope("tick_setup"):
            t = x["t"]
            q = state.queue
            alive_f = ((state.down_until <= t)
                       & (state.up_until <= t)).astype(q.dtype)
            act = pa["up_cmask"] * ((t >= pa["up_start"] + pa["up_down"])
                                    & (t < state.rb_t + pa["up_rstag"])
                                    ).astype(q.dtype)
            free = jnp.maximum(pa["qcap"] - q, 0.0)
            shed_t = jnp.where(t < state.brk_until, pa["as_shed"], 1.0)
            sel_t = (pa["sel"][op_of_task] + act * pa["d_sel"]) * shed_t
            ms_eff = pa["mode_single"] + act * pa["d_mode_s"]
            cap_t = pa["cap_base"] * state.speed * alive_f
            emitted, dropped = state.emitted, state.dropped
            produced = jnp.zeros_like(q)
            qps_acc = jnp.zeros((n_ops,), q.dtype)
            take_all = jnp.zeros_like(q)

            gate_t = x["gate"][job_of_task]  # MQ source gate (0/1)
            rfac_t = x["rfac"][job_of_task]  # traffic-rate factor
        for fi, ph in enumerate(tp.phases):
            if ph.consumes:
                with jax.named_scope(f"tick_consume{fi}"):
                    take = jnp.minimum(q, cap_t * ph.cons_mask)
                    q = q - take
                    take_all = take_all + take
                    src_emit = (pa["src_row"] * alive_f * ph.cons_mask * is_src
                                * gate_t * rfac_t)
                    produced = produced + (src_emit + take * sel_t)
                    emitted = emitted + seg(src_emit, job_of_task,
                                            num_segments=n_jobs)
                    qps_acc = qps_acc + seg(take, op_of_task,
                                            num_segments=n_ops)
            if not ph.D:
                continue
            with jax.named_scope(f"tick_route{fi}"):
                eph = pa["edges"][fi]
                dst = ph.dst_task
                alive_d = alive_f[dst]
                free_d = free[dst]
                tot_op = seg(produced, op_of_task, num_segments=n_ops)
                tot_e = tot_op[ph.src_op_of_edge]
                tot_d = tot_e[ph.edge_of]
                # forward: pointwise src task → dst task
                arr_fwd = produced[ph.fwd_src] * alive_d
                # rescale family: per-block rate = block production over the
                # block's alive destinations
                prod_blk = seg(produced[ph.bsrc_task], ph.bsrc_blk,
                               num_segments=ph.B + 1)
                alive_blk = seg(alive_d * ph.dst_in_blk, ph.blk_of,
                                num_segments=ph.B + 1)
                has = alive_blk > 0.0
                rate_blk = jnp.where(
                    has, prod_blk / jnp.where(has, alive_blk, 1.0), 0.0)
                arr_blk = jnp.where(ph.dst_in_blk > 0.0,
                                    rate_blk[ph.blk_of] * alive_d, 0.0)
                # weakhash: key-group mass spread ∝ free capacity;
                # groups with zero capacity fall back to alive-uniform
                # spread
                cap_w = jnp.maximum(free_d, 1e-9) * alive_d
                alive_eps = alive_d + 1e-9
                capsum = seg(jnp.where(ph.is_weakhash, cap_w, 0.0), ph.grp_of,
                             num_segments=ph.G + 1)
                capsum_fb = seg(jnp.where(ph.is_weakhash, alive_eps, 0.0),
                                ph.grp_of, num_segments=ph.G + 1)
                fall = capsum <= 0.0
                cap2 = jnp.where(fall[ph.grp_of], alive_eps, cap_w) * alive_d
                capsum2 = jnp.where(fall, capsum_fb, capsum)
                val_wh = cap2 * eph["mass"] / capsum2[ph.grp_of]
                # backlog: divert away from congested channels
                open_ = (free_d > pa["qcap"][dst] * 0.25).astype(q.dtype)
                val_bk = (jnp.maximum(free_d, 1e-9) * alive_d
                          * jnp.maximum(open_, 0.05))
                # normalized all-to-all family (rebalance/weakhash/backlog):
                # identical weight rows → scale one row to the edge total
                val_nrm = jnp.where(ph.is_weakhash, val_wh,
                                    jnp.where(ph.is_backlog, val_bk,
                                              alive_d)) * ph.is_norm
                rs = seg(val_nrm, ph.edge_of, num_segments=ph.n_edges)
                ratio_e = jnp.where(rs > 0.0, tot_e / rs, 0.0)
                arr_nrm = val_nrm * ratio_e[ph.edge_of]
                arriving = jnp.where(
                    ph.is_fwd, arr_fwd,
                    jnp.where(ph.is_blk, arr_blk,
                              jnp.where(ph.is_hash, tot_d * eph["share"],
                                        arr_nrm)))
                # records routed to a dead single_task-mode task drop
                # (γ=partial); edges never cross jobs, so the dst job segment
                # owns the drop
                dead_s = (alive_d <= 0.0) & (ms_eff[dst] > 0.5)
                dropped = dropped + seg(jnp.where(dead_s, arriving, 0.0),
                                        ph.job_of_entry, num_segments=n_jobs)
                arriving = jnp.where(dead_s, 0.0, arriving)
            with jax.named_scope(f"tick_accept{fi}"):
                # acceptance: head-of-line (per edge), per block
                # (group_rescale), or adaptive credits (weakhash/backlog)
                live = arriving > 1e-9
                ratio = jnp.where(live,
                                  free_d / jnp.maximum(arriving, 1e-300),
                                  jnp.inf)
                lam_e = jnp.minimum(
                    jax.ops.segment_min(ratio, ph.edge_of,
                                        num_segments=ph.n_edges), 1.0)
                lam_b = jnp.minimum(
                    jax.ops.segment_min(ratio, ph.blk_of,
                                        num_segments=ph.B + 1), 1.0)
                accepted = jnp.where(
                    ph.acc_static, arriving * lam_e[ph.edge_of],
                    jnp.where(ph.acc_block, arriving * lam_b[ph.blk_of],
                              jnp.minimum(arriving, free_d)))
                # overflow re-queues uniformly at the source op
                ovf_e = seg(arriving - accepted, ph.edge_of,
                            num_segments=ph.n_edges)
                ovf_op = seg(ovf_e, ph.src_op_of_edge, num_segments=n_ops)
                q = q + (ovf_op / par_of_op)[op_of_task]
                q = q.at[dst].add(accepted)
                free = jnp.maximum(free.at[dst].add(-accepted), 0.0)

        # pregenerated chaos host kills → failover, ckpt counter, metric
        # rows (shared with the compact tick)
        return _finish_tick(pa, state, x, q, emitted, dropped,
                            qps_acc, n_regions, n_ops, act, take_all)

    def run(pa, state, xs):
        return lax.scan(lambda st, x: tick(pa, st, x), state, xs)

    return run


# ----------------------------------------------------------------------
# per-plan-shape trace caches
# ----------------------------------------------------------------------
_FN_CACHE: dict = {}
_SHARD_CACHE: dict = {}
_CFG_SHARD_CACHE: dict = {}
_MIX_CACHE: dict = {}
_CFG_CACHE: dict = {}
_CFG_MIX_CACHE: dict = {}

# process-global trace-cache accounting: every cache getter goes through
# `_cache_get` under one lock, so concurrent sweep requests (the
# SweepService worker threads) share the compiled-fn caches race-free
# and hit/miss counts are exact. A "hit" means a request reused a fn
# another request (or an earlier call) already built — the
# one-trace-across-requests property tests assert on top of these.
_CACHE_LOCK = threading.RLock()
_TRACE_STATS = {"hits": 0, "misses": 0}
_TLS = threading.local()


def _cache_get(cache: dict, key, build):
    """Thread-safe get-or-build with hit/miss accounting (global plus
    the calling thread's scoped counter — see `scoped_cache_stats`)."""
    with _CACHE_LOCK:
        hit = key in cache
        _TRACE_STATS["hits" if hit else "misses"] += 1
        scoped = getattr(_TLS, "counts", None)
        if scoped is not None:
            scoped["hits" if hit else "misses"] += 1
        if not hit:
            cache[key] = build()
        return cache[key]


def trace_cache_stats() -> dict:
    """Process-global jit-fn cache hit/miss counters (cumulative)."""
    with _CACHE_LOCK:
        return dict(_TRACE_STATS)


class scoped_cache_stats:
    """Context manager capturing this thread's cache hits/misses —
    per-request attribution for the sweep service (global deltas are
    racy under concurrent workers)."""

    def __enter__(self):
        self.prev = getattr(_TLS, "counts", None)
        _TLS.counts = {"hits": 0, "misses": 0}
        return _TLS.counts

    def __exit__(self, *exc):
        self.counts = _TLS.counts
        _TLS.counts = self.prev
        if self.prev is not None:    # nested scopes roll up to parents
            self.prev["hits"] += self.counts["hits"]
            self.prev["misses"] += self.counts["misses"]
        return False

_XS_AXES = {"t": None, "kills": 0, "ckpt": None,
            "bfac": 0, "gate": 0, "ckage": 0, "rfac": 0}

#: the 18 traced deployment-drill leaves (see `engine.lower_upgrade`):
#: per-task canary mask / wave starts / rollback staggers / controller
#: weights / canary-minus-base config deltas, plus four drill scalars
_DRILL_KEYS = ("up_cmask", "up_start", "up_rstag", "up_wdelta",
               "d_down_s", "d_down_r", "d_down_h",
               "d_mode_s", "d_mode_r", "d_mode_h",
               "d_restore", "d_replay", "d_sel", "d_ck",
               "up_t0", "up_down", "up_thresh", "up_alpha")

# job-mix vmap axis: only the per-task source emission row varies with a
# job mix (service capacity / selectivity are per-job constants the mix
# leaves alone); everything else is broadcast
_PA_MIX_AXES = {"qcap": None, "src_row": 0, "cap_base": None, "sel": None,
                "dt": None, "task_host": None, "task_region": None,
                "detect": None, "restart_region": None,
                "restart_single": None, "mode_single": None,
                "mode_region": None, "mode_hot": None,
                "standby_switch": None, "standby_stale": None,
                "restore_base": None, "replay_rate": None,
                "lazy_extra": None, "job_of_task": None,
                "op_of_task": None,
                "par_of_op": None, "src_mask_ops": None, "edges": None,
                **dict.fromkeys(_DRILL_KEYS, None),
                **dict.fromkeys(AUTOSCALE_KEYS, None)}

# resiliency-config vmap axis: the traced failover/queue/selectivity
# leaves vary per grid row (deployment-drill leaves included — upgrade
# policy is part of the config); placement and routing constants are
# broadcast
_PA_CFG_AXES = {"qcap": 0, "src_row": None, "cap_base": None, "sel": 0,
                "dt": None, "task_host": None, "task_region": None,
                "detect": 0, "restart_region": 0, "restart_single": 0,
                "mode_single": 0, "mode_region": 0, "mode_hot": 0,
                "standby_switch": 0, "standby_stale": 0,
                "restore_base": 0, "replay_rate": 0, "lazy_extra": 0,
                "job_of_task": None, "op_of_task": None,
                "par_of_op": None, "src_mask_ops": None, "edges": None,
                **dict.fromkeys(_DRILL_KEYS, 0),
                **dict.fromkeys(AUTOSCALE_KEYS, 0)}


def get_cached_run_fns(desc: TickDesc):
    """(jitted run, jitted vmapped run) for a static plan descriptor.

    One entry — hence one trace per call signature — per plan *shape*;
    float parameters (rates, selectivities, restart times, queue caps,
    failover mode masks, …) are traced arguments, so sweeping them never
    re-traces. The state argument is donated: arena state buffers are
    consumed in place every call."""
    def _build():
        run = _build_run(desc)
        return (jax.jit(run, donate_argnums=(1,)),
                jax.jit(jax.vmap(run, in_axes=(None, 0, _XS_AXES)),
                        donate_argnums=(1,)))
    return _cache_get(_FN_CACHE, desc, _build)


def get_sharded_run_fn(desc: TickDesc, n_shards: int):
    """Device-sharded batch run fn (flat seed axis, a multiple of
    `n_shards`) through `repro.dist.sharding.sharded_seed_fn`. Cached
    per (plan shape, shard count)."""
    return _cache_get(
        _SHARD_CACHE, (desc, n_shards),
        lambda: sharded_seed_fn(_build_run(desc), xs_axes=_XS_AXES,
                                n_shards=n_shards))


def get_cached_mix_fn(desc: TickDesc):
    """Doubly-vmapped run fn: outer axis over job-mix configs (per-task
    source-rate rows), inner axis over chaos seeds — one trace sweeps an
    (M, S) grid of mix × scenario in a single device call."""
    return _cache_get(
        _MIX_CACHE, desc,
        lambda: jax.jit(
            jax.vmap(jax.vmap(_build_run(desc),
                              in_axes=(None, 0, _XS_AXES)),
                     in_axes=(_PA_MIX_AXES, None, None))))


def _cfg_xs_axes(shared_kills: bool) -> dict:
    # checkpoint-free grids share one (S, T, H) kill tensor across every
    # config (kill draws are failover-independent), so the config axis
    # broadcasts it instead of materializing C copies on device;
    # ckpt-bearing grids carry genuinely per-config kills (axis 0).
    # bfac/ckage always carry the config axis (config brownout ramps
    # compose into the factor; ckpt cadence sets the age curve); the MQ
    # gate is seed-only and broadcasts across configs; rfac carries the
    # config axis (config traffic patterns compose into the rate curve).
    return {"t": None, "kills": None if shared_kills else 0, "ckpt": 0,
            "bfac": 0, "gate": None, "ckage": 0, "rfac": 0}


def get_cached_config_fn(desc: TickDesc, shared_kills: bool = False):
    """Doubly-vmapped run fn for resiliency-config grids: outer axis over
    configs (per-task detect/restart/mode/qcap/sel leaves + per-config
    ckpt schedules), inner axis over chaos seeds — a (C, S) grid of
    config × scenario in one device call, one trace per grid shape.
    `shared_kills` selects the broadcast-kills variant (see
    `_cfg_xs_axes`)."""
    return _cache_get(
        _CFG_CACHE, (desc, shared_kills),
        lambda: jax.jit(
            jax.vmap(jax.vmap(_build_run(desc),
                              in_axes=(None, 0, _XS_AXES)),
                     in_axes=(_PA_CFG_AXES, None,
                              _cfg_xs_axes(shared_kills)))))


def get_sharded_config_fn(desc: TickDesc, n_shards: int,
                          shared_kills: bool = False):
    """Device-sharded twin of `get_cached_config_fn`: the flat seed axis
    of the (C, S) grid (a multiple of `n_shards`) splits across local
    devices through `repro.dist.sharding.sharded_grid_fn`, the config
    axis rides inside each shard. Cached per (plan shape, shard count,
    kills layout)."""
    def _build():
        seed_axes = {"t": None, "kills": 0 if shared_kills else 1,
                     "ckpt": None, "bfac": 1, "gate": 0, "ckage": 1,
                     "rfac": 1}
        return sharded_grid_fn(
            _build_run(desc), pa_axes=_PA_CFG_AXES, xs_axes=_XS_AXES,
            cfg_xs_axes=_cfg_xs_axes(shared_kills),
            seed_axes=seed_axes, n_shards=n_shards)
    return _cache_get(_CFG_SHARD_CACHE, (desc, n_shards, shared_kills),
                      _build)


def get_cached_config_mix_fn(desc: TickDesc, shared_kills: bool = False):
    """Triply-vmapped run fn: mixes × configs × seeds in one call (the
    mix axis varies only the source-rate row on top of the config
    axes)."""
    mix_top = dict.fromkeys(_PA_CFG_AXES, None)
    mix_top["src_row"] = 0

    def _build():
        run = _build_run(desc)
        return jax.jit(
            jax.vmap(
                jax.vmap(jax.vmap(run, in_axes=(None, 0, _XS_AXES)),
                         in_axes=(_PA_CFG_AXES, None,
                                  _cfg_xs_axes(shared_kills))),
                in_axes=(mix_top, None, None)))
    return _cache_get(_CFG_MIX_CACHE, (desc, shared_kills), _build)


# ----------------------------------------------------------------------
# lowering: LogicalGraph + configs → static desc + traced param arrays
# ----------------------------------------------------------------------
class _Lowered:
    def __init__(self, graph: LogicalGraph | PackedArena, *, n_hosts: int,
                 dt: float,
                 queue_cap: float, failover, ckpt, seed: int,
                 phase_mode: str = "auto", seed_width: int = 1,
                 upgrade: UpgradeConfig | None = None,
                 upgrade_spec=None,
                 autoscale: AutoscaleConfig | None = None):
        self.arena = graph if isinstance(graph, PackedArena) else None
        if self.arena is not None:
            graph = self.arena.graph
            dt, queue_cap = self.arena.dt, self.arena.queue_cap
        self.graph = graph
        self.dt = dt
        self.failover = failover
        self.ckpt_cfg = ckpt
        self.phys: PhysicalGraph = (
            self.arena.phys if self.arena is not None
            else expand(graph, n_hosts=n_hosts, seed=seed))
        self.plan = (self.arena.plan if self.arena is not None
                     else build_plan(graph, dt, queue_cap))
        self.task_host = np.array([tk.host for tk in self.phys.tasks])
        self.task_region = np.array(
            [self.phys.task_region[tk.task_id] for tk in self.phys.tasks])
        self.n_hosts = (self.arena.n_hosts if self.arena is not None
                        else int(self.task_host.max()) + 1)
        self.n_regions = len(self.phys.regions)
        self.n_jobs = self.arena.n_jobs if self.arena is not None else 1
        self.job_of_task = (self.arena.job_of_task
                            if self.arena is not None else None)
        self.job_of_op = (self.arena.job_of_op if self.arena is not None
                          else np.zeros(len(self.plan.ops), dtype=int))
        # job-local placements (per-job ChaosSpec lists draw in these)
        self.task_local_host = (
            np.concatenate([j.local_host for j in self.arena.jobs])
            if self.arena is not None else None)

        plan = self.plan
        n_tasks = plan.n_tasks
        src_row = np.zeros(n_tasks)
        cap_base = np.zeros(n_tasks)
        sel = np.zeros(len(plan.ops))
        for oi, p in enumerate(plan.ops):
            sel[oi] = p.selectivity
            if p.is_source:
                src_row[p.lo:p.hi] = p.src_row
            else:
                cap_base[p.lo:p.hi] = p.service_rate * dt

        # per-task failover vectors (per-job config lists lower here)
        codes, det, rst_s, rst_r, fx = per_task_failover(
            failover, n_tasks, self.job_of_task)
        self.fo_codes = codes
        self.fo_detect, self.fo_rs, self.fo_rr = det, rst_s, rst_r
        self.fo_extras = fx
        self.fo_lazy = lazy_ready_extra(fx["stagger"], self.task_region,
                                        self.job_of_task)
        if isinstance(ckpt, (list, tuple)) and (
                self.arena is None or len(list(ckpt)) != self.n_jobs):
            raise ValueError("per-job ckpt list needs a packed arena "
                             "with one entry per job")

        self.tensor = lower_tensor_plan(plan, self.job_of_op,
                                        mode=phase_mode,
                                        seed_width=seed_width)
        required = os.environ.get("REPRO_REQUIRE_PHASE_MODE")
        if required and self.tensor.mode != required:
            raise RuntimeError(
                f"REPRO_REQUIRE_PHASE_MODE={required} but the lowering "
                f"selected the {self.tensor.mode} path (phase_mode="
                f"{phase_mode!r}) — refusing to fall back silently")
        self.desc = TickDesc(self.tensor, self.n_regions)
        # deployment drill: lowered ONCE into traced per-task leaves
        # (inert zeros/infs without an upgrade — exact arithmetic no-ops
        # in the tick, so drill-free runs are numerically untouched)
        sel_task = np.zeros(n_tasks)
        for p in plan.ops:
            if not p.is_source:
                sel_task[p.lo:p.hi] = p.selectivity
        self._sel_task = sel_task
        self._drill = lower_upgrade(
            upgrade, upgrade_spec, n_tasks=n_tasks,
            job_of_task=self.job_of_task, task_region=self.task_region,
            dt=self.dt, base_failover=(codes, det, rst_s, rst_r, fx),
            base_ckpt=ckpt, sel_task=sel_task)
        # in-trace autoscaler: lowered ONCE into traced per-task leaves
        # (inert no-op values without a config — see AUTOSCALE_KEYS)
        self._auto = lower_autoscale(
            autoscale, n_tasks=n_tasks, dt=self.dt,
            is_src_task=self.tensor.is_src_task)
        self.arrays = self._params(plan.qcap, sel, det, rst_s, rst_r,
                                   codes, src_row, cap_base)
        self.op_names = [p.name for p in plan.ops]
        self._src_row, self._cap_base, self._sel = src_row, cap_base, sel

    def _params(self, qcap, sel, det, rst_s, rst_r, codes, src_row=None,
                cap_base=None, fx=None, drill=None,
                autoscale=None) -> dict:
        """Traced-parameter pytree for one resiliency configuration —
        `run_config_batch` stacks one of these per grid row. `drill`
        overrides the lowered deployment-drill leaves (per-config
        `UpgradeConfig` rows), `autoscale` the lowered autoscaler
        leaves (per-config `AutoscaleConfig` rows); default is this
        lowering's own."""
        if fx is None:
            fx = self.fo_extras
            lazy = self.fo_lazy
        else:
            lazy = lazy_ready_extra(fx["stagger"], self.task_region,
                                    self.job_of_task)
        jot = (self.job_of_task if self.job_of_task is not None
               else np.zeros(self.plan.n_tasks, dtype=int))
        return {
            "qcap": np.asarray(qcap, float),
            "src_row": (src_row if src_row is not None
                        else self._src_row),
            "cap_base": (cap_base if cap_base is not None
                         else self._cap_base),
            "sel": np.asarray(sel, float),
            "dt": np.float64(self.dt),
            "task_host": self.task_host.astype(np.int32),
            "task_region": self.task_region.astype(np.int32),
            "detect": np.asarray(det, float),
            "restart_region": np.asarray(rst_r, float),
            "restart_single": np.asarray(rst_s, float),
            "mode_single": (codes == 2).astype(np.float64),
            "mode_region": (codes == 1).astype(np.float64),
            "mode_hot": (codes == 3).astype(np.float64),
            "standby_switch": np.asarray(fx["switch"], float),
            "standby_stale": np.asarray(fx["stale"], float),
            "restore_base": np.asarray(fx["restore_base"], float),
            "replay_rate": np.asarray(fx["replay_rate"], float),
            "lazy_extra": np.asarray(lazy, float),
            "job_of_task": np.asarray(jot, np.int32),
            "op_of_task": self.tensor.op_of_task.astype(np.int32),
            "par_of_op": np.asarray(self.tensor.par_of_op, float),
            "src_mask_ops": np.asarray(self.tensor.src_mask_ops, float),
            # per-phase traced routing parameters: share/mass tables in
            # dense mode, the full pow2-bucketed index/mask sets in
            # compact mode (the trace key carries only the bucket sizes)
            "edges": [ph.traced()
                      if self.tensor.mode == "compact"
                      else {"share": ph.share, "mass": ph.mass}
                      for ph in self.tensor.phases],
            **(drill if drill is not None else self._drill),
            **(autoscale if autoscale is not None else self._auto),
        }

    # ------------------------------------------------------------------
    def _ckpt_timeline_kw(self, ckpt) -> dict:
        if ckpt is None:
            return dict(ckpt_interval_s=None)
        if isinstance(ckpt, CheckpointConfig):
            return dict(ckpt_interval_s=ckpt.interval_s,
                        ckpt_mode=ckpt.mode, ckpt_upload_s=ckpt.upload_s,
                        ckpt_retry=ckpt.retry_failed_region)
        cfgs = list(ckpt)
        return dict(
            ckpt_interval_s=[c.interval_s if c else None for c in cfgs],
            ckpt_mode=[c.mode if c else "region" for c in cfgs],
            ckpt_upload_s=[c.upload_s if c else 4.0 for c in cfgs],
            ckpt_retry=[c.retry_failed_region if c else True
                        for c in cfgs])

    def timeline(self, spec: ChaosSpec, n_ticks: int, *,
                 fo_codes=None, detect=None, rst_s=None, rst_r=None,
                 extras=None, lazy=None,
                 ckpt="default") -> ChaosTimeline:
        """Pregenerate one seed's chaos timeline, optionally under
        override failover/ckpt parameters (the config-axis path).

        `spec` may be a per-job `ChaosSpec` list (packed arenas): each
        job then runs its own chaos process in its local host domain,
        lifted through the job's host map
        (`core.chaos.build_perjob_chaos_timeline`)."""
        ex = extras if extras is not None else self.fo_extras
        ex_kw = dict(
            standby_switch_s=ex["switch"],
            standby_staleness_s=ex["stale"],
            restore_base_s=ex["restore_base"],
            replay_rate=ex["replay_rate"],
            lazy_extra_s=(lazy if lazy is not None else
                          (self.fo_lazy if extras is None else
                           lazy_ready_extra(ex["stagger"],
                                            self.task_region,
                                            self.job_of_task))))
        if isinstance(spec, (list, tuple)):
            if self.arena is None:
                raise ValueError("a per-job chaos list needs a packed "
                                 "arena with one entry per job")
            specs = [sp.spec if isinstance(sp, ChaosEngine)
                     else (sp or ChaosSpec()) for sp in spec]
            if len(specs) != self.n_jobs:
                raise ValueError(f"per-job chaos list must have one "
                                 f"entry per job ({len(specs)} != "
                                 f"{self.n_jobs})")
            return build_perjob_chaos_timeline(
                specs, n_ticks=n_ticks, dt=self.dt, n_hosts=self.n_hosts,
                task_host=self.task_host,
                job_hosts=[j.hosts for j in self.arena.jobs],
                task_local_host=self.task_local_host,
                job_of_task=self.job_of_task,
                task_region=self.task_region, regions=self.phys.regions,
                failover_mode=(fo_codes if fo_codes is not None
                               else self.fo_codes),
                detect_s=(detect if detect is not None
                          else self.fo_detect),
                region_restart_s=(rst_r if rst_r is not None
                                  else self.fo_rr),
                single_restart_s=(rst_s if rst_s is not None
                                  else self.fo_rs),
                **ex_kw,
                **self._ckpt_timeline_kw(self.ckpt_cfg
                                         if ckpt == "default" else ckpt))
        return build_chaos_timeline(
            spec, n_ticks=n_ticks, dt=self.dt, n_hosts=self.n_hosts,
            task_host=self.task_host, task_region=self.task_region,
            regions=self.phys.regions,
            failover_mode=(fo_codes if fo_codes is not None
                           else self.fo_codes),
            detect_s=(detect if detect is not None else self.fo_detect),
            region_restart_s=(rst_r if rst_r is not None else self.fo_rr),
            single_restart_s=(rst_s if rst_s is not None else self.fo_rs),
            job_of_task=self.job_of_task,
            **ex_kw,
            **self._ckpt_timeline_kw(self.ckpt_cfg if ckpt == "default"
                                     else ckpt))

    def state0(self, tl: ChaosTimeline,
               task_speed_override: dict[int, float] | None
               ) -> EngineState:
        n_tasks = self.plan.n_tasks
        speed = np.ones(n_tasks)
        if task_speed_override:
            for tid, s in task_speed_override.items():
                speed[tid] = s
        speed *= tl.task_speed
        return EngineState(
            queue=np.zeros(n_tasks), down_until=np.zeros(n_tasks),
            speed=speed, ckpt_epoch=np.int32(0),
            emitted=np.zeros(self.n_jobs), dropped=np.zeros(self.n_jobs),
            up_until=np.zeros(n_tasks), rb_t=np.float64(np.inf),
            dacc=np.float64(0.0),
            rew=np.zeros(n_tasks), lact=np.full(n_tasks, -1e18),
            dirp=np.zeros(n_tasks), failcnt=np.zeros(n_tasks),
            brk_until=np.zeros(n_tasks), used=np.float64(0.0),
            flip_acc=np.float64(0.0), thrash_t=np.float64(np.inf),
            nact=np.float64(0.0), rsec=np.float64(0.0))

    def event_curves(self, spec, tl: ChaosTimeline,
                     cfg_ramps=(), cfg_traffic=((), ())) -> tuple:
        """Deterministic per-tick external-event tensors for one seed:
        ``bfac`` storage-brownout factor, ``gate`` source gate (MQ
        outages × coordinator leader-loss windows — the gate is 0 where
        the MQ is down OR a ZK and an HDFS outage overlap, matching
        `ChaosEngine.leader_available`), ``ckage`` checkpoint age and
        ``rfac`` traffic-rate factor (diurnal curves × flash-crowd
        ramps, `core.chaos.traffic_curve`) — each (n_ticks, n_jobs),
        gathered per task through ``pa["job_of_task"]`` inside the
        tick. Config-level brownout ramps / traffic patterns compose
        by tuple concatenation (so the factors are op-identical to the
        numpy engines')."""
        ts = tl.ts
        cfg_diurnal, cfg_flash = (tuple(cfg_traffic[0]),
                                  tuple(cfg_traffic[1]))
        if isinstance(spec, (list, tuple)):
            specs = [sp.spec if isinstance(sp, ChaosEngine)
                     else (sp or ChaosSpec()) for sp in spec]
            bfac = np.stack(
                [brownout_curve(tuple(sp.brownout_at) + tuple(cfg_ramps),
                                ts) for sp in specs], axis=1)
            gate = np.stack(
                [mq_gate_curve(sp.mq_down, ts)
                 * coordinator_gate_curve(sp.zk_down, sp.hdfs_down, ts)
                 for sp in specs], axis=1)
            rfac = np.stack(
                [traffic_curve(tuple(sp.diurnal) + cfg_diurnal,
                               tuple(sp.flash_at) + cfg_flash, ts,
                               phase_s=sp.rate_phase_s)
                 for sp in specs], axis=1)
        else:
            bf = brownout_curve(tuple(spec.brownout_at)
                                + tuple(cfg_ramps), ts)
            gt = (mq_gate_curve(spec.mq_down, ts)
                  * coordinator_gate_curve(spec.zk_down, spec.hdfs_down,
                                           ts))
            rf = traffic_curve(tuple(spec.diurnal) + cfg_diurnal,
                               tuple(spec.flash_at) + cfg_flash, ts,
                               phase_s=spec.rate_phase_s)
            bfac = np.repeat(bf[:, None], self.n_jobs, axis=1)
            gate = np.repeat(gt[:, None], self.n_jobs, axis=1)
            rfac = np.repeat(rf[:, None], self.n_jobs, axis=1)
        ok = (tl.ckpt_ok_by_job if tl.ckpt_ok_by_job is not None
              else tl.ckpt_ok)
        ckage = ckpt_age_curve(ts, ok, self.n_jobs)
        return bfac, gate, ckage, rfac

    def prepare(self, spec: ChaosSpec, n_ticks: int,
                task_speed_override: dict[int, float] | None = None
                ) -> tuple[EngineState, dict, ChaosTimeline]:
        """Pregenerate one seed's chaos timeline → (state0, scan xs)."""
        tl = self.timeline(spec, n_ticks)
        state = self.state0(tl, task_speed_override)
        bfac, gate, ckage, rfac = self.event_curves(spec, tl)
        xs = {"t": tl.ts, "kills": tl.kills.astype(np.float64),
              "ckpt": tl.ckpt_at, "bfac": bfac, "gate": gate,
              "ckage": ckage, "rfac": rfac}
        return state, xs, tl


# ----------------------------------------------------------------------
# metrics façades (same read API as streams.engine.EngineMetrics)
# ----------------------------------------------------------------------
class JaxEngineMetrics:
    def __init__(self, op_names, t, lag, qps, backlog, emitted, dropped,
                 timeline: ChaosTimeline, ckpt_epoch: int | None = None,
                 rollback_t: float = np.inf, thrash_t: float = np.inf,
                 n_rescale: float = 0.0, resource_s: float = 0.0):
        self.t = t
        self.source_lag = lag
        self.qps = {n: qps[:, j] for j, n in enumerate(op_names)}
        self.backlog = {n: backlog[:, j] for j, n in enumerate(op_names)}
        # emitted/dropped arrive as (n_jobs,) segment totals
        self.emitted_by_job = np.atleast_1d(np.asarray(emitted, float))
        self.dropped_by_job = np.atleast_1d(np.asarray(dropped, float))
        self.emitted = float(self.emitted_by_job.sum())
        self.dropped = float(self.dropped_by_job.sum())
        self.ckpt_attempts = timeline.ckpt_attempts
        self.ckpt_success = timeline.ckpt_success
        self.ckpt_failed = timeline.ckpt_failed
        self.ckpt_by_job = timeline.ckpt_by_job
        # device-side attempt counter (scan state) — must agree with the
        # host-side timeline; pinned in tests/test_jax_engine.py
        self.ckpt_epoch = (timeline.ckpt_attempts if ckpt_epoch is None
                           else int(ckpt_epoch))
        self.recoveries = timeline.recoveries
        self.timeline = timeline
        # deployment drill: tick time the in-trace auto-rollback fired
        # (+inf when no drill ran or the canary held)
        self.rollback_t = float(rollback_t)
        # in-trace autoscaler: thrash-guard latch time (+inf = never
        # fired), scale-action count, resource-seconds integral
        self.thrash_t = float(thrash_t)
        self.n_rescale = float(n_rescale)
        self.resource_s = float(resource_s)


def backlog_series(backlog: np.ndarray, src_cols) -> tuple[np.ndarray,
                                                          np.ndarray]:
    """The per-tick backlog series a summary reads, from a ``(..., T,
    n_ops)`` per-op history on the host: the total over every op, and
    the downstream backlog over every op that is not among `src_cols`
    (the plan's source columns)."""
    down = np.setdiff1d(np.arange(backlog.shape[-1]), src_cols)
    return backlog.sum(axis=-1), backlog[..., down].sum(axis=-1)


@jax.jit
def device_backlog_series(backlog, down_mask):
    """`backlog_series` on the device, over a ``(..., T, n_ops)``
    history still there: `down_mask` is the ``(n_ops,)`` mask of
    non-source columns. A grid pass's history then reaches the host as
    two ``(..., T)`` series instead of per-op rows."""
    return (backlog.sum(axis=-1),
            jnp.where(down_mask, backlog, 0.0).sum(axis=-1))


class JaxBatchMetrics:
    """Stacked metrics of a vmapped seed batch; `row(i)` is identical to
    a standalone single-seed run (pinned in tests/test_jax_engine.py).

    `backlog_total` and `down_backlog` are the ``(S, n_ticks)`` series
    of `backlog_series`, which is all a summary reads of the backlog. A
    series-only batch (`SummaryGridPlan`) carries them without the
    per-op `qps` / `backlog` history, which are then None."""

    def __init__(self, op_names, t, lag, qps, backlog, emitted, dropped,
                 timelines, ckpt_epoch=None, jobs=None, rollback_t=None,
                 thrash_t=None, n_rescale=None, resource_s=None, *,
                 backlog_total, down_backlog):
        self.op_names = list(op_names)
        self.t = t                     # (n_ticks,)
        self.source_lag = lag          # (S, n_ticks)
        self.qps = qps                 # (S, n_ticks, n_ops) or None
        self.backlog = backlog         # (S, n_ticks, n_ops) or None
        self.backlog_total = backlog_total    # (S, n_ticks)
        self.down_backlog = down_backlog      # (S, n_ticks)
        emitted = np.asarray(emitted, float)
        dropped = np.asarray(dropped, float)
        if emitted.ndim == 1:          # legacy (S,) scalar-per-seed form
            emitted, dropped = emitted[:, None], dropped[:, None]
        self.emitted_by_job = emitted  # (S, n_jobs)
        self.dropped_by_job = dropped  # (S, n_jobs)
        self.emitted = emitted.sum(axis=-1)   # (S,)
        self.dropped = dropped.sum(axis=-1)   # (S,)
        self.ckpt_epoch = ckpt_epoch   # (S,) device-side attempt counter
        # (S,) drill auto-rollback fire times (+inf = never fired)
        self.rollback_t = (np.asarray(rollback_t, float)
                           if rollback_t is not None else None)
        # (S,) autoscaler surfaces: thrash-guard latch times, scale
        # action counts, resource-seconds integrals
        self.thrash_t = (np.asarray(thrash_t, float)
                         if thrash_t is not None else None)
        self.n_rescale = (np.asarray(n_rescale, float)
                          if n_rescale is not None else None)
        self.resource_s = (np.asarray(resource_s, float)
                           if resource_s is not None else None)
        self.timelines = list(timelines)
        self.jobs = list(jobs) if jobs is not None else None
        self.ckpt_attempts = np.array([tl.ckpt_attempts for tl in timelines])
        self.ckpt_success = np.array([tl.ckpt_success for tl in timelines])
        self.ckpt_failed = np.array([tl.ckpt_failed for tl in timelines])
        self.recoveries = [tl.recoveries for tl in timelines]

    def __len__(self) -> int:
        return len(self.timelines)

    def _require_history(self, what: str) -> None:
        if self.backlog is None:
            raise ValueError(
                f"{what} needs per-op histories, and this batch carries "
                "only the per-tick backlog series (a summary sweep's "
                "copy); run_config_batch / run_batch return full rows")

    def row(self, i: int) -> JaxEngineMetrics:
        self._require_history("row()")
        return JaxEngineMetrics(self.op_names, self.t, self.source_lag[i],
                                self.qps[i], self.backlog[i],
                                self.emitted_by_job[i],
                                self.dropped_by_job[i],
                                self.timelines[i],
                                ckpt_epoch=(self.ckpt_epoch[i]
                                            if self.ckpt_epoch is not None
                                            else None),
                                rollback_t=(self.rollback_t[i]
                                            if self.rollback_t is not None
                                            else np.inf),
                                thrash_t=(self.thrash_t[i]
                                          if self.thrash_t is not None
                                          else np.inf),
                                n_rescale=(self.n_rescale[i]
                                           if self.n_rescale is not None
                                           else 0.0),
                                resource_s=(self.resource_s[i]
                                            if self.resource_s is not None
                                            else 0.0))

    def job_view(self, job: JobSlice) -> "JaxBatchMetrics":
        """Per-job slice of a packed-arena batch: the job's metric columns
        under their original (un-namespaced) op names, source lag summed
        over the job's own sources, per-job emitted/dropped segments, and
        recovery events filtered to the job — shaped exactly like a
        single-job batch so `chaos_sweep.summarize` works per job."""
        self._require_history("job_view()")
        cols = np.asarray(job.op_cols)
        lag = self.backlog[:, :, np.asarray(job.src_cols)].sum(axis=-1)
        backlog = self.backlog[:, :, cols]
        total, down = backlog_series(
            backlog, np.searchsorted(cols, job.src_cols))
        j = job.index
        tls = [dataclasses.replace(tl, recoveries=tl.recoveries.for_job(j))
               for tl in self.timelines]
        return JaxBatchMetrics(
            job.op_names, self.t, lag, self.qps[:, :, cols], backlog,
            self.emitted_by_job[:, j:j + 1],
            self.dropped_by_job[:, j:j + 1], tls,
            ckpt_epoch=self.ckpt_epoch, rollback_t=self.rollback_t,
            thrash_t=self.thrash_t, n_rescale=self.n_rescale,
            resource_s=self.resource_s, backlog_total=total,
            down_backlog=down)


# ----------------------------------------------------------------------
# public API
# ----------------------------------------------------------------------
class JaxStreamEngine:
    """Drop-in (single-seed) twin of `StreamEngine`: same constructor
    signature, `run(duration_s)` returns `JaxEngineMetrics` with the
    numpy engine's metric names/values (1e-5). `failover` / `ckpt` may be
    per-job config lists for packed arenas, exactly as in the numpy
    engine."""

    def __init__(self, graph: LogicalGraph | PackedArena, *,
                 n_hosts: int = 8,
                 dt: float = 0.5, queue_cap: float = 256.0,
                 chaos: ChaosEngine | ChaosSpec | None = None,
                 failover=None,
                 ckpt=None,
                 task_speed_override: dict[int, float] | None = None,
                 seed: int = 0, phase_mode: str = "auto",
                 upgrade: UpgradeConfig | None = None,
                 autoscale: AutoscaleConfig | None = None):
        if isinstance(chaos, ChaosEngine):
            chaos = chaos.spec
        elif isinstance(chaos, (list, tuple)):
            chaos = [c.spec if isinstance(c, ChaosEngine)
                     else (c or ChaosSpec()) for c in chaos]
        self.spec = chaos if chaos is not None else ChaosSpec()
        self.g = graph.graph if isinstance(graph, PackedArena) else graph
        if isinstance(graph, PackedArena):
            dt = graph.dt
        self.dt = dt
        self._override = task_speed_override
        self._low = _Lowered(graph, n_hosts=n_hosts, dt=dt,
                             queue_cap=queue_cap, failover=failover,
                             ckpt=ckpt, seed=seed, phase_mode=phase_mode,
                             upgrade=upgrade, upgrade_spec=self.spec,
                             autoscale=autoscale)
        self.metrics: JaxEngineMetrics | None = None

    @property
    def lowered(self) -> _Lowered:
        return self._low

    def run(self, duration_s: float) -> JaxEngineMetrics:
        low = self._low
        n_ticks = int(round(duration_s / self.dt))
        state, xs, tl = low.prepare(self.spec, n_ticks, self._override)
        run_fn, _ = get_cached_run_fns(low.desc)
        with jax.enable_x64(True):
            final, ys = run_fn(low.arrays, state, xs)
            qps = np.asarray(ys["qps"])
            backlog = np.asarray(ys["backlog"])
            lag = np.asarray(ys["lag"])
            emitted = np.asarray(final.emitted)
            dropped = np.asarray(final.dropped)
            ckpt_epoch = int(final.ckpt_epoch)
            rollback_t = float(final.rb_t)
            thrash_t = float(final.thrash_t)
            n_rescale = float(final.nact)
            resource_s = float(final.rsec)
        self.metrics = JaxEngineMetrics(low.op_names, tl.ts, lag, qps,
                                        backlog, emitted, dropped, tl,
                                        ckpt_epoch=ckpt_epoch,
                                        rollback_t=rollback_t,
                                        thrash_t=thrash_t,
                                        n_rescale=n_rescale,
                                        resource_s=resource_s)
        return self.metrics


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


def _pad_rows(a: np.ndarray, target: int, axis: int = 0) -> np.ndarray:
    """Pad `axis` to `target` by replicating its first slice (pad rows
    simulate a real scenario, so no NaNs/branches — they are sliced off
    before any aggregate sees them)."""
    if a.shape[axis] == target:
        return a
    first = np.take(a, [0], axis=axis)
    shape = list(a.shape)
    shape[axis] = target - a.shape[axis]
    return np.concatenate([a, np.broadcast_to(first, shape)], axis=axis)


def _pad_batch(batch_state: EngineState, xs: dict, n_seeds: int,
               pad_seeds: bool, n_shards: int = 1,
               seed_axes: dict | None = None):
    """Pad the seed axis to the next power of two (and to a multiple of
    the shard count) — the retrace-free batching contract shared by
    `run_batch`, `run_mix_batch` and `run_config_batch`. `seed_axes`
    names the xs leaves carrying a seed axis (and which axis it is)."""
    if seed_axes is None:
        seed_axes = {"kills": 0, "bfac": 0, "gate": 0, "ckage": 0,
                     "rfac": 0}
    target = _next_pow2(n_seeds) if pad_seeds else n_seeds
    if target % n_shards:
        target = n_shards * -(-target // n_shards)
    if target != n_seeds:
        batch_state = EngineState(*(_pad_rows(getattr(batch_state, f),
                                              target)
                                    for f in EngineState._fields))
        xs = dict(xs, **{k: _pad_rows(np.asarray(xs[k]), target, axis=ax)
                         for k, ax in seed_axes.items()})
    return batch_state, xs


def _prep_batch(low: "_Lowered", specs, n_ticks: int, task_speed_override):
    prepped = [low.prepare(spec, n_ticks, task_speed_override)
               for spec in specs]
    states = [p[0] for p in prepped]
    tls = [p[2] for p in prepped]
    batch_state = EngineState(*(np.stack([getattr(s, f) for s in states])
                                for f in EngineState._fields))
    xs = {"t": prepped[0][1]["t"],                 # identical across seeds
          "kills": np.stack([p[1]["kills"] for p in prepped]),
          "ckpt": prepped[0][1]["ckpt"],           # static schedule
          # per-seed external-event tensors (ckpt ages vary with each
          # seed's success draws even under a static attempt schedule)
          "bfac": np.stack([p[1]["bfac"] for p in prepped]),
          "gate": np.stack([p[1]["gate"] for p in prepped]),
          "ckage": np.stack([p[1]["ckage"] for p in prepped]),
          "rfac": np.stack([p[1]["rfac"] for p in prepped])}
    return batch_state, xs, tls


def perjob_sweep_seed(base_seed: int, sweep_seed: int, job: int) -> int:
    """Collision-free derived seed for job `job` of sweep seed
    `sweep_seed` under a per-job base spec (SeedSequence entropy mix —
    distinct cells cannot share a stream)."""
    return int(np.random.SeedSequence(
        (int(base_seed), int(sweep_seed), int(job))).generate_state(1)[0])


def _as_specs(seeds, base_spec) -> list:
    """Merge sweep seeds into the base spec. A per-job `base_spec` LIST
    (packed arenas) yields one per-job spec list per seed: job j of
    sweep seed s draws from ``perjob_sweep_seed(base[j].seed, s, j)`` —
    a `np.random.SeedSequence` mix of (base seed, sweep seed, job), so
    every (seed, job) cell gets a distinct, reproducible stream even
    when base seeds are heterogeneous (plain ``base.seed + s*K + j``
    arithmetic can collide across cells). Entries of `seeds` that are
    already specs (or per-job spec lists) pass through untouched."""
    if isinstance(base_spec, (list, tuple)):
        base = [b or ChaosSpec() for b in base_spec]
        return [[dataclasses.replace(b, seed=perjob_sweep_seed(
                    b.seed, int(s), j)) for j, b in enumerate(base)]
                if isinstance(s, (int, np.integer)) else s
                for s in seeds]
    return [dataclasses.replace(base_spec or ChaosSpec(), seed=int(s))
            if isinstance(s, (int, np.integer)) else s for s in seeds]


class ChunkResult:
    """One seed-chunk's worth of a chunked run: the half-open seed range
    ``[seed_lo, seed_hi)``, its metrics (`JaxBatchMetrics` for seed
    plans; a per-config list — or mixes×configs nest — for grid plans),
    and its times from its spans: host prep (``prep_s``), the device
    wait alone (``device_s``), the device→host copy of the history and
    final state (``fetch_s``), the bytes that copy fetched
    (``history_bytes``) and the destination entries the pass routed
    (``route_entries``, the plan's `route_entries`)."""

    __slots__ = ("seed_lo", "seed_hi", "batches", "prep_s", "device_s",
                 "fetch_s", "history_bytes", "route_entries")

    def __init__(self, seed_lo, seed_hi, batches, prep_s, device_s,
                 fetch_s, history_bytes, route_entries):
        self.seed_lo = seed_lo
        self.seed_hi = seed_hi
        self.batches = batches
        self.prep_s = prep_s
        self.device_s = device_s
        self.fetch_s = fetch_s
        self.history_bytes = history_bytes
        self.route_entries = route_entries


def run_chunks(plan, chunk_size: int | None = None, on_chunk=None,
               spans: SpanLog | None = None) -> list[ChunkResult]:
    """Execute a `SeedBatchPlan`/`ConfigGridPlan` in seed chunks on a
    double-buffered pipeline: host-side timeline prep for chunk k+1 runs
    on the caller thread WHILE chunk k computes on a one-slot device
    lane (XLA releases the GIL for the blocking device call, so the two
    genuinely overlap). `on_chunk` fires with each `ChunkResult` as it
    lands, in seed order — incremental consumers see partial surfaces
    at time-to-first-chunk instead of time-to-last.

    Each chunk records three spans into `spans` (a fresh `SpanLog` when
    None), and its `ChunkResult` times are theirs: ``sweep.prep`` on the
    caller thread (with the rows of the chunk's recovery logs),
    ``sweep.device`` (dispatch and the wait for the result, with the
    pass's routed entries) and ``sweep.fetch`` (the copy to the host,
    with its bytes) on the lane."""
    spans = SpanLog() if spans is None else spans
    n_seeds = plan.n_seeds
    size = n_seeds if not chunk_size else max(1, int(chunk_size))
    bounds = [(lo, min(lo + size, n_seeds))
              for lo in range(0, n_seeds, size)]

    def _run(k, prepped, prep_s):
        entries = plan.route_entries(prepped)
        with spans.span("sweep.device", chunk=k,
                        route_entries=entries) as dev:
            out = plan.dispatch(prepped)
        with spans.span("sweep.fetch", chunk=k) as fetch:
            batches, nbytes = plan.fetch(prepped, out)
            fetch.count(bytes=nbytes)
        return ChunkResult(prepped[0], prepped[1], batches, prep_s,
                           dev.seconds, fetch.seconds, nbytes, entries)

    out: list[ChunkResult] = []

    def _land(fut):
        res = fut.result()
        out.append(res)
        if on_chunk is not None:
            on_chunk(res)

    with ThreadPoolExecutor(max_workers=1) as lane:
        fut = None
        for k, (lo, hi) in enumerate(bounds):
            with spans.span("sweep.prep", chunk=k) as prep:
                prepped = plan.prep_chunk(lo, hi)
                prep.count(recovery_rows=_recovery_rows(prepped[4]))
            if fut is not None:
                _land(fut)          # chunk k lands while k+1 is prepped
            fut = lane.submit(_run, k, prepped, prep.seconds)
        _land(fut)
    return out


def _recovery_rows(tls) -> int:
    """Rows the recovery logs of a chunk's timelines hold: `tls` is a
    list of timelines, or one such list per config."""
    return sum(_recovery_rows(x) if isinstance(x, list)
               else len(x.recoveries) for x in tls)


def _route_entries(low: "_Lowered", n_ticks: int, rows: int,
                   batch_state: EngineState) -> int:
    """Destination entries one device pass routes: Σ over the lowering's
    tick phases of `D`, times ticks, times the pass's scenarios (`rows`
    per seed, over the seed axis as dispatched, padding included)."""
    per_tick = sum(int(ph.D) for ph in low.tensor.phases)
    return per_tick * n_ticks * rows * int(batch_state.emitted.shape[0])


#: final-state leaves a chunk copies back beside its history
_FETCHED_FINAL = ("emitted", "dropped", "ckpt_epoch", "rb_t",
                  "thrash_t", "nact", "rsec")


def _fetch(final, history: dict) -> tuple[dict, int]:
    """Copy one device pass's `history` leaves and fetched final-state
    leaves to the host; returns them by name with the bytes copied."""
    with jax.enable_x64(True):
        host = {k: np.asarray(v) for k, v in history.items()}
        host.update((k, np.asarray(getattr(final, k)))
                    for k in _FETCHED_FINAL)
    return host, sum(a.nbytes for a in host.values())


#: the per-op history a full copy fetches
_HISTORY = ("qps", "backlog", "lag")


def concat_batches(parts: list[JaxBatchMetrics]) -> JaxBatchMetrics:
    """Concatenate per-chunk `JaxBatchMetrics` along the seed axis.

    Every per-seed surface is a plain row stack (no cross-seed
    reductions happen device-side), so the concatenation of chunked
    results is bit-identical to the monolithic batch — pinned by
    tests/test_sweep_service.py."""
    if len(parts) == 1:
        return parts[0]
    first = parts[0]

    def cat(name):
        v = getattr(first, name)
        if v is None:
            return None
        return np.concatenate([np.asarray(getattr(p, name))
                               for p in parts], axis=0)

    return JaxBatchMetrics(
        first.op_names, first.t, cat("source_lag"), cat("qps"),
        cat("backlog"), cat("emitted_by_job"), cat("dropped_by_job"),
        [tl for p in parts for tl in p.timelines],
        ckpt_epoch=cat("ckpt_epoch"), jobs=first.jobs,
        rollback_t=cat("rollback_t"), thrash_t=cat("thrash_t"),
        n_rescale=cat("n_rescale"), resource_s=cat("resource_s"),
        backlog_total=cat("backlog_total"),
        down_backlog=cat("down_backlog"))


class SeedBatchPlan:
    """Chunk-friendly decomposition of `run_batch`: `__init__` does all
    seed-count-independent work (lowering, trace-cache lookup — cache
    traffic lands in `cache_info`), `prep_chunk(lo, hi)` builds the
    host-side tensors for a seed slice, `dispatch` runs one device pass
    and `fetch` copies its result to the host. Driven by
    `run_chunks`."""

    def __init__(self, graph: LogicalGraph | PackedArena, seeds, *,
                 duration_s: float, base_spec: ChaosSpec | None = None,
                 n_hosts: int = 8, dt: float = 0.5,
                 queue_cap: float = 256.0, failover=None, ckpt=None,
                 task_speed_override: dict[int, float] | None = None,
                 seed: int = 0, pad_seeds: bool = True,
                 devices: int | str | None = None,
                 phase_mode: str = "auto",
                 upgrade: UpgradeConfig | None = None,
                 autoscale: AutoscaleConfig | None = None):
        specs = _as_specs(seeds, base_spec)
        if not specs:
            raise ValueError("run_batch requires at least one seed/spec")
        self.specs = specs
        self.n_seeds = len(specs)
        self.low = low = _Lowered(
            graph, n_hosts=n_hosts, dt=dt, queue_cap=queue_cap,
            failover=failover, ckpt=ckpt, seed=seed,
            phase_mode=phase_mode, seed_width=len(specs),
            upgrade=upgrade, upgrade_spec=specs[0], autoscale=autoscale)
        self.n_ticks = int(round(duration_s / low.dt))
        self._override = task_speed_override
        self.pad_seeds = pad_seeds
        self.n_shards = local_shard_count(devices)
        with scoped_cache_stats() as counts:
            if devices is not None:
                self.fn = get_sharded_run_fn(low.desc, self.n_shards)
            else:
                _, self.fn = get_cached_run_fns(low.desc)
        self.cache_info = dict(counts)

    def prep_chunk(self, lo: int, hi: int):
        batch_state, xs, tls = _prep_batch(self.low, self.specs[lo:hi],
                                           self.n_ticks, self._override)
        batch_state, xs = _pad_batch(batch_state, xs, hi - lo,
                                     self.pad_seeds, self.n_shards)
        return (lo, hi, batch_state, xs, tls)

    def dispatch(self, prepped):
        _, _, batch_state, xs, _ = prepped
        with jax.enable_x64(True):
            return jax.block_until_ready(
                self.fn(self.low.arrays, batch_state, xs))

    def route_entries(self, prepped) -> int:
        return _route_entries(self.low, self.n_ticks, 1, prepped[2])

    def fetch(self, prepped, out) -> tuple[JaxBatchMetrics, int]:
        lo, hi, _, _, tls = prepped
        final, ys = out
        h, nbytes = _fetch(final, {k: ys[k] for k in _HISTORY})
        h = {k: v[:hi - lo] for k, v in h.items()}
        low = self.low
        total, down = backlog_series(h["backlog"], low.plan.src_cols)
        return JaxBatchMetrics(low.op_names, tls[0].ts, h["lag"], h["qps"],
                               h["backlog"], h["emitted"], h["dropped"],
                               tls, ckpt_epoch=h["ckpt_epoch"],
                               jobs=(low.arena.jobs
                                     if low.arena is not None else None),
                               rollback_t=h["rb_t"],
                               thrash_t=h["thrash_t"],
                               n_rescale=h["nact"],
                               resource_s=h["rsec"],
                               backlog_total=total,
                               down_backlog=down), nbytes


def run_batch(graph: LogicalGraph | PackedArena, seeds, *,
              duration_s: float,
              base_spec: ChaosSpec | None = None, n_hosts: int = 8,
              dt: float = 0.5, queue_cap: float = 256.0,
              failover=None,
              ckpt=None,
              task_speed_override: dict[int, float] | None = None,
              seed: int = 0, pad_seeds: bool = True,
              devices: int | str | None = None,
              phase_mode: str = "auto",
              upgrade: UpgradeConfig | None = None,
              autoscale: AutoscaleConfig | None = None,
              seed_chunk: int | None = None,
              on_chunk=None
              ) -> JaxBatchMetrics:
    """Run a ``(S,)`` batch of chaos scenarios as ONE vmapped `jit` call
    (one call *per device shard* when `devices` is set).

    `seeds` is a sequence of ints (merged into `base_spec` via
    ``dataclasses.replace(spec, seed=s)``) or of full `ChaosSpec`s.
    `graph` may be a `PackedArena` — the whole co-located fleet then
    simulates in the same device call with per-job metric segments, and
    `failover` / `ckpt` may be per-job config lists.

    Retrace-free batching: with ``pad_seeds=True`` the seed axis is
    padded to the next power of two (and to a multiple of the shard
    count) by replicating scenario 0, so varying S reuses one jit trace
    per pow2 bucket instead of recompiling per batch size; pad rows are
    sliced off before the metrics object is built, so no aggregate ever
    sees them. ``devices`` splits the padded batch across local devices
    through `repro.dist.sharding.sharded_seed_fn` (``"auto"`` =
    all local devices).

    ``seed_chunk`` streams the batch through fixed-size seed chunks on
    the double-buffered `run_chunks` pipeline (host prep for chunk k+1
    overlaps device compute for chunk k); the concatenated result is
    bit-identical to the monolithic call. ``on_chunk`` fires with each
    `ChunkResult` as it lands, carrying the chunk's prep / device /
    fetch times.
    """
    plan = SeedBatchPlan(graph, seeds, duration_s=duration_s,
                         base_spec=base_spec, n_hosts=n_hosts, dt=dt,
                         queue_cap=queue_cap, failover=failover,
                         ckpt=ckpt,
                         task_speed_override=task_speed_override,
                         seed=seed, pad_seeds=pad_seeds, devices=devices,
                         phase_mode=phase_mode, upgrade=upgrade,
                         autoscale=autoscale)
    chunks = run_chunks(plan, seed_chunk, on_chunk)
    return concat_batches([c.batches for c in chunks])


def run_mix_batch(graph: LogicalGraph | PackedArena, mixes, seeds, *,
                  duration_s: float,
                  base_spec: ChaosSpec | None = None, n_hosts: int = 8,
                  dt: float = 0.5, queue_cap: float = 256.0,
                  failover=None,
                  ckpt=None,
                  task_speed_override: dict[int, float] | None = None,
                  seed: int = 0, pad_seeds: bool = True,
                  phase_mode: str = "auto",
                  autoscale: AutoscaleConfig | None = None
                  ) -> list[JaxBatchMetrics]:
    """Sweep an ``(M, S)`` grid of job-mix × chaos-seed scenarios in ONE
    doubly-vmapped `jit` call (the second vmap axis over job-mix configs).

    `mixes` is an ``(M, n_jobs)`` array of per-job source-rate
    multipliers (n_jobs = 1 for a plain graph): row m scales every job
    j's source emission by ``mixes[m, j]``. Rates are traced, not baked,
    so the whole grid shares one trace with the plan shape; chaos
    timelines are rate-independent and shared across mixes. Returns one
    `JaxBatchMetrics` per mix row.
    """
    specs = _as_specs(seeds, base_spec)
    if not specs:
        raise ValueError("run_mix_batch requires at least one seed/spec")
    low = _Lowered(graph, n_hosts=n_hosts, dt=dt, queue_cap=queue_cap,
                   failover=failover, ckpt=ckpt, seed=seed,
                   phase_mode=phase_mode, seed_width=len(specs),
                   autoscale=autoscale)
    mixes = np.atleast_2d(np.asarray(mixes, dtype=np.float64))
    if mixes.shape[1] != low.n_jobs:
        raise ValueError(
            f"mix rows must have one multiplier per job "
            f"({mixes.shape[1]} != {low.n_jobs})")
    n_ticks = int(round(duration_s / low.dt))
    batch_state, xs, tls = _prep_batch(low, specs, n_ticks,
                                       task_speed_override)
    n_seeds = len(specs)
    batch_state, xs = _pad_batch(batch_state, xs, n_seeds, pad_seeds)
    job_of_task = (low.job_of_task if low.job_of_task is not None
                   else np.zeros(low.plan.n_tasks, dtype=int))
    src_rows = low.arrays["src_row"][None, :] * mixes[:, job_of_task]
    pa = dict(low.arrays, src_row=src_rows)
    mix_fn = get_cached_mix_fn(low.desc)
    with jax.enable_x64(True):
        final, ys = mix_fn(pa, batch_state, xs)
        qps = np.asarray(ys["qps"])[:, :n_seeds]
        backlog = np.asarray(ys["backlog"])[:, :n_seeds]
        lag = np.asarray(ys["lag"])[:, :n_seeds]
        emitted = np.asarray(final.emitted)[:, :n_seeds]
        dropped = np.asarray(final.dropped)[:, :n_seeds]
        ckpt_epoch = np.asarray(final.ckpt_epoch)[:, :n_seeds]
        rollback_t = np.asarray(final.rb_t)[:, :n_seeds]
        thrash_t = np.asarray(final.thrash_t)[:, :n_seeds]
        n_rescale = np.asarray(final.nact)[:, :n_seeds]
        resource_s = np.asarray(final.rsec)[:, :n_seeds]
    jobs = low.arena.jobs if low.arena is not None else None
    total, down = backlog_series(backlog, low.plan.src_cols)
    return [JaxBatchMetrics(low.op_names, tls[0].ts, lag[m], qps[m],
                            backlog[m], emitted[m], dropped[m], tls,
                            ckpt_epoch=ckpt_epoch[m], jobs=jobs,
                            rollback_t=rollback_t[m],
                            thrash_t=thrash_t[m],
                            n_rescale=n_rescale[m],
                            resource_s=resource_s[m],
                            backlog_total=total[m], down_backlog=down[m])
            for m in range(len(mixes))]


# ----------------------------------------------------------------------
# resiliency-config grid axis
# ----------------------------------------------------------------------
def _normalize_traffic(v) -> tuple:
    """Normalize a config-level traffic pattern into the canonical
    ``(diurnal_events, flash_events)`` pair of tuples. Accepts the pair
    itself, a ``{"diurnal": ..., "flash": ...}`` dict, or a bare tuple
    of ``(t0, ramp_s, hold_s, peak)`` flash-crowd events."""
    if not v:
        return ((), ())
    if isinstance(v, dict):
        unknown = set(v) - {"diurnal", "flash"}
        if unknown:
            raise ValueError(f"unknown traffic keys: {sorted(unknown)}")
        return (tuple(tuple(e) for e in v.get("diurnal", ())),
                tuple(tuple(e) for e in v.get("flash", ())))
    v = tuple(v)
    if (len(v) == 2
            and all(isinstance(x, (list, tuple)) for x in v)
            and all(isinstance(e, (list, tuple)) for x in v for e in x)):
        return (tuple(tuple(e) for e in v[0]),
                tuple(tuple(e) for e in v[1]))
    return ((), tuple(tuple(e) for e in v))


def normalize_config(c) -> dict:
    """Normalize one resiliency-config grid entry into
    ``{"failover", "ckpt", "qcap_scale", "sel_scale", "label"}``.

    Accepted forms: a `FailoverConfig`, a `CheckpointConfig`, a
    ``(failover, ckpt)`` TUPLE, a per-job `FailoverConfig` LIST (packed
    arenas; ``None`` entries fall back to the default config — the
    tuple/list distinction is what disambiguates a 2-job list from a
    pair), or a dict with any of the keys above (the fully explicit
    spelling, and the only way to combine per-job failover lists with
    ckpt/scales). The dict form also accepts ``brownout``: config-level
    storage-brownout ramps ``((t0, t1, peak), ...)`` APPENDED to each
    seed spec's own ramps, so brownout severity rides the config axis
    deterministically (no extra draws). ``upgrade`` puts an
    `UpgradeConfig` deployment drill on the config axis — its lowered
    leaves are all traced floats, so drill rows share the drill-free
    rows' compiled trace AND their pregenerated chaos timelines
    (upgrades are in-trace only; `timeline_build_count` stays flat).
    ``traffic`` puts a traffic pattern on the config axis — canonically
    a ``(diurnal_events, flash_events)`` pair (a dict with
    ``diurnal``/``flash`` keys, or a bare tuple of flash-crowd events,
    also accepted), composed into each seed spec's own pattern by tuple
    concatenation exactly like ``brownout``; ``scaler`` puts an
    `AutoscaleConfig` in-trace autoscaler on the config axis — like
    upgrades, both lower to traced curves/floats, so timelines and the
    compiled trace are untouched."""
    out = {"failover": None, "ckpt": None, "qcap_scale": 1.0,
           "sel_scale": 1.0, "brownout": (), "upgrade": None,
           "traffic": ((), ()), "scaler": None, "label": None}
    if c is None:
        return out
    if isinstance(c, dict):
        unknown = set(c) - set(out)
        if unknown:
            raise ValueError(f"unknown config keys: {sorted(unknown)}")
        out.update(c)
        out["traffic"] = _normalize_traffic(out["traffic"])
        return out
    if isinstance(c, FailoverConfig):
        out["failover"] = c
        return out
    if isinstance(c, CheckpointConfig):
        out["ckpt"] = c
        return out
    if isinstance(c, UpgradeConfig):
        out["upgrade"] = c
        return out
    if isinstance(c, AutoscaleConfig):
        out["scaler"] = c
        return out
    if isinstance(c, tuple):
        if len(c) != 2:
            raise ValueError("tuple config entries must be "
                             "(failover, ckpt) pairs")
        out["failover"], out["ckpt"] = c
        return out
    if isinstance(c, list):        # per-job FailoverConfig sequence
        out["failover"] = c
        return out
    raise ValueError(f"unsupported config entry: {c!r}")


def _merge_bro(sp, bro):
    """Compose config-level brownout ramps into a seed spec by tuple
    concatenation (op-identical to the numpy engines' factor)."""
    if not bro:
        return sp
    if isinstance(sp, (list, tuple)):
        return [_merge_bro(x.spec if isinstance(x, ChaosEngine)
                           else (x or ChaosSpec()), bro) for x in sp]
    return dataclasses.replace(
        sp, brownout_at=tuple(sp.brownout_at) + tuple(bro))


def _spec_has_ramps(sp):
    if isinstance(sp, (list, tuple)):
        return any(
            bool(tuple((x.spec if isinstance(x, ChaosEngine)
                        else (x or ChaosSpec())).brownout_at))
            for x in sp)
    return bool(tuple(sp.brownout_at))


class ConfigGridPlan:
    """Chunk-friendly decomposition of `run_config_batch`.

    `__init__` does every seed-count-independent step ONCE per request:
    config normalization, lowering, per-config traced params, timeline
    path selection (the ckpt-bearing grid path keeps ONE
    `GridTimelineBuilder` whose per-seed draw streams are shared by all
    chunks), and the trace-cache lookup (hit/miss traffic lands in
    `cache_info`). `prep_chunk(lo, hi)` builds the host tensors for the
    seed slice ``[lo, hi)`` — each seed's timelines are built exactly
    once across all chunks, so `timeline_build_count()` matches the
    monolithic call — `dispatch` runs one device pass, and `fetch`
    copies it to the host as the per-config `JaxBatchMetrics` list for
    that slice. Driven by `run_chunks`."""

    def __init__(self, graph: LogicalGraph | PackedArena, configs,
                 seeds, *, duration_s: float,
                 base_spec: ChaosSpec | None = None,
                 mixes=None, n_hosts: int = 8,
                 dt: float = 0.5, queue_cap: float = 256.0,
                 task_speed_override: dict[int, float] | None = None,
                 seed: int = 0, pad_seeds: bool = True,
                 devices: int | str | None = None,
                 phase_mode: str = "auto"):
        specs = _as_specs(seeds, base_spec)
        if not specs:
            raise ValueError(
                "run_config_batch requires at least one seed")
        norm = [normalize_config(c) for c in configs]
        if not norm:
            raise ValueError(
                "run_config_batch requires at least one config")
        self.specs, self.norm = specs, norm
        self.low = low = _Lowered(
            graph, n_hosts=n_hosts, dt=dt, queue_cap=queue_cap,
            failover=norm[0]["failover"], ckpt=norm[0]["ckpt"],
            seed=seed, phase_mode=phase_mode,
            seed_width=len(specs) * len(norm))
        self.n_ticks = n_ticks = int(round(duration_s / low.dt))
        self.n_seeds, self.n_cfg = len(specs), len(norm)
        self._override = task_speed_override
        self.pad_seeds = pad_seeds
        jot = (low.job_of_task if low.job_of_task is not None
               else np.zeros(low.plan.n_tasks, dtype=int))

        # per-config traced params
        pa_rows, fo_vecs = [], []
        for cfg in norm:
            codes, det, rst_s, rst_r, fx = per_task_failover(
                cfg["failover"], low.plan.n_tasks, low.job_of_task)
            lazy = lazy_ready_extra(fx["stagger"], low.task_region,
                                    low.job_of_task)
            fo_vecs.append((codes, det, rst_s, rst_r, fx, lazy))
            # per-config deployment drill (inert leaves when cfg has
            # none) — lowered against the config's OWN failover/ckpt
            drill = lower_upgrade(
                cfg["upgrade"], specs[0], n_tasks=low.plan.n_tasks,
                job_of_task=low.job_of_task,
                task_region=low.task_region,
                dt=low.dt, base_failover=(codes, det, rst_s, rst_r, fx),
                base_ckpt=cfg["ckpt"],
                sel_task=low._sel_task * float(cfg["sel_scale"]))
            # per-config in-trace autoscaler (inert when cfg has none)
            auto = lower_autoscale(
                cfg["scaler"], n_tasks=low.plan.n_tasks, dt=low.dt,
                is_src_task=low.tensor.is_src_task)
            pa_rows.append(low._params(
                low.plan.qcap * float(cfg["qcap_scale"]),
                low._sel * float(cfg["sel_scale"]), det, rst_s, rst_r,
                codes, fx=fx, drill=drill, autoscale=auto))
        pa = dict(pa_rows[0])
        for k in ("qcap", "sel", "detect", "restart_region",
                  "restart_single", "mode_single", "mode_region",
                  "mode_hot", "standby_switch", "standby_stale",
                  "restore_base", "replay_rate",
                  "lazy_extra") + _DRILL_KEYS + AUTOSCALE_KEYS:
            pa[k] = np.stack([row[k] for row in pa_rows])
        self.fo_vecs = fo_vecs
        self.cfg_bros = cfg_bros = [tuple(cfg["brownout"])
                                    for cfg in norm]
        self.cfg_traffics = [cfg["traffic"] for cfg in norm]

        # timelines: shared across configs when nothing checkpoints
        # (kill/straggler draws are failover-independent); rebuilt per
        # config otherwise (storage draws interleave with kill draws).
        # per-job seed specs with restore surcharges AND brownout ramps
        # need per-job brownout factors in the recovery metadata — only
        # the per-(config, seed) rebuild path models that; everything
        # else rides the shared-draws fast paths
        perjob_specs = any(isinstance(sp, (list, tuple)) for sp in specs)
        bf_varies_by_job = perjob_specs and (
            any(cfg_bros)
            or any(_spec_has_ramps(sp) for sp in specs)) and any(
            np.any(v[4]["restore_base"]) for v in fo_vecs)
        self.no_ckpt = no_ckpt = (
            all(cfg["ckpt"] is None for cfg in norm)
            and not bf_varies_by_job)
        self.builder = None
        if no_ckpt:
            self.path = "refit"
        elif all(cfg["ckpt"] is None or isinstance(cfg["ckpt"],
                                                   CheckpointConfig)
                 for cfg in norm) and all(isinstance(sp, ChaosSpec)
                                          for sp in specs):
            # ckpt-bearing grid, single coordinators: ONE chaos draw
            # stream per seed, every config's checkpoint attempt
            # schedule refitted onto it as vectorized offset indexing —
            # zero per-(config, seed) host timeline replays
            # (core.chaos.GridTimelineBuilder; timeline_build_count
            # stays flat, pinned by tests/test_sparse_sweep.py). The
            # builder's lazily-created per-seed streams are shared by
            # every chunk, so a chunked run draws each seed exactly
            # once — bit-identical to the monolithic grid.
            self.path = "grid"
            cfg_rows = []
            for cfg, (codes, det, rst_s, rst_r, fx, lazy), bro in zip(
                    norm, fo_vecs, cfg_bros):
                ck = cfg["ckpt"]
                cfg_rows.append(dict(
                    failover_mode=codes, detect_s=det,
                    region_restart_s=rst_r, single_restart_s=rst_s,
                    standby_switch_s=fx["switch"],
                    standby_staleness_s=fx["stale"],
                    restore_base_s=fx["restore_base"],
                    replay_rate=fx["replay_rate"],
                    lazy_extra_s=lazy, brownout_at=bro,
                    ckpt_interval_s=(ck.interval_s if ck else None),
                    ckpt_mode=(ck.mode if ck else "region"),
                    ckpt_upload_s=(ck.upload_s if ck else 4.0),
                    ckpt_retry=(ck.retry_failed_region if ck else True)))
            self.builder = GridTimelineBuilder(
                specs, cfg_rows, n_ticks=n_ticks, dt=low.dt,
                n_hosts=low.n_hosts, task_host=low.task_host,
                task_region=low.task_region, regions=low.phys.regions,
                job_of_task=low.job_of_task)
        else:
            # exotic rows (per-job coordinator lists / per-job chaos
            # specs): config-specific draw interleavings force
            # per-config rebuilds
            self.path = "exotic"

        if devices is not None and mixes is not None:
            raise ValueError("devices= does not compose with mixes= "
                             "(shard the config grid without a mix "
                             "axis)")
        self.n_shards = local_shard_count(devices)
        self.jobs = low.arena.jobs if low.arena is not None else None
        self.mixes = None
        with scoped_cache_stats() as counts:
            if mixes is None:
                if devices is not None:
                    fn = get_sharded_config_fn(low.desc, self.n_shards,
                                               shared_kills=no_ckpt)
                else:
                    fn = get_cached_config_fn(low.desc,
                                              shared_kills=no_ckpt)
            else:
                mixes = np.atleast_2d(np.asarray(mixes,
                                                 dtype=np.float64))
                if mixes.shape[1] != low.n_jobs:
                    raise ValueError(
                        f"mix rows must have one multiplier per job "
                        f"({mixes.shape[1]} != {low.n_jobs})")
                pa["src_row"] = pa["src_row"][None, :] * mixes[:, jot]
                fn = get_cached_config_mix_fn(low.desc,
                                              shared_kills=no_ckpt)
                self.mixes = mixes
        self.fn = fn
        self.pa = pa
        self.cache_info = dict(counts)

    def prep_chunk(self, lo: int, hi: int):
        low, norm = self.low, self.norm
        specs = self.specs[lo:hi]
        n_ticks, n_cfg = self.n_ticks, self.n_cfg
        if self.path == "refit":
            c0, d0, s0, r0 = self.fo_vecs[0][:4]
            base_tls = [low.timeline(sp, n_ticks, fo_codes=c0,
                                     detect=d0, rst_s=s0, rst_r=r0,
                                     ckpt=None)
                        for sp in specs]
            tls = [[refit_failover(tl, task_host=low.task_host,
                                   task_region=low.task_region,
                                   failover_mode=codes, detect_s=det,
                                   single_restart_s=rst_s,
                                   region_restart_s=rst_r,
                                   job_of_task=low.job_of_task,
                                   standby_switch_s=fx["switch"],
                                   standby_staleness_s=fx["stale"],
                                   restore_base_s=fx["restore_base"],
                                   replay_rate=fx["replay_rate"],
                                   lazy_extra_s=lazy,
                                   spec=(_merge_bro(sp, bro)
                                         if isinstance(sp, ChaosSpec)
                                         else None))
                    for sp, tl in zip(specs, base_tls)]
                   for (codes, det, rst_s, rst_r, fx, lazy), bro
                   in zip(self.fo_vecs, self.cfg_bros)]
            # one (S, T, H) tensor broadcast over the config axis
            kills = np.stack([tl.kills
                              for tl in base_tls]).astype(np.float64)
            ckpt_xs = np.zeros((n_cfg, n_ticks), np.int16)
        elif self.path == "grid":
            tls = self.builder.chunk(lo, hi)
            kills = np.stack([[tl.kills for tl in row]
                              for row in tls]).astype(np.float64)
            ckpt_xs = np.stack([row[0].ckpt_at for row in tls])
        else:
            tls = [[low.timeline(_merge_bro(sp, bro), n_ticks,
                                 fo_codes=codes, detect=det,
                                 rst_s=rst_s, rst_r=rst_r,
                                 extras=fx, lazy=lazy, ckpt=cfg["ckpt"])
                    for sp in specs]
                   for cfg, (codes, det, rst_s, rst_r, fx, lazy), bro
                   in zip(norm, self.fo_vecs, self.cfg_bros)]
            kills = np.stack([[tl.kills for tl in row]
                              for row in tls]).astype(np.float64)
            ckpt_xs = np.stack([row[0].ckpt_at for row in tls])

        states = [low.state0(tl, self._override) for tl in tls[0]]
        batch_state = EngineState(
            *(np.stack([getattr(s, f) for s in states])
              for f in EngineState._fields))
        # external-event tensors: brownout factor and ckpt age ride the
        # config axis (config ramps / per-config success histories),
        # the MQ gate is seed-only and broadcasts across configs
        ev = [[low.event_curves(sp, tls[c][s],
                                cfg_ramps=self.cfg_bros[c],
                                cfg_traffic=self.cfg_traffics[c])
               for s, sp in enumerate(specs)] for c in range(n_cfg)]
        xs = {"t": tls[0][0].ts, "kills": kills, "ckpt": ckpt_xs,
              "bfac": np.stack([[e[0] for e in row] for row in ev]),
              "gate": np.stack([e[1] for e in ev[0]]),
              "ckage": np.stack([[e[2] for e in row] for row in ev]),
              "rfac": np.stack([[e[3] for e in row] for row in ev])}
        batch_state, xs = _pad_batch(
            batch_state, xs, hi - lo, self.pad_seeds, self.n_shards,
            seed_axes={"kills": 0 if self.no_ckpt else 1,
                       "bfac": 1, "gate": 0, "ckage": 1, "rfac": 1})
        return (lo, hi, batch_state, xs, tls)

    def dispatch(self, prepped):
        _, _, batch_state, xs, _ = prepped
        with jax.enable_x64(True):
            return jax.block_until_ready(self.fn(self.pa, batch_state, xs))

    def route_entries(self, prepped) -> int:
        rows = self.n_cfg * (1 if self.mixes is None else len(self.mixes))
        return _route_entries(self.low, self.n_ticks, rows, prepped[2])

    def _history(self, ys) -> dict:
        """The device history one pass copies to the host: per-op rows,
        which `run_config_batch` returns to its caller."""
        return {k: ys[k] for k in _HISTORY}

    def fetch(self, prepped, out) -> tuple[list, int]:
        lo, hi, _, _, tls = prepped
        low, mixes = self.low, self.mixes
        final, ys = out
        h, nbytes = _fetch(final, self._history(ys))
        sl = (slice(None),) * (1 if mixes is None else 2)
        h = {k: v[sl + (slice(None, hi - lo),)] for k, v in h.items()}
        if "backlog" in h:
            h["backlog_total"], h["down_backlog"] = backlog_series(
                h["backlog"], low.plan.src_cols)

        def _metrics(c, pre=()):
            r = {k: v[pre + (c,)] for k, v in h.items()}
            return JaxBatchMetrics(low.op_names, tls[0][0].ts, r["lag"],
                                   r.get("qps"), r.get("backlog"),
                                   r["emitted"], r["dropped"], tls[c],
                                   ckpt_epoch=r["ckpt_epoch"],
                                   jobs=self.jobs,
                                   rollback_t=r["rb_t"],
                                   thrash_t=r["thrash_t"],
                                   n_rescale=r["nact"],
                                   resource_s=r["rsec"],
                                   backlog_total=r["backlog_total"],
                                   down_backlog=r["down_backlog"])

        if mixes is None:
            return [_metrics(c) for c in range(self.n_cfg)], nbytes
        return [[_metrics(c, (m,)) for c in range(self.n_cfg)]
                for m in range(len(mixes))], nbytes


class SummaryGridPlan(ConfigGridPlan):
    """A `ConfigGridPlan` whose passes copy only what a summary reads:
    the source lag, the per-tick backlog series (`device_backlog_series`,
    reduced on the device after the pass) and the final-state leaves.
    Its batches carry no per-op `qps` / `backlog` rows. It runs the same
    compiled tick as `ConfigGridPlan`; only the copy differs. Driven by
    `chaos_sweep.sweep_configs`."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.down_mask = np.isin(np.arange(len(self.low.op_names)),
                                 self.low.plan.src_cols, invert=True)

    def _history(self, ys) -> dict:
        with jax.enable_x64(True):
            total, down = device_backlog_series(ys["backlog"],
                                                self.down_mask)
        return {"lag": ys["lag"], "backlog_total": total,
                "down_backlog": down}


def concat_config_batches(parts):
    """Concatenate per-chunk config-grid results (each a per-config
    list, or a mixes × configs nest) along the seed axis — the grid
    analogue of `concat_batches`."""
    if len(parts) == 1:
        return parts[0]
    if parts[0] and isinstance(parts[0][0], list):      # mixes nest
        return [[concat_batches([p[m][c] for p in parts])
                 for c in range(len(parts[0][0]))]
                for m in range(len(parts[0]))]
    return [concat_batches([p[c] for p in parts])
            for c in range(len(parts[0]))]


def run_config_batch(graph: LogicalGraph | PackedArena, configs, seeds, *,
                     duration_s: float,
                     base_spec: ChaosSpec | None = None,
                     mixes=None, n_hosts: int = 8,
                     dt: float = 0.5, queue_cap: float = 256.0,
                     task_speed_override: dict[int, float] | None = None,
                     seed: int = 0, pad_seeds: bool = True,
                     devices: int | str | None = None,
                     phase_mode: str = "auto",
                     seed_chunk: int | None = None,
                     on_chunk=None):
    """Sweep a ``(C, S)`` grid of resiliency-config × chaos-seed
    scenarios in ONE doubly-vmapped `jit` call — the third vmap axis of
    the engine, over `FailoverConfig`/`CheckpointConfig` grids.

    Every resiliency float is a traced leaf (per-task detect / restart
    budgets / mode masks, queue capacities, selectivities), so the whole
    grid shares one compiled trace per grid *shape*; kill tensors are
    shared across configs whenever no config checkpoints (checkpoint
    storage draws are config-dependent, so ckpt-bearing grids rebuild
    per-config timelines). `configs` entries go through
    `normalize_config` — per-job config lists are supported inside a
    `PackedArena`. With `mixes` (an ``(M, n_jobs)`` source-rate grid) the
    call becomes a triply-vmapped ``(M, C, S)`` cube on the same trace.

    ``seed_chunk`` streams the seed axis through fixed-size chunks on
    the double-buffered `run_chunks` pipeline — one device pass per
    chunk, host timeline prep for chunk k+1 overlapping device compute
    for chunk k, each seed's timelines built exactly once across all
    chunks (`timeline_build_count` matches the monolithic call). The
    concatenated grid is bit-identical to the one-pass grid, so
    chunking is purely a memory-ceiling / time-to-first-result knob.
    ``on_chunk`` fires with each `ChunkResult` as it lands, carrying
    the chunk's prep / device / fetch times.

    Returns one `JaxBatchMetrics` per config row — or, with `mixes`, a
    list over mixes of lists over configs.
    """
    plan = ConfigGridPlan(graph, configs, seeds, duration_s=duration_s,
                          base_spec=base_spec, mixes=mixes,
                          n_hosts=n_hosts, dt=dt, queue_cap=queue_cap,
                          task_speed_override=task_speed_override,
                          seed=seed, pad_seeds=pad_seeds,
                          devices=devices, phase_mode=phase_mode)
    chunks = run_chunks(plan, seed_chunk, on_chunk)
    return concat_config_batches([c.batches for c in chunks])
