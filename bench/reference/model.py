"""The settings a request's configurations are made of, with the
system's documented defaults, for the reference side of the benchmark.

Traffic files name them as ``{"$type": "<class>", ...fields}``; the
harness builds the program's classes of the same names from the same
fields, and `objects` builds these."""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class ChaosSpec:
    seed: int = 0
    storage_slow_prob: float = 0.0
    storage_slow_factor: float = 10.0
    storage_fail_prob: float = 0.0
    host_kill_prob_per_s: float = 0.0
    host_kill_at: tuple = ()
    straggler_frac: float = 0.0
    straggler_factor: float = 4.0
    net_delay_factor: float = 1.0
    zk_down: tuple = ()
    hdfs_down: tuple = ()
    brownout_at: tuple = ()
    mq_down: tuple = ()
    burst_at: tuple = ()
    upgrade_at: tuple = ()
    diurnal: tuple = ()
    flash_at: tuple = ()
    rate_phase_s: float = 0.0


@dataclasses.dataclass(frozen=True)
class FailoverConfig:
    mode: str = "region"
    detect_s: float = 1.0
    region_restart_s: float = 45.0
    single_restart_s: float = 3.0
    standby_switch_s: float = 0.05
    standby_staleness_s: float = 0.5
    restore_base_s: float = 0.0
    replay_rate: float = 0.0
    lazyload_stagger_s: float = 0.0


@dataclasses.dataclass(frozen=True)
class CheckpointConfig:
    interval_s: float = 30.0
    mode: str = "region"
    upload_s: float = 4.0
    retry_failed_region: bool = True


@dataclasses.dataclass(frozen=True)
class StartupConfig:
    object_reuse: bool = True
    batched_deploy: bool = True
    straggler_mitigation: bool = True
    alloc_threshold_s: float = 120.0
    overprovision_frac: float = 0.3
    overprovision_cap: int = 5
    hotupdate: bool = False


@dataclasses.dataclass(frozen=True)
class UpgradeConfig:
    t_upgrade_s: float = 30.0
    wave_stagger_s: float = 2.0
    hot: bool = True
    startup: StartupConfig | None = None
    wave_down_s: float | None = None
    canary_frac: float = 0.5
    canary_jobs: tuple | None = None
    rollback_threshold: float = math.inf
    rollback_window_s: float = 5.0
    canary_failover: FailoverConfig | None = None
    canary_ckpt: CheckpointConfig | None = None
    canary_sel_scale: float = 1.0


TYPES = {c.__name__: c for c in (ChaosSpec, FailoverConfig,
                                 CheckpointConfig, StartupConfig,
                                 UpgradeConfig)}


def deploy_downtime(startup: StartupConfig | None, hot: bool) -> float:
    """Seconds of downtime one rolling-upgrade wave pays: a cached (2 s)
    or full (18 s) compile, a 6 s state upload when cold, and a 3 s
    first step; object reuse takes 30% off the first step, batched
    deployment half of what is left, and straggler mitigation divides
    upload and first step by ``1 + min(overprovision_frac, 1)``."""
    cfg = startup or StartupConfig()
    compile_s = 2.0 if hot else 18.0
    transfer_s = 0.0 if hot else 6.0
    first_step_s = 3.0
    if cfg.object_reuse:
        first_step_s *= 0.7
    if cfg.batched_deploy:
        first_step_s *= 0.5
    if cfg.straggler_mitigation:
        tail = 1.0 / (1.0 + min(cfg.overprovision_frac, 1.0))
        transfer_s *= tail
        first_step_s *= tail
    return compile_s + transfer_s + first_step_s
