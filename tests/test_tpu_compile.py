"""Compile rehearsals for a described TPU v5e chip (no chip attached).

The config-grid run function of the sweep service's main path is
compiled for one described v5e chip in the dense and the compact tick
lowering, and a TPU service refuses the removed ``phase_mode="pallas"``
at `submit`, before any job or trace.

The topology is described only inside a fixture: one process at a time
may load the TPU library, so describing it at import would break the
other test workers.
"""
import os

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.chaos import ChaosSpec
from repro.launch.serve import SweepService
from repro.streams import nexmark
from repro.streams.engine import CheckpointConfig, FailoverConfig
from repro.streams.jax_engine import (ConfigGridPlan, device_backlog_series,
                                      trace_cache_stats)

SPEC = nexmark.ha_drill_spec(burst_t=10.0, brownout=(5.0, 20.0, 4.0),
                             mq_outage=(22.0, 25.0),
                             host_kill_prob_per_s=0.002)
CONFIGS = [(FailoverConfig(mode=m), CheckpointConfig(interval_s=iv))
           for m in ("hot_standby", "region") for iv in (5.0, 10.0)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's executables cannot be read back without it, so
    # keep the persistent compile cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _grid_args(plan: ConfigGridPlan, sharding):
    """Shapes (no arrays) of one full-grid device pass, on `sharding`."""
    _, _, state, xs, _ = plan.prep_chunk(0, plan.n_seeds)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                       sharding=sharding),
        (plan.pa, state, xs))


def _compile(plan: ConfigGridPlan, sharding):
    with jax.enable_x64(True):
        return plan.fn.lower(*_grid_args(plan, sharding)).compile()


@pytest.mark.parametrize("mode", ["dense", "compact"])
def test_config_grid_compiles_for_v5e(one_chip, mode):
    arena = nexmark.q12_arena(n_tasks=240, n_hosts=16)
    plan = ConfigGridPlan(arena, CONFIGS, range(4), duration_s=30.0,
                          base_spec=SPEC, phase_mode=mode)
    assert plan.low.tensor.mode == mode
    compiled = _compile(plan, one_chip)
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes > 0
    # (C, S, T, n_ops) qps + backlog histories come back in f64
    n_ops = len(plan.low.op_names)
    assert mem.output_size_in_bytes >= 2 * 8 * (
        len(CONFIGS) * 4 * plan.n_ticks * n_ops)


def test_backlog_series_compiles_for_v5e(one_chip):
    """The summary copy's reduction at the q12 cell's chunk shapes:
    (C, S, T, n_ops) = (12, 8, 360, 1248) f64 in, two (C, S, T) out."""
    c, s, t, n_ops = 12, 8, 360, 1248
    args = (jax.ShapeDtypeStruct((c, s, t, n_ops), np.float64,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((n_ops,), np.bool_, sharding=one_chip))
    with jax.enable_x64(True):
        compiled = device_backlog_series.lower(*args).compile()
    # two f64 series, in the chip's tiled layout (T padded to 384)
    out = compiled.memory_analysis().output_size_in_bytes
    assert 2 * c * s * t * 8 <= out < 2 * c * s * 384 * 8 + 1024


def test_roofline_peaks_keyed_by_device_kind(topo):
    """The peaks table is keyed by what JAX reports for the chip, and a
    chip it does not list is an error, not a default."""
    from repro.launch.roofline import V5E, chip_peaks, kernel_roofline

    assert topo.devices[0].device_kind == V5E
    peaks = chip_peaks(V5E)
    roof = kernel_roofline(peaks["bf16_flops"], 2 * peaks["hbm_bw"], V5E)
    assert (roof["compute_s"], roof["memory_s"]) == (1.0, 2.0)
    assert roof["bound"] == "memory"
    with pytest.raises(KeyError, match="no published peaks"):
        kernel_roofline(1.0, 1.0, "TPU v4")


def test_pallas_tick_refused_on_tpu(monkeypatch):
    """The fused Pallas tick is gone: on a TPU backend the service
    rejects ``phase_mode="pallas"`` as bad input at `submit`, before a
    job is queued or a trace is looked up."""
    arena = nexmark.q12_arena(n_tasks=240, n_hosts=16)
    with SweepService(workers=1) as svc:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        before = trace_cache_stats()
        with pytest.raises(ValueError, match="dense|compact|auto"):
            svc.submit("sweep_configs", arena, range(4),
                       configs=CONFIGS, duration_s=30.0,
                       base_spec=ChaosSpec(), phase_mode="pallas")
        assert svc.jobs() == []
        assert trace_cache_stats() == before
