"""Region checkpointing: merge semantics, restore round-trips, and property
tests over random failure patterns (hypothesis)."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.ckpt.manifest import Manifest, RegionSnapshot
from repro.ckpt.storage import LocalFS, ObjectStoreSim, SimHDFS, FallbackStorage
from repro.configs import get_smoke_arch
from repro.core import regions as R
from repro.core.chaos import ChaosEngine, ChaosSpec
from repro.core.clock import VirtualClock
from repro.core.region_checkpoint import RegionCheckpointer
from repro.models import build


@pytest.fixture(scope="module")
def model_and_params():
    m = build(get_smoke_arch("stablelm-1.6b"))
    return m, m.init(jax.random.PRNGKey(0))


def _ckpt(tmp, regions, mode="region", chaos=None, clock=None):
    clock = clock or VirtualClock()
    store = SimHDFS(tmp, clock=clock, chaos=chaos or ChaosEngine())
    return RegionCheckpointer(store, "job", regions, mode=mode, clock=clock)


def test_partition_covers_everything(model_and_params):
    m, params = model_and_params
    regions = R.partition_regions(m.param_specs(), 4)
    paths = set()
    for reg in regions:
        for s in reg.slices:
            key = (s.path, s.layer_lo)
            assert key not in paths, "overlapping slices"
            paths.add(key)
    # every leaf appears
    leaf_paths = {p for p, _ in R._flatten_with_paths(m.param_specs())}
    covered = {s.path for reg in regions for s in reg.slices}
    assert covered == leaf_paths


def test_restore_roundtrip_exact(model_and_params, tmp_path):
    m, params = model_and_params
    regions = R.partition_regions(m.param_specs(), 3)
    ck = _ckpt(tmp_path / "s", regions)
    ck.save(5, params)
    restored, info = ck.restore(params, gamma="full")
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert set(info["steps"].values()) == {5}


def test_merge_semantics_full_vs_partial(model_and_params, tmp_path):
    m, params = model_and_params
    regions = R.partition_regions(m.param_specs(), 4)
    ck = _ckpt(tmp_path / "s", regions)
    ck.save(1, params)
    # simulate a failed region-2 upload at step 2 by editing the manifest
    params2 = jax.tree.map(lambda x: x + 1, params)
    ck.save(2, params2)
    ck.manifest.history[2] = [s for s in ck.manifest.history[2] if s.step != 2]
    _, info_p = ck.restore(params, gamma="partial")
    assert info_p["steps"][2] == 1 and info_p["steps"][0] == 2
    assert info_p["staleness"][2] == 1
    _, info_f = ck.restore(params, gamma="full")
    assert set(info_f["steps"].values()) == {1}, \
        "γ=full must fall back to the newest globally consistent step"


def test_global_mode_aborts_on_failure(model_and_params, tmp_path):
    m, params = model_and_params
    regions = R.partition_regions(m.param_specs(), 4)
    chaos = ChaosEngine(ChaosSpec(seed=5, storage_fail_prob=0.6))
    ck = _ckpt(tmp_path / "s", regions, mode="global", chaos=chaos)
    reports = [ck.save(i, params) for i in range(6)]
    failed = [r for r in reports if not r.success]
    assert failed, "chaos should break at least one attempt"
    stats = ck.success_rate()
    assert stats["usable_rate"] < 1.0


def test_region_mode_stays_usable_under_chaos(model_and_params, tmp_path):
    m, params = model_and_params
    regions = R.partition_regions(m.param_specs(), 4)
    chaos = ChaosEngine(ChaosSpec(seed=5, storage_fail_prob=0.3))
    ck = _ckpt(tmp_path / "s", regions, mode="region", chaos=chaos)
    for i in range(6):
        ck.save(i, jax.tree.map(lambda x, i=i: x + i, params))
    restored, info = ck.restore(params, gamma="partial")
    assert max(info["staleness"].values()) <= 6
    stats = ck.success_rate()
    assert stats["usable_rate"] == 1.0, \
        "region mode merges failures instead of aborting"


# ----------------------------------------------------------------------
# property tests over random failure patterns (manifest-level)
# ----------------------------------------------------------------------
@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 5), st.booleans()), min_size=1,
                max_size=24))
def test_manifest_merge_invariants(events):
    """For any sequence of (step, ok)-per-region events:
    γ=full view is step-uniform; γ=partial staleness = newest - per-region."""
    n_regions = 3
    man = Manifest("j", n_regions)
    steps_by_region = {r: [] for r in range(n_regions)}
    step = 0
    for inc, ok in events:
        step += 1 + inc
        for r in range(n_regions):
            if ok or (r + step) % 2:  # failure pattern varies by region
                man.add(RegionSnapshot(r, step, {}, 0))
                steps_by_region[r].append(step)
    if not all(steps_by_region.values()):
        return
    view = man.merge_view("partial")
    newest = max(s.step for s in view.values())
    for r, snap in view.items():
        assert snap.step == max(steps_by_region[r])
        assert man.staleness(view)[r] == newest - snap.step
    common = set.intersection(*(set(v) for v in steps_by_region.values()))
    if common:
        viewf = man.merge_view("full")
        assert len({s.step for s in viewf.values()}) == 1
        assert viewf[0].step == max(common)
    else:
        with pytest.raises(LookupError):
            man.merge_view("full")


def test_content_dedup(model_and_params, tmp_path):
    """Identical region content re-uploads nothing (content addressing)."""
    m, params = model_and_params
    regions = R.partition_regions(m.param_specs(), 2)
    clock = VirtualClock()
    store = SimHDFS(tmp_path / "s", clock=clock, chaos=ChaosEngine())
    ck = RegionCheckpointer(store, "job", regions, clock=clock)
    ck.save(1, params)
    n1 = store.put_count
    ck.save(2, params)  # same bytes
    assert store.put_count <= n1 + 2, "only manifests should be re-written"
