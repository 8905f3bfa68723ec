"""Property-based tests for `weakhash_assign` invariants (hypothesis):

* counts sum to N and every key stays inside its candidate group
  (bounded candidate set — the WeakHash §III-A contract);
* capacity/balance: least-loaded water-filling never spreads a group
  wider than max(initial spread, 1);
* permutation-of-keys invariance of the per-task counts;
* chunked-streaming mode: ``chunk >= N`` reproduces the batch
  assignment exactly, ``chunk=1`` degenerates to the sequential greedy,
  and every chunk size preserves the invariants.
"""
import numpy as np

from hypothesis import given, settings, strategies as st
from repro.core.weakhash import candidate_group, load_cv, weakhash_assign


def _keys(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 1 << 20, n)


@settings(max_examples=30)
@given(st.integers(1, 8), st.integers(1, 6), st.integers(0, 400),
       st.integers(0, 10_000))
def test_counts_sum_and_candidate_containment(n_groups, gsz, n_keys, seed):
    n_tasks = n_groups * gsz
    keys = _keys(seed, n_keys)
    out = weakhash_assign(keys, n_tasks, n_groups)
    counts = np.bincount(out, minlength=n_tasks)
    assert counts.sum() == n_keys
    assert np.array_equal(out // gsz, candidate_group(keys, n_groups))
    # capacity bound: zero starting loads → water level caps every task
    # at ceil(group_keys / gsz); spread within a group is at most 1
    per_group = counts.reshape(n_groups, gsz)
    assert (per_group.max(1) - per_group.min(1) <= 1).all()
    gkeys = np.bincount(candidate_group(keys, n_groups),
                        minlength=n_groups)
    assert (per_group.max(1) <= np.ceil(gkeys / gsz)).all()


@settings(max_examples=25)
@given(st.integers(1, 6), st.integers(2, 6), st.integers(1, 300),
       st.integers(0, 10_000))
def test_balance_never_widens_initial_spread(n_groups, gsz, n_keys, seed):
    n_tasks = n_groups * gsz
    rng = np.random.default_rng(seed)
    keys = _keys(seed + 1, n_keys)
    loads = rng.integers(0, 40, n_tasks).astype(np.float64)
    out = weakhash_assign(keys, n_tasks, n_groups, loads=loads)
    final = loads + np.bincount(out, minlength=n_tasks)
    fg = final.reshape(n_groups, gsz)
    lg = loads.reshape(n_groups, gsz)
    spread0 = lg.max(1) - lg.min(1)
    spread1 = fg.max(1) - fg.min(1)
    assert (spread1 <= np.maximum(spread0, 1.0)).all()


@settings(max_examples=25)
@given(st.integers(1, 8), st.integers(1, 5), st.integers(1, 400),
       st.integers(0, 10_000))
def test_group_counts_permutation_invariance(n_groups, gsz, n_keys, seed):
    n_tasks = n_groups * gsz
    keys = _keys(seed, n_keys)
    perm = np.random.default_rng(seed + 7).permutation(n_keys)
    a = np.bincount(weakhash_assign(keys, n_tasks, n_groups),
                    minlength=n_tasks)
    b = np.bincount(weakhash_assign(keys[perm], n_tasks, n_groups),
                    minlength=n_tasks)
    assert np.array_equal(a, b)
    assert load_cv(weakhash_assign(keys, n_tasks, n_groups), n_tasks) == \
        load_cv(weakhash_assign(keys[perm], n_tasks, n_groups), n_tasks)


# ----------------------------------------------------------------------
# chunked-streaming mode
# ----------------------------------------------------------------------
@settings(max_examples=25)
@given(st.integers(1, 6), st.integers(1, 5), st.integers(1, 300),
       st.integers(1, 64), st.integers(0, 10_000))
def test_chunked_mode_invariants(n_groups, gsz, n_keys, chunk, seed):
    n_tasks = n_groups * gsz
    keys = _keys(seed, n_keys)
    out = weakhash_assign(keys, n_tasks, n_groups, chunk=chunk)
    counts = np.bincount(out, minlength=n_tasks)
    assert counts.sum() == n_keys
    assert np.array_equal(out // gsz, candidate_group(keys, n_groups))


@settings(max_examples=20)
@given(st.integers(1, 6), st.integers(1, 5), st.integers(0, 300),
       st.integers(0, 10_000))
def test_chunk_of_full_batch_is_the_batch(n_groups, gsz, n_keys, seed):
    """chunk >= N is ONE water-fill — the batch assignment, key-for-key."""
    n_tasks = n_groups * gsz
    keys = _keys(seed, n_keys)
    batch = weakhash_assign(keys, n_tasks, n_groups)
    for chunk in (max(n_keys, 1), n_keys + 17):
        chunked = weakhash_assign(keys, n_tasks, n_groups, chunk=chunk)
        assert np.array_equal(chunked, batch)
        assert np.array_equal(np.bincount(chunked, minlength=n_tasks),
                              np.bincount(batch, minlength=n_tasks))


@settings(max_examples=15)
@given(st.integers(1, 6), st.integers(1, 5), st.integers(0, 200),
       st.integers(0, 10_000))
def test_chunk_one_degenerates_to_sequential(n_groups, gsz, n_keys, seed):
    """chunk=1 is one least-loaded pick per key — the sequential greedy
    exactly (arrival order, lowest-index tie break), per key."""
    n_tasks = n_groups * gsz
    keys = _keys(seed, n_keys)
    rng = np.random.default_rng(seed + 3)
    loads = rng.integers(0, 20, n_tasks).astype(np.float64)
    a = weakhash_assign(keys, n_tasks, n_groups, loads=loads, chunk=1)
    b = weakhash_assign(keys, n_tasks, n_groups, loads=loads,
                        sequential=True)
    assert np.array_equal(a, b)


@settings(max_examples=15)
@given(st.integers(2, 6), st.integers(2, 5), st.integers(50, 300),
       st.integers(0, 10_000))
def test_chunked_interpolates_between_batch_and_sequential(
        n_groups, gsz, n_keys, seed):
    """Chunked counts stay balanced: per-group spread stays ≤ 1 for any
    chunk size when starting from flat loads (each chunk water-fills on
    refreshed loads, so imbalance never accumulates)."""
    n_tasks = n_groups * gsz
    keys = _keys(seed, n_keys)
    for chunk in (7, 32, 128):
        out = weakhash_assign(keys, n_tasks, n_groups, chunk=chunk)
        per_group = np.bincount(out, minlength=n_tasks).reshape(
            n_groups, gsz)
        assert (per_group.max(1) - per_group.min(1) <= 1).all(), chunk
