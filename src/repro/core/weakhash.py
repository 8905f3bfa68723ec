"""WeakHash (paper §III-A): relax the strict key→task binding to a bounded
candidate set + dynamic (load-aware) selection. Host-side numpy version used
by the stream engine, the data pipeline and the cluster sim; the token-path
twin lives in kernels/weakhash_route (jnp/Pallas).

`weakhash_assign` is vectorized: instead of the O(N·gsz) sequential greedy
loop it computes, per candidate group, the exact per-task key counts the
greedy process would produce (a water-filling argument — see
`_group_counts`), then materializes assignments in one scatter.

Tie-order relaxation (documented, tested): the sequential greedy interleaves
keys across tasks in arrival order; the vectorized path assigns each group's
keys task-major (task 0's quota first, then task 1's, ...). Per-task counts
— and therefore `load_cv` and group containment — are IDENTICAL (bit-exact
for integer-valued starting loads, to within one float-ulp tie reshuffle
otherwise); only which individual key lands on which in-group task differs.
Pass ``sequential=True`` for the original arrival-order semantics.
"""
from __future__ import annotations

import numpy as np

KNUTH = np.uint32(2654435761)


def strong_hash(keys: np.ndarray, n_tasks: int) -> np.ndarray:
    """Flink-style keyBy: key → exactly one task."""
    return ((keys.astype(np.uint64) * 2654435761) % n_tasks).astype(np.int64)


def candidate_group(keys: np.ndarray, n_groups: int) -> np.ndarray:
    return ((keys.astype(np.uint64) * 2654435761) % n_groups).astype(np.int64)


def _group_counts(L: np.ndarray, k: np.ndarray) -> np.ndarray:
    """Exact per-task key counts of sequential least-loaded filling.

    Greedy least-loaded with unit increments picks exactly the k smallest
    values of the virtual grid {L[j] + i : i >= 0} (ties broken toward the
    lower task index). The count for task j is therefore the number of its
    grid values below the k-th smallest ("water level"), found here by a
    vectorized bisection per group.

    L: (G, m) starting loads per group; k: (G,) keys per group.
    Returns integer counts (G, m) with counts.sum(1) == k.
    """
    G, m = L.shape
    kf = k.astype(np.float64)
    base = L.min(axis=1)
    lo = base - 1.0                # N(lo) = 0 < k
    hi = base + kf                 # argmin task alone yields k+1 ≥ k
    for _ in range(64):
        mid = 0.5 * (lo + hi)
        cnt = np.maximum(np.floor(mid[:, None] - L) + 1.0, 0.0).sum(axis=1)
        ge = cnt >= kf
        hi = np.where(ge, mid, hi)
        lo = np.where(ge, lo, mid)
    c = np.maximum(np.floor(hi[:, None] - L) + 1.0, 0.0).astype(np.int64)
    surplus = c.sum(axis=1) - k
    # remove the surplus from the tie candidates (tasks whose topmost picked
    # value sits at the water level), highest task index first — mirroring
    # the greedy's lowest-index-wins tie break
    top = L + (c - 1)
    cand = (c > 0) & (top > lo[:, None])
    rank_from_right = np.cumsum(cand[:, ::-1], axis=1)[:, ::-1]
    c -= cand & (rank_from_right <= surplus[:, None])
    c[k == 0] = 0
    return c


def weakhash_assign(keys: np.ndarray, n_tasks: int, n_groups: int,
                    loads: np.ndarray | None = None,
                    rng: np.random.Generator | None = None,
                    sequential: bool = False,
                    chunk: int | None = None) -> np.ndarray:
    """Assign each key to a task within its candidate group, least-loaded
    first (records within a batch update the load estimate greedily,
    mirroring credit consumption). Vectorized; see the module docstring for
    the tie-order relaxation versus ``sequential=True``.

    ``chunk=C`` enables the chunked-streaming mode: the water-fill runs
    per chunk of C keys and the load estimates are refreshed between
    chunks, interpolating between the batch semantics (``chunk >= N``
    reproduces the batch assignment array exactly — one chunk IS the
    batch) and the sequential credit semantics (``chunk=1`` degenerates
    to one least-loaded pick per key, i.e. ``sequential=True``
    key-for-key, so it takes the sequential path)."""
    assert n_tasks % n_groups == 0, (n_tasks, n_groups)
    gsz = n_tasks // n_groups
    if chunk == 1:
        sequential = True
    if chunk is not None and not sequential:
        assert chunk > 0, chunk
        loads_c = (np.zeros(n_tasks, np.float64) if loads is None
                   else loads.astype(np.float64).copy())
        out = np.empty(len(keys), np.int64)
        for lo in range(0, len(keys), chunk):
            part = weakhash_assign(keys[lo:lo + chunk], n_tasks, n_groups,
                                   loads=loads_c)
            out[lo:lo + chunk] = part
            loads_c += np.bincount(part, minlength=n_tasks)
        return out
    group = candidate_group(keys, n_groups)
    loads = np.zeros(n_tasks, np.float64) if loads is None else loads.astype(
        np.float64).copy()
    if sequential:
        # greedy sequential least-loaded pick (arrival-order semantics;
        # kept as the reference for the vectorized path's parity tests)
        out = np.empty(len(keys), np.int64)
        for i, g in enumerate(group):
            base = g * gsz
            cand = loads[base:base + gsz]
            j = int(np.argmin(cand))
            out[i] = base + j
            loads[base + j] += 1.0
        return out
    k_per_group = np.bincount(group, minlength=n_groups)
    counts = _group_counts(loads.reshape(n_groups, gsz), k_per_group)
    # group-sorted key positions receive tasks task-major per group
    task_seq = np.repeat(np.arange(n_tasks, dtype=np.int64),
                         counts.reshape(-1))
    order = np.argsort(group, kind="stable")
    out = np.empty(len(keys), np.int64)
    out[order] = task_seq
    return out


def load_cv(assignments: np.ndarray, n_tasks: int,
            weights: np.ndarray | None = None) -> float:
    """Coefficient of variation of per-task load (skew metric)."""
    w = np.ones(len(assignments)) if weights is None else weights
    loads = np.bincount(assignments, weights=w, minlength=n_tasks)
    mu = loads.mean()
    return float(loads.std() / mu) if mu > 0 else 0.0
