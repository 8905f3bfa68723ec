"""The end-to-end arithmetic: rate over the window, tails over the
requests due in it, and the seeds and arrival schedules of a run."""
import math

import numpy as np
import pytest

from bench.harness import check, program, stats
from bench.harness.stats import Request


def _req(i, due, lands=(), done=None, error=None):
    r = Request(i, due=due, submitted=due, done=done, error=error)
    r.chunks = [(t, n, None) for t, n in lands]
    return r


def test_rate_counts_work_landed_in_window_over_time_to_last_landing():
    reqs = [_req(1, 0.0, [(10.0, 100), (20.0, 100), (30.0, 100)], 30.0),
            _req(2, 30.0, [(40.0, 100), (55.0, 100)], None)]
    value, n = stats.rate(reqs, 0.0, 50.0)
    assert n == 4
    assert value == pytest.approx(400 / 40.0)
    assert stats.rate([_req(1, 0.0)], 0.0, 50.0) == (0.0, 0)


def test_latencies_count_open_requests_at_their_age_and_failures_as_inf():
    reqs = [_req(1, 0.0, [(1.0, 10), (2.0, 10)], done=2.5),
            _req(2, 5.0, [(9.0, 10)], done=None),          # open at close
            _req(3, 6.0, [], done=None),                   # nothing yet
            _req(4, 7.0, error="boom"),
            _req(5, 11.0, [(12.0, 1)], done=13.0)]         # due after close
    ttfr, wall = stats.latencies(reqs, 10.0)
    assert ttfr == [1.0, 4.0, 4.0, math.inf]
    assert wall == [2.5, 5.0, 4.0, math.inf]
    assert stats.nearest_rank(wall, 0.9) == math.inf
    assert stats.nearest_rank(wall[:3], 0.5) == 4.0


@pytest.mark.parametrize("q,want", [(0.5, 5), (0.9, 9), (1.0, 10),
                                    (0.01, 1)])
def test_nearest_rank(q, want):
    assert stats.nearest_rank(range(10, 0, -1), q) == want


def test_open_schedule_is_fixed_by_its_order_seed():
    a = program.open_schedule(3.0, 50.0, 1)
    b = program.open_schedule(3.0, 50.0, 1)
    c = program.open_schedule(3.0, 50.0, 2)
    assert len(a) == len(c) == 150
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    gaps = -np.log1p(-(np.arange(150) + 0.5) / 150) / 3.0
    gaps *= 50.0 / gaps.sum()
    for due in (a, c):
        got = np.append(np.diff(due), due[0] + gaps.min() / 2)
        np.testing.assert_allclose(np.sort(got), np.sort(gaps), rtol=1e-9)
    assert (a >= 0).all() and (a < 50.0).all()


def test_request_seeds_are_fresh_blocks_per_request():
    s = 2**31 + 7
    blocks = [program.request_seeds(s, i, 32) for i in range(4)]
    assert blocks[1] == program.request_seeds(s, 1, 32)
    flat = [x for b in blocks for x in b]
    assert len(set(flat)) == len(flat)


@pytest.mark.parametrize("key", check.FLOW_KEYS + check.EVENT_KEYS
                         + ("recovery_time_s", "slo_violation_ticks",
                            "rollback_t"))
@pytest.mark.parametrize("side", ["program", "reference"])
def test_a_nan_on_either_side_is_never_correct(key, side):
    want = {k: 5.0 for k in check.FLOW_KEYS + check.EVENT_KEYS
            + ("recovery_time_s", "slo_violation_ticks", "rollback_t")}
    limits = {k: 0.0 for k in check.compare([want], [want], 180.0)}
    assert check.verdict(check.compare([want], [dict(want)], 180.0),
                         limits)[0]
    got = dict(want)
    (got if side == "program" else want)[key] = math.nan
    ok, table = check.verdict(check.compare([got], [want], 180.0), limits)
    assert not ok, table


def test_equal_infinities_agree_and_inf_against_finite_reads_big():
    assert check._gap(math.inf, math.inf, 180.0) == 0.0
    assert check._gap(math.inf, 3.0, 180.0) == 180.0
    assert check._gap(3.0, 1.0, 180.0) == 2.0
    assert check._gap(math.nan, math.nan, 180.0) == math.inf
