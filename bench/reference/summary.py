"""A scenario's resiliency summary from its per-tick record: the
surfaces a release gate reads (recovery time, SLO violation, backlog,
lost work, checkpoint counts, rollback time)."""
from __future__ import annotations

import math

import numpy as np

from bench.reference.sim import Outcome


def recovery_time(ts, lag, down_bk, recs) -> float:
    """Seconds from the first failure until the job is healthy again:
    the last outage has ended, the per-tick growth of source lag is back
    at its pre-failure 95th percentile (plus a margin of 1e-12 of the
    largest lag, against rounding of lags near 3e9), and the downstream
    backlog is within twice its pre-failure median. 0 when never
    unhealthy, inf when still unhealthy at the horizon."""
    t_fail = recs[0]["t"]
    outage_end = max(r["t"] + r["downtime"] for r in recs)
    pre = ts < t_fail
    dlag = np.diff(lag, prepend=lag[:1])
    grow_thr = (float(np.percentile(dlag[pre], 95)) if pre.any()
                else 0.0) + 1e-9 + 1e-12 * float(np.abs(lag).max())
    bk_thr = max(2.0 * (float(np.median(down_bk[pre])) if pre.any()
                        else 0.0), 1.0)
    breach = (ts < outage_end) | (dlag > grow_thr) | (down_bk > bk_thr)
    breach &= ts >= t_fail
    if not breach.any():
        return 0.0
    last = int(np.nonzero(breach)[0][-1])
    if last == len(ts) - 1:
        return math.inf
    return float(ts[last + 1] - t_fail)


def summarize(out: Outcome) -> dict:
    """The numbers the benchmark compares for one scenario. The SLO
    threshold is twice the median source lag before the first failure
    (over the whole run when nothing fails)."""
    ts = out.t
    lag = out.lag.astype(np.float64)
    down = out.down_backlog.astype(np.float64)
    recs = out.recoveries
    t_fail = recs[0]["t"] if recs else None
    pre = lag[ts < t_fail] if t_fail is not None else lag
    thr = 2.0 * (float(np.median(pre)) if len(pre) else 0.0) + 1e-9
    return {
        "n_failures": len(recs),
        "recovery_time_s": (recovery_time(ts, lag, down, recs)
                            if recs else 0.0),
        "max_backlog": float(out.backlog.max()),
        "max_lag": float(lag.max()),
        "slo_violation_ticks": int(np.sum(lag > thr)),
        "dropped": float(out.dropped),
        "emitted": float(out.emitted),
        "ckpt_attempts": int(out.ckpt_attempts),
        "ckpt_success": int(out.ckpt_success),
        "rollback_t": float(out.rollback_t),
    }
