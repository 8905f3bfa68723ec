"""Whether the timed path's answers are correct: a sample, drawn from
the run's seed, of the (configuration, seed) scenarios that landed in
the window is recomputed by the plain reference (`bench.reference`),
and the program's summaries are compared with the reference's. Every
finished request's cube is also checked against its chunks: the final
surfaces must be the chunks' surfaces laid side by side, bit for bit.
"""
from __future__ import annotations

import dataclasses
import importlib
import math

import numpy as np

from bench.harness.data import decode
from bench.reference import model
from bench.reference.fleet import fleet as ref_fleet
from bench.reference.sim import Scenario, simulate
from bench.reference.summary import summarize

FLOW_KEYS = ("emitted", "dropped", "max_backlog", "max_lag")
EVENT_KEYS = ("n_failures", "ckpt_attempts", "ckpt_success")
SURFACES = ("recovery_surface", "slo_surface", "backlog_surface",
            "lost_surface", "rollback_surface")


@dataclasses.dataclass
class Pick:
    """One sampled scenario: config row `c` of a landed chunk, seed `s`."""
    seed: int
    c: int
    program: dict


def program_values(chunk, c: int, s: int) -> dict:
    sm = chunk.summaries[c][s]
    out = {k: getattr(sm, k) for k in FLOW_KEYS + EVENT_KEYS
           + ("recovery_time_s", "slo_violation_ticks")}
    out["rollback_t"] = float(chunk.rollback_surface[c][s])
    return out


def sample(requests, w1: float, n: int, rng) -> list[Pick]:
    """`n` scenarios drawn without replacement from every chunk that
    reached its subscriber by `w1`."""
    pool = [(ch, c, s) for r in requests for t, _, ch in r.chunks
            if t <= w1 for c in range(len(ch.summaries))
            for s in range(ch.n_seeds)]
    if not pool:
        return []
    idx = rng.choice(len(pool), size=min(n, len(pool)), replace=False)
    return [Pick(int(pool[i][0].seeds[pool[i][2]]), pool[i][1],
                 program_values(*pool[i])) for i in sorted(idx)]


class Reference:
    """The reference's view of one cell: its fleet, the request's
    configuration rows and its chaos spec, all from the cell's data."""

    def __init__(self, config: dict, traffic: dict):
        self.fleet = ref_fleet(config)
        types = model.TYPES
        kind = importlib.import_module(
            f"bench.reference.kinds.{traffic['kind']}")
        self.rows = kind.rows(decode(traffic["args"], types))
        self.base = decode(traffic["base_spec"], types)
        self.horizon = float(traffic["horizon_s"])

    def values(self, pick: Pick, dtype=np.float64) -> dict:
        row = self.rows[pick.c]
        spec = dataclasses.replace(
            self.base, seed=pick.seed,
            brownout_at=tuple(self.base.brownout_at) + row["brownout"])
        out = simulate(self.fleet, Scenario(spec, row["failover"],
                                            row["ckpt"], row["upgrade"],
                                            self.horizon), dtype)
        return summarize(out)


def _gap(a: float, b: float, big: float = math.inf) -> float:
    """|a - b| where either may be inf or NaN: equal infinities agree,
    an inf against a finite value reads as `big`, and a NaN on either
    side reads as inf, so that no NaN can drop out of a `max`."""
    if math.isnan(a) or math.isnan(b):
        return math.inf
    if math.isinf(a) or math.isinf(b):
        return 0.0 if a == b else big
    return abs(a - b)


def compare(got: list[dict], want: list[dict], horizon: float) -> dict:
    """The compared numbers over paired scenario summaries."""
    flow = rec = slo = rb = 0.0
    events = 0
    for g, w in zip(got, want):
        for k in FLOW_KEYS:
            d = _gap(g[k], w[k])
            flow = max(flow, d if math.isinf(d)
                       else d / max(abs(w[k]), 1.0))
        rec = max(rec, _gap(g["recovery_time_s"], w["recovery_time_s"],
                            horizon))
        rb = max(rb, _gap(g["rollback_t"], w["rollback_t"], horizon))
        slo = max(slo, _gap(g["slo_violation_ticks"],
                            w["slo_violation_ticks"]))
        events += any(g[k] != w[k] for k in EVENT_KEYS)
    return {"flow_rel_err": flow, "recovery_gap_s": rec,
            "slo_tick_gap": float(slo), "rollback_gap_s": rb,
            "event_mismatch": float(events)}


def concat_mismatch(requests) -> int:
    """Finished requests whose cube is not its chunks side by side."""
    bad = 0
    for r in requests:
        if r.done is None or r.error is not None:
            continue
        grid = getattr(r.result, "grid", r.result)
        chunks = [ch for _, _, ch in r.chunks]
        for name in SURFACES:
            full = getattr(grid, name)
            parts = np.concatenate([getattr(ch, name) for ch in chunks],
                                   axis=1)
            if not np.array_equal(full, parts, equal_nan=True):
                bad += 1
                break
    return bad


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; correct when none exceeds it."""
    table = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    return all(v <= limits[k] for k, v in numbers.items()), table
