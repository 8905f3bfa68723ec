"""The system under test, built from the cell's data: the packed fleet,
the request's driver arguments, and the seeds of each request."""
from __future__ import annotations

import numpy as np

from bench.harness.data import decode


def types() -> dict:
    """The program's classes that traffic files name by ``$type``."""
    from repro.core.chaos import ChaosSpec
    from repro.core.startup import StartupConfig
    from repro.streams.engine import (CheckpointConfig, FailoverConfig,
                                      UpgradeConfig)
    return {c.__name__: c for c in (ChaosSpec, StartupConfig,
                                    CheckpointConfig, FailoverConfig,
                                    UpgradeConfig)}


def arena(config: dict):
    """The configuration's jobs packed into one arena."""
    from repro.streams.engine import pack_arena
    from repro.streams.graph import LogicalEdge, LogicalGraph, LogicalOp

    graphs = {name: LogicalGraph(name,
                                 tuple(LogicalOp(**o) for o in g["ops"]),
                                 tuple(LogicalEdge(**e) for e in g["edges"]))
              for name, g in config["graphs"].items()}
    pattern = config["job_pattern"]
    jobs = [graphs[pattern[j % len(pattern)]]
            for j in range(int(config["n_jobs"]))]
    return pack_arena(jobs, config.get("host_map", "shared"),
                      n_hosts=int(config["n_hosts"]),
                      dt=float(config["dt"]),
                      queue_cap=float(config["queue_cap"]))


def request_kwargs(traffic: dict) -> dict:
    """Keyword arguments of every request of the mix."""
    t = types()
    kw = dict(decode(traffic["args"], t),
              base_spec=decode(traffic["base_spec"], t),
              duration_s=float(traffic["horizon_s"]))
    if traffic.get("devices") is not None:
        kw["devices"] = int(traffic["devices"])
    return kw


def request_seeds(seed: int, index: int, n: int) -> list[int]:
    """The chaos seeds of request `index` of a run: a block of its own,
    drawn from the run's seed, so no two requests replay one scenario."""
    return [int(s) for s in np.random.SeedSequence(
        (int(seed), int(index))).generate_state(n, dtype=np.uint32)]


def open_schedule(rate_per_s: float, seconds: float, order_seed: int
                  ) -> np.ndarray:
    """Due times in ``[0, seconds)`` of an open loop at `rate_per_s`:
    the ``n = rate * seconds`` quantiles of the exponential gap, in the
    order `order_seed` draws. The traffic file fixes `order_seed`, so
    every run offers the same arrivals and the run's seed changes only
    what the requests ask (their chaos seeds): at 80% load the order of
    arrivals alone moves a p90 by a third from one order to the next."""
    n = max(1, int(round(rate_per_s * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate_per_s
    gaps *= seconds / gaps.sum()
    rng = np.random.default_rng(int(order_seed))
    due = np.cumsum(rng.permutation(gaps)) - gaps.min() / 2
    return due[due < seconds]
