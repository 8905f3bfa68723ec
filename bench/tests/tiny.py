"""Cells of the benchmark shrunk to what a test on the CPU can hold:
fewer jobs, seeds and ticks, the same paths and the same limits. A cell
``<config>.<traffic>`` is built from its two files, whether or not
``BENCHMARK.json`` lists it."""
import json

from bench.harness import data


def cell(name: str, *, n_jobs: int = 4, seeds: int = 4, chunk: int = 2,
         horizon_s: float = 70.0, kill_prob: float | None = None):
    config, traffic = name.split(".", 1)
    c = data.Cell(name, 1,
                  json.loads((data.BENCH / "configs" / f"{config}.json")
                             .read_text()),
                  json.loads((data.BENCH / "traffic" / f"{traffic}.json")
                             .read_text()), [], [])
    c.config["n_jobs"] = n_jobs
    t = c.traffic
    t.update(seeds_per_request=seeds, seed_chunk=chunk,
             horizon_s=horizon_s)
    t["check"]["scenarios"] = 10_000       # every scenario that landed
    if kill_prob is not None:
        t["base_spec"]["host_kill_prob_per_s"] = kill_prob
    if t["loop"]["type"] == "open":
        t["loop"]["rate_per_s"] = 2.0
    return c
