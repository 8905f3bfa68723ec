"""The benchmark's data: ``BENCHMARK.json``, the configuration and
traffic files a cell names, and the per-layer metric readers, all found
by name so that a new cell needs new files and no new code."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]        # the metrics this cell reports
    per_layer: list[dict]


def _applies(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # without a list, a per-layer metric goes with every cell that
    # reports the end-to-end metric it moves
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(one of {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


def reader(metric: str, root: pathlib.Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def decode(value, types: dict):
    """Traffic data to objects: ``{"$type": name, ...}`` becomes
    ``types[name](...)``, lists become tuples and ``"inf"`` becomes
    infinity, recursively."""
    if isinstance(value, dict):
        fields = {k: decode(v, types) for k, v in value.items()
                  if k != "$type"}
        return types[value["$type"]](**fields) if "$type" in value \
            else fields
    if isinstance(value, list):
        return tuple(decode(v, types) for v in value)
    if value == "inf":
        return math.inf
    return value
