"""Cells, configurations, traffic mixes and metric readers are found by
name, so a new one needs files and a `workloads` entry, not code; and
the reference lays a configuration's fleet out as the program does."""
import json
import math
import shutil

import numpy as np
import pytest

from bench.harness import data, program
from bench.reference import model
from bench.reference.fleet import fleet as ref_fleet

CONFIGS = ["q12_fleet", "drill_fleet"]


def test_every_cell_of_the_benchmark_loads():
    bench = json.loads((data.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = data.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(data.reader(m["name"]))
        assert set(cell.traffic["check"]["limits"]) >= {
            "flow_rel_err", "recovery_gap_s", "event_mismatch"}


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A later change adds a configuration, a mix and a per-layer metric
    as files plus entries in BENCHMARK.json; nothing else is edited."""
    shutil.copytree(data.BENCH, tmp_path / "bench")
    bench = json.loads((data.ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((data.BENCH / "configs" / "q12_fleet.json").read_text())
    cfg.update(name="q12_small", n_jobs=3)
    (tmp_path / "bench" / "configs" / "q12_small.json").write_text(
        json.dumps(cfg))
    mix = json.loads((data.BENCH / "traffic" / "gate_open.json")
                     .read_text())
    mix["loop"] = {"type": "open", "rate_per_s": 0.5, "order_seed": 1}
    (tmp_path / "bench" / "traffic" / "trickle.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "requests_seen.py").write_text(
        "def read(run):\n    return len(run.requests)\n")
    bench["configs"].append({"name": "q12_small", "source": "x",
                             "file": "bench/configs/q12_small.json",
                             "reduced": ["n_jobs"], "why": "x"})
    bench["workloads"].append({"name": "q12_small.trickle",
                               "config": "q12_small", "traffic": "trickle",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "requests_seen", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "service queue",
                               "moves": "setup_s",
                               "workloads": ["q12_small.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = data.load_cell("q12_small.trickle", root=tmp_path)
    assert cell.config["n_jobs"] == 3
    assert cell.traffic["loop"]["rate_per_s"] == 0.5
    assert [m["name"] for m in cell.per_layer] == [
        "setup_compile_s", "requests_seen"]
    assert data.reader("requests_seen", root=tmp_path)(
        type("R", (), {"requests": [1, 2]})()) == 2
    assert ref_fleet(cell.config).n_tasks == 72
    with pytest.raises(KeyError):
        data.load_cell("no_such.cell", root=tmp_path)


def test_decode_builds_typed_objects_tuples_and_infinity():
    got = data.decode({"a": {"$type": "CheckpointConfig", "interval_s": 10},
                       "b": [[1, 2], "inf"]}, model.TYPES)
    assert got == {"a": model.CheckpointConfig(interval_s=10),
                   "b": ((1, 2), math.inf)}


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_fleet_is_laid_out_as_the_program_packs_it(name):
    cfg = json.loads((data.BENCH / "configs" / f"{name}.json").read_text())
    arena = program.arena(cfg)
    ref = ref_fleet(cfg)
    assert ref.n_tasks == arena.plan.n_tasks
    assert ref.n_hosts == arena.n_hosts
    host = np.concatenate([ref.templates[t].local_host
                           for t in ref.job_template])
    np.testing.assert_array_equal(
        host, [tk.host for tk in arena.phys.tasks])
    regions = [j.region_hi - j.region_lo for j in arena.jobs]
    assert regions == [ref.templates[t].n_regions for t in ref.job_template]


def test_q12_config_is_the_programs_q12_arena():
    from repro.streams import nexmark

    cfg = json.loads((data.BENCH / "configs" / "q12_fleet.json").read_text())
    ours = program.arena(cfg)
    theirs = nexmark.q12_arena(n_tasks=10_000)
    assert ours.graph == theirs.graph
    assert ours.n_hosts == theirs.n_hosts
    assert ours.plan.dt == theirs.plan.dt
    assert ours.plan.queue_cap == theirs.plan.queue_cap


def test_drill_config_is_the_programs_drill_fleet():
    """The program's drill fleet laid out alike (jobs, operators,
    parallelism, partitioners, hosts, queues), with Q3 on NEXMark's event
    mix and filters: persons to auctions 1:3, 3 of 6 states, 1 of 5
    categories, and one joined row per kept auction with a kept seller."""
    from repro.streams import nexmark

    cfg = json.loads((data.BENCH / "configs" / "drill_fleet.json")
                     .read_text())
    ours = program.arena(cfg)
    theirs = nexmark.drill_fleet(n_jobs=8, queue_cap=1e9)
    assert ours.n_hosts == theirs.n_hosts
    assert ours.plan.queue_cap == theirs.plan.queue_cap
    assert [tk.host for tk in ours.phys.tasks] == [
        tk.host for tk in theirs.phys.tasks]
    assert [j.graph.name for j in ours.jobs] == [
        j.graph.name for j in theirs.jobs]
    q3 = {o["name"]: o for o in cfg["graphs"]["nexmark_q3"]["ops"]}
    assert q3["auctions"]["source_rate"] == 3 * q3["persons"]["source_rate"]
    assert (q3["filter_p"]["selectivity"], q3["filter_a"]["selectivity"]) \
        == (0.5, 0.2)
    kept = (0.5 * q3["persons"]["source_rate"]
            + 0.2 * q3["auctions"]["source_rate"])
    joined = 0.2 * q3["auctions"]["source_rate"] * 0.5
    assert q3["join"]["selectivity"] == pytest.approx(joined / kept)
    assert q3["sink"]["service_rate"] == pytest.approx(
        1.2 * joined / q3["sink"]["parallelism"])
    for name, g in cfg["graphs"].items():
        prog = next(j.graph for j in theirs.jobs if j.graph.name == name)
        assert [(o["parallelism"], o.get("is_source", False))
                for o in g["ops"]] == [(o.parallelism, o.is_source)
                                       for o in prog.ops]
        assert [(e["partitioner"], e.get("key_skew_zipf", 0.0))
                for e in g["edges"]] == [(e.partitioner, e.key_skew_zipf)
                                         for e in prog.edges]
