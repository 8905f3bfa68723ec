"""The fleet-wide release drill, ``q3q11_fleet.drill``, at a size the CPU
holds (4 jobs: 2 Q3 and 2 Q11): the program agrees with the reference
and the float32 control does not, a broken timed path reads `correct`
false, the configuration is the drill fleet's graphs at 554 jobs, and the
four-chip q12 mix is the one-chip mix on four devices."""
import json

import pytest

from bench.harness import data, program
from bench.reference.fleet import fleet as ref_fleet
from bench.tests import test_control, test_data, test_faults

CELL = "q3q11_fleet.drill"


def _file(*parts) -> dict:
    return json.loads(data.BENCH.joinpath(*parts).read_text())


def test_program_passes_and_float32_control_fails():
    test_control.test_program_passes_and_float32_control_fails(CELL)


@pytest.mark.parametrize("fault", list(test_faults.FAULTS))
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    test_faults.test_broken_timed_path_is_not_correct(monkeypatch, fault,
                                                      CELL)


def test_config_is_the_drill_fleets_graphs_at_554_jobs():
    ours = _file("configs", "q3q11_fleet.json")
    drill = _file("configs", "drill_fleet.json")
    for key in ("graphs", "job_pattern", "nexmark", "guarantees", "source",
                "dt", "host_map", "queue_cap", "reduced"):
        assert ours[key] == drill[key], key
    assert len(ours["source"]) <= 200
    assert (ours["n_jobs"], ours["n_hosts"]) == (554, 64)
    arena = program.arena(ours)
    ref = ref_fleet(ours)
    assert arena.plan.n_tasks == ref.n_tasks == 9_972
    assert [j.graph.name for j in arena.jobs].count("nexmark_q3") == 277
    # under the shared host map only local hosts 0-23 carry tasks
    assert {tk.host for tk in arena.phys.tasks} == set(range(24))


def test_reference_fleet_is_laid_out_as_the_program_packs_it():
    test_data.test_reference_fleet_is_laid_out_as_the_program_packs_it(
        "q3q11_fleet")


def test_join_fleet_lowers_to_five_compact_phases():
    from repro.streams.jax_engine import _Lowered

    cfg = _file("configs", "q3q11_fleet.json")
    arena = program.arena(cfg)
    for width in (1, 96):
        low = _Lowered(arena, n_hosts=cfg["n_hosts"], dt=cfg["dt"],
                       queue_cap=cfg["queue_cap"], failover=None,
                       ckpt=None, seed=0, seed_width=width)
        assert low.tensor.mode == "compact"
        assert [ph.D for ph in low.tensor.phases] == \
               [3_324, 2_216, 1_108, 1_108, 0]


def test_four_chip_mix_is_the_one_chip_mix_on_four_devices():
    one = _file("traffic", "replication.json")
    four = _file("traffic", "replication.4chip.json")
    assert one["devices"] is None and four["devices"] == 4
    assert {k: v for k, v in four.items() if k != "devices"} == \
           {k: v for k, v in one.items() if k != "devices"}
    assert program.request_kwargs(four)["devices"] == 4
