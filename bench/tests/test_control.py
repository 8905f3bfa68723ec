"""The comparison that decides `correct`, at a size the CPU holds: the
program agrees with the plain reference within every limit, and the
control, the reference computed in float32 in the program's place,
breaks at least one limit of each cell."""
import dataclasses

import numpy as np
import pytest

from bench.harness import check, program
from bench.tests import tiny

CELLS = ["q12_fleet.replication", "drill_fleet.gate_open"]
SEEDS = [3, 2**31 + 17, 977]


def _program_cube(cell):
    from repro.streams import chaos_sweep

    kw = program.request_kwargs(cell.traffic)
    kw.pop("devices", None)
    return getattr(chaos_sweep, cell.traffic["kind"])(
        program.arena(cell.config), SEEDS, seed_chunk=2, **kw).grid


@pytest.mark.parametrize("name", CELLS)
def test_program_passes_and_float32_control_fails(name):
    cell = tiny.cell(name, kill_prob=0.01)
    limits = cell.traffic["check"]["limits"]
    grid = _program_cube(cell)
    ref = check.Reference(cell.config, cell.traffic)
    got, want, control = [], [], []
    for c in range(len(grid.configs)):
        for s, seed in enumerate(SEEDS):
            sm = grid.results[c].summaries[s]
            g = {k: getattr(sm, k) for k in check.FLOW_KEYS
                 + check.EVENT_KEYS
                 + ("recovery_time_s", "slo_violation_ticks")}
            g["rollback_t"] = float(grid.rollback_surface[c][s])
            pick = check.Pick(seed, c, g)
            got.append(g)
            want.append(ref.values(pick))
            control.append(ref.values(pick, np.float32))
    ok, table = check.verdict(check.compare(got, want, ref.horizon),
                              {k: limits[k] for k in check.compare(
                                  [], [], 0.0)})
    assert ok, table
    bad, table = check.verdict(check.compare(control, want, ref.horizon),
                               {k: limits[k] for k in check.compare(
                                   [], [], 0.0)})
    assert not bad, table
