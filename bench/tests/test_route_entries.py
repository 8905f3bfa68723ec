"""The reader of the grid program's device time per routed destination
entry: device time over executions times the entries one execution
routes, and nothing read where the program's chunks carry no
``route_entries`` (a program that does not count them)."""
import pytest

from bench.harness import data
from bench.tests.test_trace import _report

METRIC = "tick_ns_per_route_entry.batch"


class _Chunk:
    def __init__(self, **kw):
        self.__dict__.update(kw)


class _Run:
    def __init__(self, chunks, tr):
        self._chunks, self.trace = chunks, tr

    def chunks_in_window(self):
        return self._chunks


def test_device_time_over_runs_times_route_entries():
    # 10 s of grid time over both chips, 2 executions on the first chip
    run = _Run([_Chunk(route_entries=5_000), _Chunk(route_entries=5_000)],
               _report())
    assert data.reader(METRIC)(run) == pytest.approx(
        1e9 * 10.0 / (2 * 5_000))


@pytest.mark.parametrize("case", ["no_field", "no_trace", "no_chunks"])
def test_nothing_to_read_gives_none(case):
    run = {"no_field": _Run([_Chunk(prep_s=1.0)], _report()),
           "no_trace": _Run([_Chunk(route_entries=5_000)], None),
           "no_chunks": _Run([], _report())}[case]
    assert data.reader(METRIC)(run) is None
