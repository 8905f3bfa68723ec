"""Reduction of a profiler trace of the window (``.xplane.pb``) to the
device numbers: busy time as the union of the intervals in which an
operation ran on each chip, the grid program's executions and their
device time, and the breakdown of device operations and idle gaps.

On a TPU each chip is a plane ``/device:TPU:<n>``; its ``XLA Ops`` line
holds the operations and its ``XLA Modules`` line the program
executions. A TPU trace without those is refused, never read another
way. The CPU backend, on which the tests record their traces, has no
device planes: it records its operations on host threads, with an
``hlo_op`` and an ``hlo_module`` stat, and ``platform="cpu"`` reads them
as the operations of device ``device_ordinal``.
"""
from __future__ import annotations

import collections
import dataclasses
import pathlib
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TPU_PLANE = re.compile(r"/device:TPU:(\d+)")


class TraceError(RuntimeError):
    """The trace lacks what the reduction reads on this platform."""


@dataclasses.dataclass
class Event:
    name: str
    start: float                   # ns on the trace's clock
    end: float
    module: str = ""


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint cover of the given (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle stretches of [lo, hi] between busy intervals."""
    out, t = [], lo
    for s, e in union(busy):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Report:
    """What the readers and the result line take from one trace."""
    window_s: float
    ops: dict[int, list[Event]]            # device -> operations
    modules: dict[int, list[Event]]        # device -> program executions
    host: list[Event]                      # host-thread events
    lo: float                              # trace window on its clock
    hi: float

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(covered((e.start, e.end) for e in evs)
                   for evs in self.ops.values()) / len(self.ops) * 1e-9

    def _grid_module(self) -> str | None:
        """The program that held the devices longest: the tick grid."""
        total: collections.Counter = collections.Counter()
        for evs in self.modules.values():
            for e in evs:
                total[e.module or e.name] += e.end - e.start
        return total.most_common(1)[0][0] if total else None

    @property
    def grid_runs(self) -> int:
        """Executions of the grid program on the first device."""
        name = self._grid_module()
        if name is None:
            return 0
        first = min(self.modules)
        return sum((e.module or e.name) == name for e in self.modules[first])

    @property
    def grid_busy_s(self) -> float:
        """Device time of the grid program's executions, summed over
        the chips."""
        name = self._grid_module()
        return sum(covered((e.start, e.end) for e in evs
                           if (e.module or e.name) == name)
                   for evs in self.modules.values()) * 1e-9

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (seconds summed
        over chips) and the longest idle gaps of the first chip, each
        named by the host event that overlaps it most."""
        per_op: collections.Counter = collections.Counter()
        for evs in self.ops.values():
            for e in evs:
                per_op[e.name] += e.end - e.start
        ops = [[n, t * 1e-9] for n, t in per_op.most_common(top)]
        if not self.ops:
            return {"device_ops": ops, "idle_gaps": []}
        first = min(self.ops)
        idle = sorted(gaps([(e.start, e.end) for e in self.ops[first]],
                           self.lo, self.hi),
                      key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": ops,
                "idle_gaps": [[self._label(s, e), (e - s) * 1e-9]
                              for s, e in idle]}

    def _label(self, s: float, e: float) -> str:
        """The host event covering most of (s, e), among those shorter
        than the whole window (thread-long spans name nothing)."""
        best, name = 0.0, "no host event"
        span = self.hi - self.lo
        for h in self.host:
            if h.end - h.start >= 0.5 * span:
                continue
            ov = min(e, h.end) - max(s, h.start)
            if ov > best:
                best, name = ov, h.name
        return name


def _stat(ev, key):
    try:
        return dict(ev.stats).get(key)
    except (TypeError, ValueError):
        return None


def read(trace_dir, w0: float, w1: float, n_chips: int,
         platform: str = "tpu") -> Report:
    """Reduce the newest ``.xplane.pb`` under `trace_dir`, traced from
    host time `w0` to `w1`, over the first `n_chips` devices of
    `platform` ("tpu", or "cpu" for the host threads of a CPU trace)."""
    from jax.profiler import ProfileData

    pbs = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                 key=lambda p: p.stat().st_mtime)
    if not pbs:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(pbs[-1]))
    ops: dict[int, list[Event]] = {}
    modules: dict[int, list[Event]] = {}
    host: list[Event] = []
    cpu_ops: dict[int, list[Event]] = collections.defaultdict(list)
    runs: dict[tuple, list[Event]] = collections.defaultdict(list)
    planes = []
    for plane in data.planes:
        planes.append(plane.name)
        m = TPU_PLANE.fullmatch(plane.name)
        if m and platform == "tpu":
            dev = int(m.group(1))
            if dev >= n_chips:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[dev] = [Event(e.name, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      str(_stat(e, "hlo_module") or ""))
                                for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[dev] = [Event(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns,
                                          e.name)
                                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    op = _stat(e, "hlo_op") if platform == "cpu" else None
                    if op is not None:
                        dev = int(_stat(e, "device_ordinal") or 0)
                        ev = Event(str(op), e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   str(_stat(e, "hlo_module") or ""))
                        cpu_ops[dev].append(ev)
                        runs[(dev, ev.module, _stat(e, "run_id"))].append(ev)
                    elif e.duration_ns > 0:
                        host.append(Event(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
    if platform == "cpu":
        ops = {d: v for d, v in cpu_ops.items() if d < n_chips}
        # an execution of a module is the span of one run of its ops
        modules = collections.defaultdict(list)
        for (d, m, _), evs in runs.items():
            if d < n_chips:
                modules[d].append(Event(m, min(e.start for e in evs),
                                        max(e.end for e in evs), m))
        modules = dict(modules)
    want = set(range(n_chips))
    if set(ops) != want or set(modules) != want:
        raise TraceError(
            f"{platform} trace: want {OPS_LINE!r} and {MODULES_LINE!r} of "
            f"devices {sorted(want)}, found ops of {sorted(ops)} and "
            f"modules of {sorted(modules)} among planes {planes}")
    every = [e for evs in ops.values() for e in evs] + host
    lo = min((e.start for e in every), default=0.0)
    hi = max(lo + (w1 - w0) * 1e9, max((e.end for e in every),
                                       default=lo))
    return Report(w1 - w0, ops, modules, host, lo, hi)
