"""Device time of the tick by step, from the ``jax.named_scope`` each
step of the dense and compact ticks runs under (``tick_setup``,
``tick_route1``, ...; `repro.streams.jax_engine.TICK_STEPS`), plus the
idle gaps named by the program's spans: a table for PERF.md, not a
metric.

    python -m bench.harness.phases .bench_trace/<cell> [--chips 1]

reads the newest ``.xplane.pb`` under the directory (a ``--trace 1`` run
of ``bench/run.py`` leaves it there) and prints one JSON object: device
seconds per step summed over chips, the device time of the longest
``while`` op (the grid program's scan), the idle gaps of the first chip
named by the trace's ``sweep.*`` spans, and the unspanned idle share.

An ``XLA Ops`` event is named after its HLO instruction; the scope is
that instruction's ``op_name``, read from the programs' HLO the
profiler keeps in its ``/host:metadata`` plane.
"""
from __future__ import annotations

import argparse
import collections
import json
import pathlib
import re

from bench.harness import spans, trace

STEP = re.compile(r"tick_[a-z]+\d*")
INSTRUCTION = re.compile(r"%?([^\s=]+)")


def step_of(text: str) -> str | None:
    """The tick step a scope path names (``.../tick_route1/...`` →
    ``tick_route1``), or None."""
    m = STEP.search(text)
    return m.group(0) if m else None


def step_seconds(ops) -> dict[str, float]:
    """Seconds of device time per tick step over ``(scope text, start
    ns, end ns)`` ops; ops outside every step go under ``unscoped``."""
    out: collections.Counter = collections.Counter()
    for text, s, e in ops:
        out[step_of(text) or "unscoped"] += (e - s) * 1e-9
    return dict(out)


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes):
    """``(field number, value)`` of each field of one protobuf message;
    length-delimited values as bytes."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 1:
            value, i = buf[i:i + 8], i + 8
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


def _first(buf: bytes, number: int) -> bytes:
    return next((v for k, v in _fields(buf) if k == number), b"")


def hlo_scopes(xspace: bytes) -> dict[str, str]:
    """HLO instruction name → its ``op_name`` over every program whose
    HLO the trace holds (``XSpace.planes`` → the ``/host:metadata``
    plane's event metadata → stat ``HloProto`` → ``hlo_module`` →
    computations → instructions → ``metadata.op_name``). Where programs
    share an instruction name, a name inside a tick step wins."""
    out: dict[str, str] = {}
    for number, plane in _fields(xspace):
        if number != 1 or _first(plane, 2) != b"/host:metadata":
            continue
        for key, entry in _fields(plane):
            if key != 4:                        # event_metadata map
                continue
            for k, stat in _fields(_first(entry, 2)):
                if k != 5:
                    continue
                module = _first(_first(stat, 6), 1)
                for kc, comp in _fields(module):
                    if kc != 3:
                        continue
                    for ki, ins in _fields(comp):
                        if ki != 2:
                            continue
                        name = _first(ins, 1).decode()
                        op = _first(_first(ins, 7), 2).decode()
                        if not step_of(out.get(name, "")):
                            out[name] = op
    return out


def _device_ops(data, n_chips: int, platform: str):
    """``(instruction name, start, end)`` of every device operation of
    the first `n_chips` devices, as `trace.read` finds them."""
    ops = []
    for plane in data.planes:
        m = trace.TPU_PLANE.fullmatch(plane.name)
        for line in plane.lines:
            if m and platform == "tpu" and int(m.group(1)) < n_chips \
                    and line.name == trace.OPS_LINE:
                ops += [(INSTRUCTION.match(e.name).group(1), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
            elif plane.name.startswith("/host:") and platform == "cpu":
                for e in line.events:
                    op = trace._stat(e, "hlo_op")
                    if op is not None and \
                            int(trace._stat(e, "device_ordinal") or 0) \
                            < n_chips:
                        ops.append((str(op), e.start_ns,
                                    e.start_ns + e.duration_ns))
    return ops


def steps(trace_dir, n_chips: int = 1, platform: str = "tpu") -> dict:
    """Device seconds per tick step and of the longest ``while`` op, from
    the newest trace under `trace_dir`."""
    from jax.profiler import ProfileData

    pbs = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                 key=lambda p: p.stat().st_mtime)
    if not pbs:
        raise trace.TraceError(f"no .xplane.pb under {trace_dir}")
    raw = pbs[-1].read_bytes()
    scope = hlo_scopes(raw)
    ops = _device_ops(ProfileData.from_serialized_xspace(raw), n_chips,
                      platform)
    whiles: collections.Counter = collections.Counter()
    for name, s, e in ops:
        if name.startswith("while"):
            whiles[name] += (e - s) * 1e-9
    return {"steps_s": step_seconds((scope.get(n, ""), s, e)
                                    for n, s, e in ops),
            "while_s": max(whiles.values(), default=0.0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    report = trace.read(args.trace_dir, 0.0, 0.0, args.chips)
    report.window_s = (report.hi - report.lo) * 1e-9
    print(json.dumps({
        **steps(args.trace_dir, args.chips),
        "idle_gaps": spans.named_gaps(report, spans.leaf_spans(report)),
        "device_idle_unspanned_frac": spans.unspanned_idle_frac(
            report, spans.leaf_spans(report)),
    }, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
