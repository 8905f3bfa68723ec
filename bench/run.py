"""Benchmark of the release-gate sweep service on one TPU host.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` (a fleet configuration under a
traffic mix, both files named by the cell) against
`repro.launch.serve.SweepService` on the chips JAX finds, and prints as
its last line one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics read from a profiler trace of the window), ``device``
and, last, ``check``: every number compared with the reference beside
its limit. Exits non-zero, printing no result, where JAX finds no TPU
or fewer chips than the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness.data import load_cell
    from bench.harness.driver import NoChip, result_line, run

    cell = load_cell(args.workload)
    try:
        out, measured = run(cell, args.seed, args.seconds,
                            bool(args.trace), T_PROCESS)
    except NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    print(result_line(out, measured, bool(args.trace)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
