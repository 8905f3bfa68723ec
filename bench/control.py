"""The lower-precision control of a cell's comparison, at the cell's own
size: the plain reference computed in float32, put in the program's
place, against the reference in float64, on the scenarios a run would
sample. Prints one JSON line of compared numbers per seed; a limit is
sound only if every seed's line breaks at least one of them.

    python bench/control.py --workload <cell> --seeds 1,2,3

The benchmark's own runs never run this. It needs no accelerator: the
reference runs on the host.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT)]


def readings(cell, seed: int) -> dict:
    import numpy as np

    from bench.harness import check, program

    tr = cell.traffic
    ref = check.Reference(cell.config, tr)
    n = int(tr["seeds_per_request"])
    # the scenarios of the first requests of a run of this seed, sampled
    # as a run samples what landed
    pool = [(s, c) for i in (1, 2)
            for s in program.request_seeds(seed, i, n)
            for c in range(len(ref.rows))]
    rng = np.random.default_rng(np.random.SeedSequence((int(seed), 2)))
    idx = rng.choice(len(pool), size=min(int(tr["check"]["scenarios"]),
                                         len(pool)), replace=False)
    picks = [check.Pick(pool[i][0], pool[i][1], {}) for i in sorted(idx)]
    t0 = time.perf_counter()
    want = [ref.values(p) for p in picks]
    got = [ref.values(p, np.float32) for p in picks]
    numbers = check.compare(got, want, ref.horizon)
    numbers.update(seed=seed, scenarios=len(picks),
                   seconds=time.perf_counter() - t0)
    return numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from bench.harness.data import load_cell

    cell = load_cell(args.workload)
    for s in args.seeds.split(","):
        print(json.dumps(readings(cell, int(s))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
