"""`bench/run.py` measures nothing and prints no result where JAX finds
no TPU, and where the checkout holds only the benchmark's own files."""
import os
import shutil
import subprocess
import sys

import pytest

from bench.harness.data import ROOT


@pytest.mark.parametrize("only_bench", [False, True])
def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(
        tmp_path, only_bench):
    root = ROOT
    if only_bench:
        shutil.copytree(ROOT / "bench", tmp_path / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
        root = tmp_path
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "q12_fleet.replication", "--seed", str(2**31 + 3), "--seconds",
         "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    if not only_bench:
        assert "no TPU found" in p.stderr
