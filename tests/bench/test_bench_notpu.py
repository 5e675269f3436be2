"""The harness refuses to measure without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("where", ["checkout", "benchmark_only"])
def test_refuses_without_tpu_or_program(tmp_path, where):
    cwd = ROOT
    if where == "benchmark_only":
        cwd = tmp_path / "co"
        cwd.mkdir()
        shutil.copy(ROOT / "BENCHMARK.json", cwd)
        shutil.copytree(ROOT / "bench", cwd / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "testbed.steady", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert ("no TPU" if where == "checkout" else "no program") in r.stderr
