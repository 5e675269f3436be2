"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phases (simulate to a backlog, stop after N ticks there, rank the arena by
kernel and oracle) work at a tiny size through the Pallas interpreter."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, where):
    script = ROOT / "chip_smoke.py"
    if where == "alone":      # a directory holding the script and nothing else
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(script), "--backlog", "32",
                        "--ticks", "1"], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "chip_smoke: FAILED" in r.stderr
    assert ("no TPU" if where == "checkout" else "no repro package") \
        in r.stderr
    assert '"ok"' not in r.stdout


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_at_tiny_backlog(smoke):
    """The one-chip phases on CPU: the run stops after the asked ticks at
    the backlog, and the interpreted kernel ranks the arena with the
    oracle's exact bits."""
    kb = smoke.knowledge_base(7)
    insts = smoke.trace(40, 7)
    sim, res, st = smoke.run_backlog(kb, insts, 7, backlog=32, ticks=2,
                                     max_events=100_000,
                                     clock=smoke.compile_clock())
    assert st["peak_live"] >= 32
    assert st["ticks_at_backlog"] == 2
    assert sim.now == st["last_tick"]          # stopped on the second tick
    n_rows, cmp, dispatch = smoke.oracle_check(sim.sched, interpret=True)
    assert n_rows == len(sim.sched._live)
    assert cmp == {"ranks": (0, 0), "probs": (0, 0), "edges": (0, 0)}
    assert dispatch == ("pallas", True)
