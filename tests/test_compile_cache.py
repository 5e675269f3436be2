"""The entry points' persistent compile cache lands in
``JAX_COMPILATION_CACHE_DIR`` when it is set, and otherwise in the fixed
``.jax_cache/`` of the checkout — nowhere else.  Each case runs in its own
process against a copy of the module placed in a scratch checkout, so the
compile it makes writes into the test's directory, not this repository."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

MODULE = Path(__file__).resolve().parents[1] / "src/repro/compile_cache.py"
PROBE = """
import json, jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
used = enable_compile_cache()
jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()
print(json.dumps(used))
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_lands_in_one_place(tmp_path, env_set):
    checkout = tmp_path / "checkout"
    pkg = checkout / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    shutil.copy(MODULE, pkg / "compile_cache.py")
    env_dir = tmp_path / "from_env"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(checkout / "src"), JAX_PLATFORMS="cpu")
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", PROBE], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    want = env_dir if env_set else checkout / ".jax_cache"
    assert json.loads(r.stdout.strip().splitlines()[-1]) == str(want)
    assert any(want.iterdir())
    made = sorted(p.name for p in tmp_path.iterdir())
    assert made == sorted(["checkout", want.name] if env_set
                          else ["checkout"])
    assert sorted(p.name for p in checkout.iterdir()) == sorted(
        ["src"] if env_set else ["src", ".jax_cache"])
