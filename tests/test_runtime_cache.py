"""Where the persistent compile cache goes (common/runtime.py): one rule.

Every case runs `configure_jax_runtime` in a fresh interpreter — JAX reads
`JAX_COMPILATION_CACHE_DIR` at import, and the rule is about what a new
process does.
"""

import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SNIPPET = """
import json, sys, types
import jax
updates = []
real = jax.config.update
def spy(name, value):
    updates.append(name)
    real(name, value)
jax.config.update = spy
from elasticdl_tpu.common.runtime import configure_jax_runtime
returned = configure_jax_runtime(types.SimpleNamespace(
    compilation_cache_dir=sys.argv[1], compilation_cache_min_compile_s=-1.0))
print(json.dumps({"dir": jax.config.jax_compilation_cache_dir,
                  "returned": returned, "updates": updates}))
"""


def _configure(flag="", env_dir=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run(
        [sys.executable, "-c", _SNIPPET, flag], cwd=REPO_ROOT, env=env,
        capture_output=True, text=True, timeout=120, check=True)
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_environment_wins_and_nothing_is_updated(tmp_path):
    got = _configure(flag=str(tmp_path / "flag"), env_dir=str(tmp_path / "env"))
    assert got["dir"] == got["returned"] == str(tmp_path / "env")
    assert "jax_compilation_cache_dir" not in got["updates"]


def test_default_is_the_checkout_and_does_not_move():
    first, second = _configure(), _configure()
    assert first["dir"] == os.path.join(REPO_ROOT, ".jax_cache")
    assert second["dir"] == first["dir"] == first["returned"]


def test_flag_wins_over_the_default(tmp_path):
    got = _configure(flag=str(tmp_path / "flag"))
    assert got["dir"] == got["returned"] == str(tmp_path / "flag")
