"""``repro.compile_cache``: where the persistent compile cache lives.

Each case runs in a fresh interpreter so the global JAX config of the
test process is left alone.
"""
import json
import os
import subprocess
import sys

import pytest

from conftest import SRC

PROBE = r"""
import json, sys
import jax, jax.numpy as jnp
from repro import compile_cache
cache_dir = compile_cache.enable_compile_cache()
if sys.argv[1] == "compile":
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(3.0)).block_until_ready()
print(json.dumps({"dir": cache_dir,
                  "default": str(compile_cache.DEFAULT_CACHE_DIR)}))
"""


def _probe(env_dir, action):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", PROBE, action], env=env,
                         capture_output=True, text=True, timeout=300,
                         check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_dir_stands_and_small_compiles_are_cached(tmp_path):
    got = _probe(tmp_path, "compile")
    assert got["dir"] == str(tmp_path)
    # a sub-second compile is written, next to nothing else
    assert any(tmp_path.iterdir())


@pytest.mark.parametrize("env_dir", [None, ""])
def test_default_dir_is_fixed_in_the_checkout(env_dir):
    got = _probe(env_dir, "none")
    assert got["dir"] == got["default"]
    assert got["default"] == os.path.join(os.path.dirname(SRC), ".jax_cache")
