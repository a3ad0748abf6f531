"""Where the entry points keep JAX's persistent compilation cache:
``$JAX_COMPILATION_CACHE_DIR`` when it is set, else ``<checkout>/.jax_cache``,
and nowhere else.  Each case compiles in a fresh process, since the cache is
process-wide configuration."""
import os
import pathlib
import subprocess
import sys

import pytest

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]

# the checkout path is redirected into tmp_path so the test writes nothing
# into the repository
SCRIPT = """
import pathlib, sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
compile_cache.CHECKOUT_CACHE = pathlib.Path(sys.argv[1])
print(compile_cache.enable_compile_cache())
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def test_checkout_cache_is_a_fixed_path_in_the_checkout():
    assert compile_cache.CHECKOUT_CACHE == REPO / ".jax_cache"


@pytest.mark.parametrize("env_set", [True, False],
                         ids=["env-dir", "checkout-dir"])
def test_compiles_land_in_one_cache_dir(tmp_path, env_set):
    env_dir, checkout_dir = tmp_path / "env", tmp_path / "checkout"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO / "src"), env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", SCRIPT, str(checkout_dir)],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    used, unused = ((env_dir, checkout_dir) if env_set
                    else (checkout_dir, env_dir))
    assert out.stdout.strip() == str(used)
    assert any(used.iterdir())
    assert not unused.exists()
