"""Where the entry points keep JAX's persistent compilation cache."""
import os
import pathlib
import subprocess
import sys

from repro.launch import jitcache

REPO = pathlib.Path(__file__).resolve().parents[1]

_COMPILE_ONCE = """
import sys
import jax, jax.numpy as jnp
from repro.launch import jitcache
if len(sys.argv) > 1:
    jitcache.DEFAULT_DIR = sys.argv[1]
print(jitcache.use_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
"""


def _run(env_dir, *argv):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _COMPILE_ONCE, *argv],
                         env=env, capture_output=True, text=True,
                         timeout=120, check=True)
    return out.stdout.strip().splitlines()[-1]


def test_cache_lands_where_the_environment_says(tmp_path):
    target = tmp_path / "from_env"
    assert _run(target) == str(target)
    assert any(target.iterdir())


def test_cache_defaults_to_one_fixed_gitignored_path(tmp_path):
    assert jitcache.DEFAULT_DIR == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
    # unset, the entry point itself picks the default directory
    target = tmp_path / "default"
    assert _run(None, str(target)) == str(target)
    assert any(target.iterdir())
