"""Placement of JAX's persistent compilation cache by the entry points
(``repro.utils.compile_cache.enable_compile_cache``). Each case runs in a
fresh interpreter: JAX reads ``JAX_COMPILATION_CACHE_DIR`` at start-up."""
import os
import pathlib
import subprocess
import sys

from repro.utils import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]

SCRIPT = """
import pathlib, sys
import jax, jax.numpy as jnp
from repro.utils import compile_cache
compile_cache.DEFAULT_DIR = pathlib.Path(sys.argv[1])
print(compile_cache.enable_compile_cache())
# keep even this sub-second compile, so the test sees where entries go
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(7)).block_until_ready()
"""


def _run(tmp_path, env_dir):
    env = dict(os.environ)
    env.pop(compile_cache.ENV, None)
    if env_dir is not None:
        env[compile_cache.ENV] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(tmp_path / "default")],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_cache_written_where_the_env_says(tmp_path):
    env_dir = tmp_path / "env"
    assert _run(tmp_path, env_dir) == str(env_dir)
    assert any(env_dir.iterdir())
    assert not (tmp_path / "default").exists()


def test_cache_defaults_to_a_fixed_dir(tmp_path):
    assert _run(tmp_path, None) == str(tmp_path / "default")
    assert any((tmp_path / "default").iterdir())


def test_default_dir_is_git_ignored_in_the_checkout():
    assert compile_cache.DEFAULT_DIR == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored or ".jax_cache" in ignored
