"""Placement of the persistent compilation cache set by the entry points.

Each case runs in a child process: the cache settings are process-wide JAX
config, and this worker's other tests must not start writing a cache.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_default_dir_is_in_the_checkout():
    assert compile_cache.DEFAULT_CACHE_DIR == ROOT / ".jax_cache"


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_entries_land_in_one_place(tmp_path, env_set):
    env_dir, default_dir = tmp_path / "from_env", tmp_path / "default"
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        from pathlib import Path
        from repro.launch import compile_cache

        compile_cache.DEFAULT_CACHE_DIR = Path({str(default_dir)!r})
        print(compile_cache.enable_compile_cache())
        jax.jit(lambda a: jnp.sin(a) @ a.T)(jnp.ones((8, 8))).block_until_ready()
    """)
    env = {k: v for k, v in os.environ.items() if k != compile_cache.CACHE_ENV}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    if env_set:
        env[compile_cache.CACHE_ENV] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env, cwd=tmp_path)
    assert r.returncode == 0, r.stderr[-4000:]
    used, unused = (env_dir, default_dir) if env_set else (default_dir, env_dir)
    assert r.stdout.strip().splitlines()[-1] == str(used)
    assert used.is_dir() and any(used.iterdir())
    assert not unused.exists()
