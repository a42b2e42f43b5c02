"""repro.launch.compile_cache: where the persistent compile cache lives,
and that a second identical run is served from it."""
import os
import subprocess
import sys

import jax

from repro.launch import compile_cache

RUN_TWICE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import cache_stats, enable_compile_cache
print(enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
print(cache_stats()["hits"])
"""


def test_env_var_wins_and_nothing_is_set_in_code(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    expected = os.path.join(root, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == expected
        assert jax.config.jax_compilation_cache_dir == expected
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_second_identical_run_hits_the_cache(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep \
        + env.get("PYTHONPATH", "")
    hits = []
    for _ in range(2):
        proc = subprocess.run([sys.executable, "-c", RUN_TWICE],
                              capture_output=True, text=True, timeout=300,
                              env=env)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        lines = proc.stdout.split()
        assert lines[0] == str(tmp_path)
        hits.append(int(lines[-1]))
    assert hits[0] == 0 and hits[1] >= 1, hits
    assert os.listdir(tmp_path)
