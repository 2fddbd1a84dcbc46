"""The compile-cache helper of the entry points, and imports that touch no
JAX backend."""
import os
import subprocess
import sys

from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# compiles one function with the helper's cache; DEFAULT_CACHE_DIR is
# pointed at a test directory so the checkout's own cache stays untouched
_COMPILE = """
import sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
compile_cache.DEFAULT_CACHE_DIR = sys.argv[1]
print(compile_cache.enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.ones(8)).block_until_ready()
"""


def _run(code, *args, env_update=None, drop=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.update(env_update or {})
    for k in drop:
        env.pop(k, None)
    return subprocess.run([sys.executable, "-c", code, *args], env=env,
                          capture_output=True, text=True, timeout=120)


def test_default_cache_dir_is_fixed_in_checkout():
    assert compile_cache.DEFAULT_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cache_lands_in_env_dir_when_set(tmp_path):
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    r = _run(_COMPILE, str(default_dir),
             env_update={compile_cache.CACHE_ENV: str(env_dir)})
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(env_dir)
    assert any(env_dir.iterdir())
    assert not default_dir.exists()


def test_cache_lands_in_default_dir_when_unset(tmp_path):
    default_dir = tmp_path / "default"
    r = _run(_COMPILE, str(default_dir), drop=(compile_cache.CACHE_ENV,))
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(default_dir)
    assert any(default_dir.iterdir())


def test_importing_repro_initialises_no_backend():
    code = """
import importlib, pkgutil
import repro
from jax._src import xla_bridge
for m in pkgutil.walk_packages(repro.__path__, "repro."):
    importlib.import_module(m.name)
    assert not xla_bridge._backends, m.name
print("clean")
"""
    r = _run(code)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "clean"
