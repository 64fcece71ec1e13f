"""The entry points' persistent compile cache goes to
$JAX_COMPILATION_CACHE_DIR when it is set and to <checkout>/.jax_cache
otherwise; it is checked in fresh processes, since it is process-wide
JAX configuration."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
PROBE = """
import jax, jax.numpy as jnp
from repro.compile_cache import cache_bytes, enable_compile_cache
path = enable_compile_cache()
jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
print(path, jax.config.jax_compilation_cache_dir, cache_bytes(path))
"""


def _probe(env_dir):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(ROOT / "src"), env.get("PYTHONPATH")) if p)
    p = subprocess.run([sys.executable, "-c", PROBE], capture_output=True,
                       text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    path, configured, size = p.stdout.split()
    assert path == configured
    return Path(path), int(size)


def test_cache_goes_to_the_environment_directory(tmp_path):
    path, size = _probe(tmp_path / "cache")
    assert path == tmp_path / "cache"
    assert size > 0                       # the tiny jit was cached there


def test_cache_defaults_to_the_checkout():
    path, _ = _probe(None)
    assert path == ROOT / ".jax_cache"
