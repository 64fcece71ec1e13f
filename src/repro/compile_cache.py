"""Where the entry points keep JAX's persistent compilation cache.

``enable_compile_cache()`` is called by the serving CLI, the benchmark
runner and ``chip_smoke.py`` — never on ``import repro``, so library users
and the ahead-of-time compile tests stay cache-free.
"""
from __future__ import annotations

import os
from pathlib import Path

# A fixed path inside the checkout: the cache key includes nothing of the
# path, but a directory that moves between runs is never found again.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn the persistent cache on at ``$JAX_COMPILATION_CACHE_DIR`` if
    set, else at ``<checkout>/.jax_cache``; cache every compile, however
    short (the Pallas kernels compile in well under a second each)."""
    import jax

    path = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def cache_bytes(path: Path) -> int:
    """Bytes currently held in the cache directory."""
    if not path.is_dir():
        return 0
    return sum(f.stat().st_size for f in path.rglob("*") if f.is_file())
