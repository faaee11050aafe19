"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``launch.train.run``,
``launch.serve.run``) call ``enable_compile_cache()`` before they compile;
importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

# <repo>/.jax_cache: a fixed path, because the path is part of the
# cache's key and a directory that moves never hits
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads
    it itself; otherwise the cache goes to ``DEFAULT_DIR``.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
