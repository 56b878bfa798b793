"""Where JAX keeps its persistent compilation cache.

The entry points (``chip_smoke.py``, ``launch/serve.py``,
``launch/bench_serve.py``, ``cli.py``) call ``setup_compilation_cache``
before their first compile; importing this module changes nothing.  JAX
reads ``JAX_COMPILATION_CACHE_DIR`` itself, so when it is set the cache
goes there and no other directory is configured.  Otherwise the cache
lives at one fixed path inside the checkout, ``<repo>/.jax_cache``: the
directory is part of every entry's key, so a path that moved between runs
would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
