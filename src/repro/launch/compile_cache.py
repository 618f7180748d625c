"""Where an entry point keeps JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins: no
other directory is configured.  Otherwise the cache lives at the fixed
``<repo>/.jax_cache`` (listed in ``.gitignore``).  The directory is part of
what makes a later run find an entry, so it is never derived from a
temporary name, a pid or the time.

Only entry points (``chip_smoke.py``, the launchers) call
:func:`use_compile_cache`, before their first compile; importing a library
module never touches the cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Every program is cached, however quick its compile: a smoke run
    compiles many small programs, and a rerun should find them all."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
