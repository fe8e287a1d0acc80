"""Where the entry points keep JAX's persistent compilation cache.

A cold process on the chip otherwise recompiles every program it runs.
``use_compile_cache()`` is called from the ``main()`` of each entry point
(``repro.launch.serve``, ``repro.launch.train``, ``chip_smoke.py``) and
never at import, so tests and library users keep JAX's own default.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache: a fixed path (never a temporary name, pid or time), so
# later runs from the same checkout find the entries; git ignores it.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads the
    variable itself and nothing here overrides it.  Otherwise the cache
    goes to ``CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
