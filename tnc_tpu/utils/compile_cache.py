"""Where JAX's persistent compilation cache lives.

One rule, shared by every program of this repo that compiles for a
device (``chip_smoke.py``, ``bench.py``): a cache directory placed from
outside wins, and otherwise the directory is a fixed path inside the
checkout. The path is part of the cache's key, so it never carries a
temporary name, a pid or a time.

>>> DEFAULT_CACHE_DIR.endswith(os.path.join(".cache", "jax_cache"))
True
"""

from __future__ import annotations

import os

#: ``<checkout>/.cache/jax_cache`` (``.cache/`` is git-ignored)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".cache",
    "jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX's own reading of it
    stands and no directory is set here; without it the directory is
    :data:`DEFAULT_CACHE_DIR`.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir
