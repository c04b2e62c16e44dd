"""Persistent XLA compilation cache placement for process entry points.

Called from ``main``-level code only (the daemon CLI and
``scripts/tpu_smoke.py``) — never at library import, so an embedding
application keeps its own cache policy.

The directory is part of every cache key, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself; nothing here
touches the directory setting then), otherwise the cache lives at the fixed,
git-ignored ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` — next to the package, not under a temp name
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and return
    its directory.  Call before the first compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # JAX stores only executables that took >= 1.0 s to compile by default;
    # the exchange and gather compile well under that, so nothing would ever
    # be written.  Store everything.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
