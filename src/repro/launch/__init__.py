"""Launchers: production mesh, multi-pod dry-run, train/serve/rightsize."""

import os
from pathlib import Path

# a fixed path inside the checkout: the path is part of the cache key,
# so a cache that moves between runs never hits
_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Keep JAX's persistent compilation cache in ``<checkout>/.jax_cache``.

    Entry points call this before their first compile; importing the
    library sets nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already keeps the cache there and this sets no other directory.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(_CACHE_DIR))
