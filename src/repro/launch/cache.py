"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set, so a deployment can
place the cache; otherwise it is ``<checkout>/.jax_cache``, one fixed
path, so that a later run from the same checkout finds what an earlier
one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
