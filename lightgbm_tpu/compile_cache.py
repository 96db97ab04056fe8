"""Where JAX's persistent compilation cache lives (one rule, one place).

The grower and chunk programs take tens of seconds to compile, so every
process after the first should start hot.  The directory is part of the
cache key's surroundings: a path that moves never hits.  Rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets nothing;
- otherwise ``<checkout>/.jax_cache`` — fixed, git-ignored, kept out of
  the chip tool's copy (``.chiprunignore``), never a temporary name.
"""
from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def configure(min_compile_secs: float = 1.0) -> None:
    """Apply the rule above (``jax.config.jax_compilation_cache_dir``
    then names the directory in force either way)."""
    if os.environ.get(CACHE_ENV):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
