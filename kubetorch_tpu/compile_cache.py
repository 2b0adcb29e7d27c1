"""Where JAX's persistent compilation cache lives.

One rule for every process that compiles (rank workers, ``bench.py``, the
OpenAI server, the warm-template process and the replicas it forks): a
``JAX_COMPILATION_CACHE_DIR`` set from outside is left alone, and no other
directory is ever set in code; unset, the cache is one fixed directory inside
the checkout. The path is part of JAX's cache key, so it must not move between
processes or runs — never a temp name, a pid or a time.

jax reads the variable once, at import: call :func:`ensure_compile_cache`
before the process first imports jax, or at the latest before its first
compile. This module itself must stay jax-free.
"""

from __future__ import annotations

import os
import sys

ENV = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache (git-ignored)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def ensure_compile_cache() -> str:
    """Point jax at the persistent compile cache; returns the directory."""
    path = os.environ.setdefault(ENV, DEFAULT_DIR)
    jax = sys.modules.get("jax")
    if jax is not None and jax.config.jax_compilation_cache_dir != path:
        # imported before the variable was placed (``python -m`` of a module
        # whose package imports jax): it was read at import, so tell the
        # live config — effective until the first compile
        jax.config.update("jax_compilation_cache_dir", path)
    return path
