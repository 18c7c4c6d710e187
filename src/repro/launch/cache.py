"""JAX's persistent compilation cache at a fixed path.

The cache is keyed on the directory as well as the program, so it only ever
hits when the path is the same from one run to the next: never a temporary
directory, a pid or a time stamp.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: ``<repo>/.jax_cache`` — gitignored; used when the environment names none.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / '.jax_cache'


def use_compile_cache() -> None:
    """Turn the persistent compilation cache on. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
    nothing; otherwise the cache goes to :data:`REPO_CACHE_DIR`."""
    if 'JAX_COMPILATION_CACHE_DIR' not in os.environ:
        jax.config.update('jax_compilation_cache_dir', str(REPO_CACHE_DIR))
