"""JAX's persistent compilation cache, placed from outside the program.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX keeps its cache there and
nothing else is configured. Otherwise the cache lives at the fixed
`<checkout>/.jax_cache`: the directory is part of what makes a later run
find an entry, so it never takes a temporary name, a process id or a time.
Every compile is cached, however short: JAX's default floor of one second
would leave out most of the serving programs, which compile in about that.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> Path:
    """Point JAX's persistent cache at its directory and return it. Call at
    start-up, before the first compile."""
    path = Path(os.environ.get(ENV_VAR) or CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
