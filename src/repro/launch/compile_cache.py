"""JAX persistent compilation cache for the command-line entry points.

``enable()`` is called by ``chip_smoke.py``, ``launch/serve.py`` ``main`` and
``benchmarks/bench_selfjoin.py`` ``main`` -- never at ``import repro``, so
library users and the tests keep JAX's own default (no persistent cache).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it at start-up and it
alone decides where the cache lives; nothing here overrides it. Otherwise
the cache goes to ONE fixed directory inside the checkout,
``<repo>/.jax_cache`` (listed in ``.gitignore``). The path is part of what
a later run must find again, so it is never a temp, pid or time-based one.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
