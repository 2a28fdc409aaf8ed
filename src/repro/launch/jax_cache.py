"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, the ``serve`` and ``train`` launchers,
the benchmark harness) call :func:`use_compile_cache` once before they
compile anything; importing the library never touches the cache.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and nothing
else is configured.  Otherwise the cache is ``<checkout>/.jax_cache``: a
fixed path, because the path is part of what makes a later process find
the entries again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    import jax
    path = os.environ.get(ENV) or str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
