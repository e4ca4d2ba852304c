"""Where JAX keeps its persistent compilation cache.

Each entry point (``chip_smoke.py``, ``launch.train``,
``launch.sweeps``, ``benchmarks/run.py``) calls
:func:`use_compile_cache` before its first compile; importing this
module sets nothing. With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads
the variable itself and this sets no other path. Otherwise the cache
lives at ``<checkout>/.jax_cache`` — one fixed path (git-ignored), so a
second run of the same program finds what the first one compiled. On
the CPU backend the cache stays off: XLA:CPU warns on every reload that
the cached code may not match the host's instruction set.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str | None:
    """Point JAX's persistent cache at its one place; returns the path
    (None: no cache). Must run before the process compiles anything."""
    import jax

    if os.environ.get(ENV):
        return os.environ[ENV]
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
