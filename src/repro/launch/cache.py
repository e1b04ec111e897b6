"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``python -m repro.launch.train``,
``benchmarks/run.py``) call :func:`configure_compile_cache` once, before
they compile anything.  Importing this module configures nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: that directory, and no other.
* Otherwise ``<checkout>/.jax_cache``.  The path is fixed because it is
  part of the cache's key: a directory named after a PID, a temporary
  name or the time would never be hit again.  It is gitignored.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    import jax

    path = os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
