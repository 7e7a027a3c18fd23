"""JAX's persistent compilation cache, placed from outside or at a fixed path.

``enable_compile_cache()`` is called from the ``main()`` of every entry
point (launch/train.py, launch/serve.py, benchmarks/run.py and
chip_smoke.py) and never at import.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it and this code sets no other directory; otherwise the
cache lives at ``<checkout>/.jax_cache`` (gitignored).  The path is fixed
because it is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # every program is worth keeping: a cold run on a fresh machine pays
    # each compile again, and the small ones add up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
