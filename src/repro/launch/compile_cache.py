"""Where JAX's persistent compilation cache lives for this checkout.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.bench``) call ``use_compile_cache()`` once at start-up;
importing the library never touches the cache setting.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The fixed in-checkout cache directory (listed in .gitignore).  A
#: fixed path matters: the directory is part of what JAX keys on, so a
#: temporary or per-process directory would never hit.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to ``DEFAULT_DIR``.

    Returns:
      the directory the cache writes to.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
