"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, the ``benchmarks`` CLIs) call
:func:`enable_compile_cache` before their first compile.  A set
``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself and nothing is
set here.  Otherwise the cache lives at ``.jax_cache/`` in the checkout
root (gitignored) — a fixed path, never one derived from a temp name, a
pid or the time, so a later run of the same checkout finds it again.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
