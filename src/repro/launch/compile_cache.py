"""Where JAX keeps its persistent compilation cache.

Called first by every entry point that compiles at full size
(``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks.run``), so a
second run of the 36-layer prefill and the decode bursts loads compiled
programs instead of compiling them again. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
changed here. Otherwise the cache lives at one fixed path inside the
checkout (``.jax_cache``, git-ignored): the path is part of what makes a
second run find the first run's entries, so it never carries a temp name,
a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
