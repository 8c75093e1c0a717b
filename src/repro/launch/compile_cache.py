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

The cache key keeps the programs' op metadata, whose ``op_name`` paths
carry the device scopes of ``repro.telemetry.scopes``: by default JAX
strips it, and a program would then load an executable compiled from the
same code without its scopes, whose profile no scope reader can read.
Source file names in that metadata lose the checkout's own path, so a
checkout anywhere finds its entries.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
CACHE_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(CHECKOUT) + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
