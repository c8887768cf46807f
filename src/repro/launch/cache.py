"""Where JAX's persistent compilation cache lives.

One rule for every entry point (``repro.launch.train``,
``repro.launch.serve``, ``chip_smoke.py``): if ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing is set here; otherwise compiled
programs go to ``.jax_cache/`` at the root of the checkout.  The path is
fixed — never a temp name, a PID or a time — because a cache that moves
between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    path = CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
