"""Where JAX keeps its persistent compilation cache for this repo's entry points.

A cold run of the served path spends much of its time compiling, so every
entry point (``chip_smoke.py``, ``launch/serve_cnn.py``, ``launch/serve_lm.py``,
``benchmarks/run.py``) calls :func:`use_compile_cache` first thing in
``main()`` — never at import, so tests and library users keep JAX's own
settings.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins: the
helper then changes nothing.  Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout.  The path is fixed so that a later run finds what an
earlier one wrote: a directory named after a temporary, a process id or the
time would start empty every time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    if set, else at ``<checkout>/.jax_cache``; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
