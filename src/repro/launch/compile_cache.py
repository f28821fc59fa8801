"""Where JAX keeps its persistent compilation cache for this repo's entry
points.

Library modules never touch the cache; an entry point (``chip_smoke.py``,
``repro.launch.serve.main``) calls :func:`configure` once before its first
compile. ``JAX_COMPILATION_CACHE_DIR``, when set, wins and nothing is set in
code (JAX reads the variable itself). Otherwise the cache lives at a fixed
``.jax_cache/`` in the root of the checkout: the path is part of the cache
key, so a directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str | None:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use. On the CPU backend nothing is cached unless the
    variable asks for it: XLA:CPU compiles in seconds, and its cached
    executables fail a host-feature check (with a warning) when loaded."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    path = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
