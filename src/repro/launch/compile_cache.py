"""Persistent compilation cache for the programs this repo runs on a chip.

JAX reads `JAX_COMPILATION_CACHE_DIR` itself; where it is set, that is
where compiled programs go and nothing here overrides it. Otherwise the
cache lives at one fixed path inside the checkout, `<repo>/.jax_cache`:
the path is part of what a later run looks up, so it never moves between
runs. Entry points that compile for the chip (`chip_smoke.py`,
`launch/serve.py`) call `enable_compile_cache()` once, before compiling;
tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
