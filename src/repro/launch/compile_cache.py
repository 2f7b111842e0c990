"""JAX's persistent compilation cache, placed from outside the program.

A full-width round compiles for minutes, and a chip machine starts with
no compiled code.  Every entry point calls :func:`configure` before its
first ``jit``:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else is
  configured here.
* not set: the cache lives at ``<checkout>/.jax_cache`` -- a fixed path
  (the directory is part of the cache key, so a per-run name would never
  hit), listed in ``.gitignore``.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def configure() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    where = os.environ.get(ENV)
    if where:
        return where
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
