"""Persistent XLA compilation cache shared by every entry point.

A cold process recompiles every program it runs; on the chip the train
step of a published-width model alone takes tens of seconds. With the
persistent cache a second process reads those executables back instead.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is left
alone. Otherwise the cache lives at a FIXED path inside the checkout:
a directory that moves between runs (a temporary directory, a pid or a
timestamp in its name) is never found again.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use. Call it
    before the first compilation of the process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
