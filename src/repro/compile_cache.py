"""Where the persistent XLA compilation cache lives.

Entry points call ``enable_compile_cache()`` from their ``main()`` (never at
import time): a deployment that sets ``JAX_COMPILATION_CACHE_DIR`` keeps the
cache where it says, and jax reads that variable itself. Otherwise the cache
goes to ``.jax_cache/`` at the checkout root, a fixed path derived from this
file's location — the path is part of the cache key, so a directory that
moved between runs would never hit.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
