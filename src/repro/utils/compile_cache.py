"""Persistent XLA compilation cache for the repo's entry points.

Compiling the trainer's steps, the inference encoder and the retrieval
programs is a large share of a cold run on a TPU. Entry points (examples,
benchmarks, ``chip_smoke.py``) call :func:`enable_compile_cache` first, so
later runs of the same checkout load compiled programs instead.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/utils/compile_cache.py -> the checkout root
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing is configured here. Otherwise the cache lives in ``.jax_cache/``
    at the checkout root: a fixed path, since a cache whose directory moves
    from run to run never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
