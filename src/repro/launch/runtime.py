"""Process setup shared by the entry points (``launch/train.py``,
``chip_smoke.py``): JAX's persistent compilation cache and the device
report every run prints before it does any work."""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# a fixed path inside the checkout (git-ignored): the cache is found again
# only by runs that look in the same directory, so it never moves
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, decides the directory.
    Otherwise an accelerator run caches in :data:`DEFAULT_CACHE_DIR` and a
    CPU run caches nothing (None): its compiles are cheap, and XLA:CPU
    warns on every entry it loads back."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        if jax.default_backend() == "cpu":
            return None
        path = str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path


def device_info() -> dict:
    """Platform, kind and count of the devices JAX runs on."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
