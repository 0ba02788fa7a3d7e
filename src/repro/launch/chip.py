"""Process set-up shared by the graph entry points (``lcc_run``,
``stream_run``, ``query_serve``) and ``chip_smoke.py``.

- ``enable_compile_cache`` keeps JAX's persistent compilation cache at
  one fixed place, so a second run of the same program on the same
  chip skips its compiles. ``JAX_COMPILATION_CACHE_DIR`` wins when it
  is set (JAX reads it itself); otherwise the cache is ``.jax_cache/``
  at the checkout root. Call it before the first compile. It also
  starts feeding JAX's compile seconds and cache hits and misses into
  the set-up record of ``repro.obs.trace`` (``setup_record()``).
- ``device_summary`` names what JAX actually runs on, read from
  ``jax.devices()``, so every run states its platform.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

from ..obs import trace as obs_trace

__all__ = ["CACHE_DIR", "device_summary", "enable_compile_cache"]

# src/repro/launch/chip.py -> the checkout root
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its fixed directory and
    return that directory."""
    obs_trace.install_compile_listener()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def device_summary() -> dict:
    """``{"platform", "kind", "count"}`` of the devices JAX runs on."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
