"""Persistent XLA compilation cache for the entry points.

Every entry point (`repro.launch.serve`, `repro.launch.train`,
`benchmarks/run.py`, `chip_smoke.py`) calls `enable()` once before it
touches a device.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing else is configured here.  Otherwise the cache lives at a
fixed path inside the checkout, ``<repo>/.jax_cache`` (git-ignored).  The
directory is part of what a later run must find again, so it never comes
from a temporary name, a process id or the clock.

`enable()` also keeps the TPU runtime from writing its logs to a fixed
path under /tmp, outside the checkout (``TPU_LOG_DIR=disabled`` unless the
variable is set); the runtime reads it when the backend starts.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["ENV_VAR", "REPO_CACHE", "enable"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
