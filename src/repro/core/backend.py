"""What the attached device decides at run time.

Two decisions live here so that no caller threads them by hand:

  * :func:`pallas_interpret` — Pallas kernels compile for a TPU and run
    through the Pallas interpreter on every other backend;
  * :func:`enable_compile_cache` — where JAX's persistent compilation cache
    lives, for the entry points (``chip_smoke.py``, ``launch/serve.py``,
    ``launch/train.py``).

The planner's hardware peaks are keyed by device kind in
:func:`repro.core.ir.hardware_for`.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache: src/repro/core/backend.py -> parents[3] is the checkout
_REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def pallas_interpret(device=None) -> bool:
    """True when Pallas kernels must run interpreted on ``device`` (default:
    the first attached device): compiled on ``tpu``, interpreted elsewhere."""
    if device is None:
        device = jax.devices()[0]
    return device.platform != "tpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it itself,
    and no other directory is set here); otherwise the fixed in-repo
    ``.jax_cache``.  The path is part of every entry's key, so it holds no
    temporary name, pid or time."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, not only the slow-to-compile ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
