"""Mesh construction and process-level JAX setup.

Production mesh: a v5e pod, 16x16 = 256 chips; multi-pod adds a leading
'pod' axis.  Functions only — importing this module never touches jax
device state."""
from __future__ import annotations

import os
from pathlib import Path

import jax
from jax.sharding import AxisType

#: compile-cache directory used when JAX_COMPILATION_CACHE_DIR is unset: a
#: fixed path inside the checkout (the path is part of the cache key, so a
#: moving directory would never hit)
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def _auto_mesh(shape, axes):
    # Auto axes: the model code pins layouts with with_sharding_constraint
    # (repro.dist.sharding), which only accepts Auto mesh axes
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Mesh over whatever devices exist: ``(n // model_axis, model_axis)``
    over ``("data", "model")``."""
    n = len(jax.devices())
    data = max(1, n // model_axis)
    return _auto_mesh((data, model_axis), ("data", "model"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads it
    itself; nothing else is set here).  Otherwise the cache lives in
    ``DEFAULT_COMPILE_CACHE``.  Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_COMPILE_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# TPU v5e hardware constants (per chip) for the roofline analysis
PEAK_FLOPS_BF16 = 197e12     # FLOP/s
HBM_BW = 819e9               # B/s
ICI_BW_PER_LINK = 50e9       # B/s per link
