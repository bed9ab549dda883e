"""Runtime setup shared by the launchers and the distributed drivers:
mesh construction and the persistent compilation cache."""

from __future__ import annotations

import os
from pathlib import Path
from typing import Sequence

import jax

# <repo root>/.jax_cache (git-ignored).  A fixed path: a later process
# finds the programs an earlier one compiled only under the same path.
_DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def make_auto_mesh(
    shape: Sequence[int],
    axis_names: Sequence[str],
    devices=None,
):
    """``jax.make_mesh`` with every axis ``Auto``."""
    kwargs = {"axis_types": (jax.sharding.AxisType.Auto,) * len(
        tuple(axis_names))}
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(tuple(shape), tuple(axis_names), **kwargs)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process.

    Call from a launcher's ``main()``, never at import.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
    is changed; otherwise the cache goes to ``<repo root>/.jax_cache``.
    Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_CACHE_DIR))
    return str(_DEFAULT_CACHE_DIR)
