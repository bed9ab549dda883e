"""Helpers shared by the Pallas kernel wrappers (ops.py modules).

Single home for tile/padding/backend-detection logic so a change to
padding semantics or lane constraints applies to every kernel at once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LANES = 128

# f32 dots on a TPU default to one bf16 pass: measured on a v5e, ~2.5e-3
# relative error against float64, in XLA and in Mosaic alike, which costs
# the greedy its orthogonality and its pivots.  Every dot of the reduction
# and serving path asks for full f32 instead (the CPU computes f32 dots in
# full f32 either way).
PRECISION = jax.lax.Precision.HIGHEST


def matmul(a, b):
    """``a @ b`` at :data:`PRECISION`."""
    return jnp.matmul(a, b, precision=PRECISION)


@functools.lru_cache(maxsize=None)
def default_interpret() -> bool:
    """Interpret-mode default: compiled Mosaic kernels on a TPU, the Pallas
    interpreter elsewhere.

    Resolved once per process: ``jax.default_backend()`` initializes the
    backends and walks the device list, which is wasted work inside every
    trace of a jitted hot loop, and the platform cannot change after JAX is
    initialized, so a process-wide cache is exact.
    """
    return jax.default_backend() != "tpu"


def validate_tiles(name: str, **tiles: int) -> None:
    """Reject tile sizes the TPU lanes cannot shape, with a clear error."""
    for tile_name, tile in tiles.items():
        if tile <= 0 or tile % LANES != 0:
            raise ValueError(
                f"{name}: tile {tile_name}={tile} must be a positive "
                f"multiple of {LANES} (TPU lane count); got a remainder of "
                f"{tile % LANES if tile > 0 else tile}"
            )


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pad_to(x, size, axis):
    """Zero-pad ``x`` along ``axis`` to length ``size``."""
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)
