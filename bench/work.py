"""Bytes each kernel has to move per build, from the configuration's shapes.

Counted at the configuration's dtype and shapes, never at the
implementation's padded or plane-split copies, so a kernel's roofline
share reads the same work whatever implements it.  The sweeps are
HBM-bound (one multiply-add per element read), and no float32 compute
peak is published for the chips in ``peaks.py``, so only bytes are
counted.
"""

from __future__ import annotations

import numpy as np

REAL_BYTES = 4  # the float32 residual vectors (norms, accumulated |c|^2)


def itemsize(dtype: str) -> int:
    return np.dtype(dtype).itemsize


def sweeps_per_build(max_k: int, block_p: int) -> int:
    """S is read once per block of ``block_p`` pivots: ceil(k / p)."""
    return -(-max_k // block_p)


def greedy_update_bytes(n_rows: int, n_cols: int, dtype: str,
                        max_k: int) -> int:
    """The stepwise Eq.-6.3 sweep, per build: each of the k sweeps reads S
    and q, reads and writes the accumulated |c|^2, reads |s|^2, and
    writes c = q^H S."""
    s = itemsize(dtype)
    per_sweep = (n_rows * n_cols * s + n_rows * s
                 + n_cols * 3 * REAL_BYTES + n_cols * s)
    return max_k * per_sweep


def block_sweep_bytes(n_rows: int, n_cols: int, dtype: str, max_k: int,
                      block_p: int) -> int:
    """The blocked sweep, per build: each of the ceil(k/p) sweeps reads S
    and the p new basis vectors, reads and writes the accumulated |c|^2,
    and writes the p rows of C = Q_new^H S."""
    s = itemsize(dtype)
    per_sweep = (n_rows * n_cols * s + block_p * n_rows * s
                 + n_cols * 2 * REAL_BYTES + block_p * n_cols * s)
    return sweeps_per_build(max_k, block_p) * per_sweep


def sweep_bytes(config: dict, traffic: dict, chips: int) -> int:
    """Bytes per chip per build of the sweep kernel the cell runs: S is
    column-sharded over the chips, so each chip sweeps M / chips columns."""
    n_cols = config["n_cols"] // chips
    args = (config["n_rows"], n_cols, config["dtype"], config["max_k"])
    if traffic["block_p"] == 1:
        return greedy_update_bytes(*args)
    return block_sweep_bytes(*args, traffic["block_p"])
