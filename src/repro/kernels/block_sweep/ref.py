"""Pure-jnp oracle for the fused blocked Eq.-(6.3) panel sweep."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import matmul as _mm


def block_sweep_ref(Qnew: jax.Array, S: jax.Array, acc: jax.Array):
    """Reference semantics of one blocked pivot-sweep update.

    The blocked form of the paper's Eq. (6.3) bookkeeping: after a block of
    p new basis vectors is revealed, every column's accumulated sum gains
    the squared projections onto ALL p of them in one pass over S.

    Args:
      Qnew: (N, p) the block's new basis vectors (rejected in-block
            candidates are zero columns — exact no-ops here).
      S:    (N, M) local snapshot shard.
      acc:  (M,) accumulated sum_j |c_j|^2 (real).

    Returns:
      C:       (p, M) = Qnew^H S (dtype of S) — the block's rows of R.
      acc_out: (M,) = acc + sum_i |C[i]|^2.
    """
    C = _mm(Qnew.conj().T, S)
    acc_out = acc + jnp.sum(jnp.abs(C) ** 2, axis=0).astype(acc.dtype)
    return C, acc_out
