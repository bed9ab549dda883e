"""Pure-jnp oracle for the fused pivot-search update (paper Eq. 6.3)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import matmul as _mm


def greedy_update_ref(q: jax.Array, S: jax.Array, acc: jax.Array,
                      norms_sq: jax.Array):
    """Reference semantics of one pivot-search update.

    Args:
      q:        (N,) current basis vector (real or complex).
      S:        (N, M) local snapshot shard.
      acc:      (M,) accumulated sum_j |c_j|^2 (real).
      norms_sq: (M,) reference norms (real).

    Returns:
      c:        (M,) = q^H S (dtype of S).
      acc_out:  (M,) = acc + |c|^2.
      max_res:  ()  max_i (norms_sq - acc_out)_i.
      argmax:   ()  int32 argmax_i of the residual.
    """
    c = _mm(q.conj(), S)
    acc_out = acc + jnp.abs(c) ** 2
    res = norms_sq - acc_out
    return c, acc_out, jnp.max(res), jnp.argmax(res).astype(jnp.int32)
