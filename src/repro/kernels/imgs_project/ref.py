"""Pure-jnp oracle for one iterated-Gram-Schmidt projection pass."""

from __future__ import annotations

import jax

from repro.kernels.common import matmul as _mm


def imgs_project_ref(v: jax.Array, Q: jax.Array):
    """One classical-GS pass: c = Q^H v; v' = v - Q c.

    Args:
      v: (N,) vector to orthogonalize.
      Q: (N, K) basis (zero columns are no-ops).

    Returns (v', c) with c: (K,).
    """
    c = _mm(Q.conj().T, v)
    return v - _mm(Q, c), c
