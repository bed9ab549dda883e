"""Pure-jnp oracle for one classical-GS PANEL projection pass."""

from __future__ import annotations

import jax

from repro.kernels.common import matmul as _mm


def imgs_panel_ref(V: jax.Array, Q: jax.Array):
    """One classical-GS pass on a whole candidate panel.

    The BLAS-3 form of :func:`repro.kernels.imgs_project.ref.imgs_project_ref`
    applied to p candidates at once: ``C = Q^H V``; ``V' = V - Q C`` — one
    read of Q per panel instead of per candidate (the panel factorization
    idea of the blocked-QR literature the paper cites: Quintana-Orti's
    BLAS-3 QR, Demmel et al. CA-RRQR).

    Args:
      V: (N, p) candidate panel (zero columns are no-ops).
      Q: (N, K) basis (zero columns are no-ops).

    Returns (V', C) with C: (K, p).
    """
    C = _mm(Q.conj().T, V)
    return V - _mm(Q, C), C
