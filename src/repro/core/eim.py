"""Empirical interpolation (EIM/DEIM) and reduced-order quadrature (ROQ).

The greedycpp code pairs the greedy basis with empirical-interpolation node
selection ("a fast algorithm, see Alg. 5 of Ref. [6]") and uses the result to
build reduced-order quadrature rules that accelerate gravitational-wave
likelihood evaluations.  This module implements:

- :func:`eim_nodes` — greedy node selection (DEIM): node i maximizes the
  magnitude of the i-th basis vector's interpolation residual.
- :func:`empirical_interpolant` — builds B = Q (Q[nodes, :])^{-1} so that
  I_k[f] = B @ f[nodes] interpolates f at the nodes.
- :func:`roq_weights` — reduced-order quadrature weights: for an inner
  product <d, h> = sum_x w_x conj(d_x) h_x, precompute omega so that
  <d, h> ~= sum_j omega_j h(node_j)  (the paper's GW inference application).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.common import matmul as _mm


class EIMResult(NamedTuple):
    nodes: jax.Array   # (k,) int32 interpolation rows ("empirical nodes")
    B: jax.Array       # (N, k) interpolant matrix: I[f] = B @ f[nodes]


def eim_nodes(Q: jax.Array) -> EIMResult:
    """Greedy EIM node selection for the basis columns of Q (N, k).

    Iteration i selects the row where the current basis vector is worst
    represented by interpolation on the existing nodes (classic DEIM).
    Implemented with ``lax.fori_loop`` and a growing (masked) node set so it
    jits with static shapes.
    """
    N, k = Q.shape

    def body(i, carry):
        nodes, = carry
        qi = Q[:, i]
        # Solve interpolation coefficients on existing nodes (first i rows):
        # A c = qi[nodes[:i]]  with A = Q[nodes[:i], :i].
        # Build a padded k x k system that is identity beyond i.
        sel = Q[nodes, :]                       # (k, k) rows at current nodes
        row_mask = jnp.arange(k) < i
        A = jnp.where(
            row_mask[:, None] & row_mask[None, :],
            sel,
            jnp.eye(k, dtype=Q.dtype),
        )
        rhs = jnp.where(row_mask, qi[nodes], jnp.zeros((k,), Q.dtype))
        c = jnp.linalg.solve(A, rhs)
        r = qi - _mm(Q, jnp.where(row_mask, c, jnp.zeros_like(c)))
        node_i = jnp.argmax(jnp.abs(r)).astype(jnp.int32)
        return (nodes.at[i].set(node_i),)

    nodes0 = jnp.zeros((k,), jnp.int32)
    nodes0 = nodes0.at[0].set(jnp.argmax(jnp.abs(Q[:, 0])).astype(jnp.int32))
    (nodes,) = jax.lax.fori_loop(1, k, body, (nodes0,))

    B = _mm(Q, jnp.linalg.inv(Q[nodes, :]))
    return EIMResult(nodes=nodes, B=B)


def empirical_interpolant(B: jax.Array, nodes: jax.Array, f: jax.Array):
    """Evaluate the empirical interpolant of f (vector or batch of columns)."""
    if f.ndim == 1:
        return _mm(B, f[nodes])
    return _mm(B, f[nodes, :])


def roq_weights(data: jax.Array, quad_w: jax.Array, B: jax.Array):
    """Reduced-order quadrature weights for <data, .> (GW likelihood use).

    <d, h> = sum_x w_x conj(d_x) h_x ~= sum_j omega_j h(node_j) with
    omega = B^T (w * conj(d)).
    """
    return _mm(B.T, quad_w.astype(B.dtype) * jnp.conj(data))
