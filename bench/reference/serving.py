"""Plain reference for the serving cells, and their lower-precision control.

A request is f = Q coef known at the EIM nodes; the empirical
interpolant is exact on span(Q), so the right answer is f itself at all N
samples, computed here in complex128 from the basis Q as the build
returned it (before the engine copied it) and the request's
coefficients.  Nothing of the program's interpolant (its nodes'
inverse, its matrix B) enters the reference.

The control puts the reference interpolant B = Q Q[nodes]^-1 (float64,
rounded to the served dtype) in the program's place and evaluates it on
the chip with every dot one precision below the configuration's full
float32: three bfloat16 passes (``bench.reference.greedy.real_dot``).
"""

from __future__ import annotations

import numpy as np


def serve_error(Q64, coef, answers) -> float:
    """Largest gap between the answers (N, n) and their exact values
    Q coef, relative to the largest exact value."""
    if answers.shape[1] == 0:
        return 0.0
    exact = Q64 @ coef
    return float(np.max(np.abs(answers - exact)) / np.max(np.abs(exact)))


def control_answers(Q64, nodes, F, passes=3):
    """The reference interpolant evaluated on the device at ``passes``
    bfloat16 passes for the requests ``F`` (n, k) at the nodes; returns
    the answers (N, n)."""
    import jax

    from bench.reference.greedy import complex_dot

    B = (Q64 @ np.linalg.inv(Q64[nodes])).astype(F.dtype)
    Ft = np.ascontiguousarray(F.T)
    apply = jax.jit(lambda a, b: complex_dot(a, b, passes))
    re, im = apply((B.real.copy(), B.imag.copy()),
                   (Ft.real.copy(), Ft.imag.copy()))
    return np.asarray(re).astype(np.complex128) + 1j * np.asarray(im)
