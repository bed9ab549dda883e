"""Plain reference for the build cells, and their lower-precision control.

``certificate`` checks a finished build against what Algorithm 3 (the
paper's RB-greedy, Sec. 5) defines, in float64 on the host.  It takes the
snapshot matrix S (the input) and the build's Q, R, pivots and errs, and
recomputes from S alone every quantity the build claims:

- C = Q^H S for every column, so R (the sweep's output) is checked
  entry by entry;
- the exact residual^2 of every column after j bases,
  res2[j] = |s|^2 - sum_{i<j} |C_i|^2, so each pivot is checked to be the
  column of largest residual (the top ``p`` of a block of ``p`` pivots
  chosen together) and each err is checked to be that pivot's residual;
- Q^H Q - I.

It imports nothing of the system under test.

``plain_greedy`` is Algorithm 3 written out in ``jax.numpy`` with every dot
at a stated precision.  Run at three bfloat16 passes (what
``Precision.HIGH`` asks of a TPU: the step below the full float32 the
configuration states), it is the control that the comparison has to
reject.  At one pass (XLA's default on a TPU), and with each sweep's
pivots taken one rank too low (``skip=1``), it gives the upper readings
of the numbers three passes do not separate (PERF.md).
"""

from __future__ import annotations

import functools

import numpy as np


def certificate(S, Q, R, pivots, errs, p: int = 1,
                block_cols: int = 4096) -> dict:
    """Numbers that say how far a build departs from Algorithm 3.

    All gaps are relative to the largest column of S (|s|_max, or its
    square for residuals^2), so they read the same at any column scale.
    ``p`` is the number of pivots the build picks per sweep (1 stepwise).
    """
    Q64 = np.asarray(Q).astype(np.complex128)
    N, k = Q64.shape
    M = S.shape[1]
    QH = np.ascontiguousarray(Q64.conj().T)
    S_host = np.asarray(S)
    C = np.empty((k, M), np.complex128)
    norms = np.empty(M, np.float64)
    for lo in range(0, M, block_cols):
        hi = min(M, lo + block_cols)
        blk = S_host[:, lo:hi].astype(np.complex128)
        C[:, lo:hi] = QH @ blk
        norms[lo:hi] = np.einsum("nm,nm->m", blk.real, blk.real) \
            + np.einsum("nm,nm->m", blk.imag, blk.imag)
        del blk
    scale2 = float(norms.max())
    ortho = float(np.max(np.abs(QH @ Q64 - np.eye(k))))
    R64 = np.asarray(R).astype(np.complex128)
    r_gap = float(np.max(np.abs(R64[:k] - C))) / scale2 ** 0.5
    cum = np.cumsum(C.real ** 2 + C.imag ** 2, axis=0)
    piv = np.asarray(pivots).astype(np.int64)
    e2 = np.asarray(errs).astype(np.float64) ** 2
    pivot_gap = err_gap = 0.0
    for j0 in range(0, k, p):
        row = norms - (cum[j0 - 1] if j0 else 0.0)
        top = np.partition(row, M - p)[M - p]   # p-th largest residual^2
        for j in range(j0, min(j0 + p, k)):
            pivot_gap = max(pivot_gap, float(top - row[piv[j]]) / scale2)
            err_gap = max(err_gap, abs(float(e2[j] - row[piv[j]])) / scale2)
    final = float(np.max(norms - cum[k - 1])) if k else scale2
    return {
        "ortho": ortho,
        "r_gap": r_gap,
        "pivot_gap": pivot_gap,
        "err_gap": err_gap,
        "dup_pivots": int(k - np.unique(piv[:k]).size),
        "k": int(k),
        "final_err": max(final, 0.0) ** 0.5 / scale2 ** 0.5,
    }


def split_bf16(x):
    """``x`` (float32) as bfloat16 parts ``(hi, lo)`` with x ~ hi + lo.

    ``hi`` is x rounded to bfloat16 (to nearest, ties to even) on the
    bits: a float32 -> bfloat16 -> float32 round trip may be folded away
    by the compiler, which would leave ``lo`` zero.  ``lo = x - hi`` is
    exact in float32 and is rounded to bfloat16 where the dot takes it.
    """
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def real_dot(a, b, passes):
    """``a @ b`` for float32 ``a``, ``b`` at a stated precision.

    ``passes=None`` is full float32 (``Precision.HIGHEST``).  ``passes=3``
    is three bfloat16 passes, ``hi*hi + hi*lo + lo*hi`` with exact
    products summed in float32 (what ``Precision.HIGH`` asks of a TPU);
    ``passes=1`` is one (``Precision.DEFAULT`` on a TPU).  Written out, so
    that the same precision runs on any backend.  ``a`` or ``b`` may come
    already split by :func:`split_bf16`.
    """
    import jax
    import jax.numpy as jnp

    if passes is None:
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    ah, al = a if isinstance(a, tuple) else split_bf16(a)
    bh, bl = b if isinstance(b, tuple) else split_bf16(b)

    def mm(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    out = mm(ah, bh)
    if passes == 3:
        out = out + mm(ah, bl) + mm(al, bh)
    return out


def complex_dot(a, b, passes):
    """``a @ b`` for complex64 operands given as (re, im) planes, each
    plane float32 or already split; returns the (re, im) planes."""
    (ar, ai), (br, bi) = a, b
    return (real_dot(ar, br, passes) - real_dot(ai, bi, passes),
            real_dot(ar, bi, passes) + real_dot(ai, br, passes))


@functools.lru_cache(maxsize=None)
def _plain_greedy_fn(max_k: int, p: int, passes, skip: int):
    import jax
    import jax.numpy as jnp

    def dot(a, b):
        re, im = complex_dot((a.real, a.imag), (b.real, b.imag), passes)
        return jax.lax.complex(re, im)

    def run(S):
        N, M = S.shape
        n_blocks = -(-max_k // p)
        slots = n_blocks * p
        norms = jnp.sum(S.real ** 2 + S.imag ** 2, axis=0)
        rdt = norms.dtype
        if passes is None:
            planes = (S.real, S.imag)
        else:   # S is read every sweep: split it once
            planes = (split_bf16(S.real), split_bf16(S.imag))

        def body(b, st):
            Q, R, acc, piv, errs = st
            vals, idx = jax.lax.top_k(norms - acc, p + skip)
            vals, idx = vals[skip:], idx[skip:]
            for i in range(p):
                v = jax.lax.dynamic_slice_in_dim(S, idx[i], 1, axis=1)
                for _ in range(2):  # twice is enough (Kahan-Parlett)
                    v = v - dot(Q, dot(Q.conj().T, v))
                v = v / jnp.sqrt(jnp.sum(v.real ** 2 + v.imag ** 2))
                Q = jax.lax.dynamic_update_slice_in_dim(
                    Q, v, b * p + i, axis=1)
            Qb = jax.lax.dynamic_slice_in_dim(Q, b * p, p, axis=1).conj().T
            cr, ci = complex_dot((Qb.real, Qb.imag), planes, passes)
            Cb = jax.lax.complex(cr, ci)
            acc = acc + jnp.sum(cr ** 2 + ci ** 2, axis=0)
            R = jax.lax.dynamic_update_slice_in_dim(R, Cb, b * p, axis=0)
            piv = jax.lax.dynamic_update_slice_in_dim(
                piv, idx.astype(jnp.int32), b * p, axis=0)
            errs = jax.lax.dynamic_update_slice_in_dim(
                errs, jnp.sqrt(jnp.maximum(vals, 0.0)), b * p, axis=0)
            return Q, R, acc, piv, errs

        init = (jnp.zeros((N, slots), S.dtype), jnp.zeros((slots, M), S.dtype),
                jnp.zeros((M,), rdt), jnp.zeros((slots,), jnp.int32),
                jnp.zeros((slots,), rdt))
        Q, R, _, piv, errs = jax.lax.fori_loop(0, n_blocks, body, init)
        return Q[:, :max_k], R[:max_k], piv[:max_k], errs[:max_k]

    return jax.jit(run)


def plain_greedy(S, max_k: int, p: int = 1, passes=None, skip: int = 0):
    """Algorithm 3 (``p`` pivots per sweep) in plain ``jax.numpy`` on a
    complex64 S, every dot at the precision :func:`real_dot` states.
    ``skip`` > 0 plants a fault: each sweep passes over its ``skip``
    largest residuals and takes the next ``p``.
    Returns ``(Q, R, pivots, errs)`` on the device, ``max_k`` of each."""
    return _plain_greedy_fn(int(max_k), int(p), passes, int(skip))(S)
