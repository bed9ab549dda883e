"""Public jit'd wrapper for the fused greedy pivot-search update.

Handles dtype dispatch (real vs complex planes) and CPU interpret fallback.
S goes to the kernel at its own shape (N, M), never padded: the kernel masks
a ragged last tile on either axis (see kernel.py).  Only q is padded, with
zeros, to a whole number of row tiles; acc and norms_sq keep their length M.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.greedy_update import kernel as _k
from repro.kernels.common import (  # noqa: F401  (re-exported)
    LANES,
    default_interpret,
    validate_tiles,
)
from repro.kernels.common import pad_to as _pad_to
from repro.kernels.common import round_up as _round_up


def greedy_update(
    q: jax.Array,
    S: jax.Array,
    acc: jax.Array,
    norms_sq: jax.Array,
    nt: int = 256,
    mt: int = 1024,
    interpret: bool | None = None,
):
    """Fused pivot-search update: c = q^H S, acc += |c|^2, residual argmax.

    Args:
      q:        (N,) basis vector (f32/f64/c64/c128).
      S:        (N, M) snapshot shard.
      acc:      (M,) accumulated |c|^2 (real).
      norms_sq: (M,) reference norms (real).
      nt, mt:   VMEM tile sizes (rows, cols).
      interpret: force Pallas interpret mode; default: interpret unless the
        backend is TPU.

    Returns (c, acc_out, max_res, argmax) matching
    :func:`repro.kernels.greedy_update.ref.greedy_update_ref`.
    """
    if interpret is None:
        interpret = default_interpret()
    validate_tiles("greedy_update", nt=nt, mt=mt)

    N, M = S.shape
    nt = min(nt, _round_up(N, LANES))
    mt = min(mt, _round_up(M, LANES))
    Np = _round_up(N, nt)

    acc_2d = acc[None, :].astype(jnp.float32)
    norms_2d = norms_sq[None, :].astype(jnp.float32)

    if jnp.iscomplexobj(S):
        plane = jnp.float32 if S.dtype == jnp.complex64 else jnp.float64
        qr = _pad_to(q.real[None, :].astype(plane), Np, 1)
        qi = _pad_to(q.imag[None, :].astype(plane), Np, 1)
        cr, ci, acc_out, bmax, bidx = _k.greedy_update_complex(
            qr, qi, S.real.astype(plane), S.imag.astype(plane), acc_2d,
            norms_2d, nt=nt, mt=mt, interpret=interpret
        )
        c = (cr[0] + 1j * ci[0]).astype(S.dtype)
    else:
        qp = _pad_to(q[None, :].astype(S.dtype), Np, 1)
        c, acc_out, bmax, bidx = _k.greedy_update_real(
            qp, S, acc_2d, norms_2d, nt=nt, mt=mt, interpret=interpret
        )
        c = c[0]

    # Final reduction over the per-block maxima (tiny: M/mt entries, one
    # per lane row; see kernel._write_block_max).
    bmax, bidx = bmax[0, ::LANES], bidx[0, ::LANES]
    blk = jnp.argmax(bmax)
    max_res = bmax[blk]
    argmax = bidx[blk]
    acc_out = acc_out[0].astype(acc.dtype)
    return c, acc_out, max_res.astype(norms_sq.dtype), argmax
