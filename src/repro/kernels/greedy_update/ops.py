"""Public jit'd wrapper for the fused greedy pivot-search update.

Handles dtype dispatch (real vs complex planes), tile padding, and CPU
interpret fallback.  The padded columns get ``norms_sq = -1e30`` so they can
never win the argmax; padded rows are zeros so they are no-ops in the dot
products.
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
    Np, Mp = _round_up(N, nt), _round_up(M, mt)

    acc_p = _pad_to(acc[None, :].astype(jnp.float32), Mp, 1)
    norms_p = _pad_to(
        norms_sq[None, :].astype(jnp.float32), Mp, 1, value=_k.NEG_LARGE
    )

    if jnp.iscomplexobj(S):
        plane = jnp.float32 if S.dtype == jnp.complex64 else jnp.float64
        qr = _pad_to(q.real[None, :].astype(plane), Np, 1)
        qi = _pad_to(q.imag[None, :].astype(plane), Np, 1)
        Sr = _pad_to(_pad_to(S.real.astype(plane), Np, 0), Mp, 1)
        Si = _pad_to(_pad_to(S.imag.astype(plane), Np, 0), Mp, 1)
        cr, ci, acc_out, bmax, bidx = _k.greedy_update_complex(
            qr, qi, Sr, Si, acc_p, norms_p, nt=nt, mt=mt, interpret=interpret
        )
        c = (cr[0, :M] + 1j * ci[0, :M]).astype(S.dtype)
    else:
        qp = _pad_to(q[None, :].astype(S.dtype), Np, 1)
        Sp = _pad_to(_pad_to(S, Np, 0), Mp, 1)
        c, acc_out, bmax, bidx = _k.greedy_update_real(
            qp, Sp, acc_p, norms_p, nt=nt, mt=mt, interpret=interpret
        )
        c = c[0, :M]

    # Final reduction over the per-block maxima (tiny: M/mt entries, one
    # per lane row; see kernel._write_block_max).
    bmax, bidx = bmax[0, ::LANES], bidx[0, ::LANES]
    blk = jnp.argmax(bmax)
    max_res = bmax[blk]
    argmax = bidx[blk]
    acc_out = acc_out[0, :M].astype(acc.dtype)
    return c, acc_out, max_res.astype(norms_sq.dtype), argmax
