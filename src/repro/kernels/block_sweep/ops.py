"""Public jit'd wrapper for the fused blocked Eq.-(6.3) panel sweep.

Handles dtype dispatch (real vs complex planes), panel/tile padding, and
CPU interpret fallback.  The panel row count p is padded to a sublane
multiple with zero rows (no-ops in the GEMMs and in the acc column sums);
padded snapshot rows/columns are zero too, so C and acc are exact on the
un-padded region.

Precision note: the kernel accumulates C and acc in f32 (TPU MXU native),
so f64/c128 inputs are reduced at f32 accuracy on this path — for builds
whose tau sits below ~1e-7 use the ``xla``/``xla_ref`` backends, which
keep full working precision (same caveat as
:mod:`repro.kernels.imgs_project` / :mod:`repro.kernels.imgs_panel`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.block_sweep import kernel as _k
from repro.kernels.common import (
    LANES,
    default_interpret,
    validate_tiles,
)
from repro.kernels.common import pad_to as _pad_to
from repro.kernels.common import round_up as _round_up

_SUBLANES = 8  # f32 sublane count: the panel's row-padding quantum


def block_sweep(
    Qnew: jax.Array,
    S: jax.Array,
    acc: jax.Array,
    nt: int = 256,
    mt: int = 1024,
    interpret: bool | None = None,
):
    """Fused blocked sweep: C = Qnew^H S, acc += sum_i |C_i|^2.

    Args:
      Qnew: (N, p) block of new basis vectors (f32/f64/c64/c128); rejected
        in-block candidates are zero columns (exact no-ops).
      S:    (N, M) snapshot shard.
      acc:  (M,) accumulated |c|^2 (real).
      nt, mt: VMEM tile sizes (rows, cols).
      interpret: force Pallas interpret mode; default: interpret unless the
        backend is TPU.

    Returns (C, acc_out) matching
    :func:`repro.kernels.block_sweep.ref.block_sweep_ref`.
    """
    if interpret is None:
        interpret = default_interpret()
    validate_tiles("block_sweep", nt=nt, mt=mt)

    N, M = S.shape
    p = Qnew.shape[1]
    pp = _round_up(max(p, 1), _SUBLANES)
    nt = min(nt, _round_up(N, LANES))
    mt = min(mt, _round_up(M, LANES))
    Np, Mp = _round_up(N, nt), _round_up(M, mt)

    acc_p = _pad_to(acc[None, :].astype(jnp.float32), Mp, 1)

    if jnp.iscomplexobj(S):
        plane = jnp.float32 if S.dtype == jnp.complex64 else jnp.float64
        qhr = _pad_to(_pad_to(Qnew.real.T.astype(plane), pp, 0), Np, 1)
        qhi = _pad_to(_pad_to(Qnew.imag.T.astype(plane), pp, 0), Np, 1)
        Sr = _pad_to(_pad_to(S.real.astype(plane), Np, 0), Mp, 1)
        Si = _pad_to(_pad_to(S.imag.astype(plane), Np, 0), Mp, 1)
        cr, ci, acc_out = _k.block_sweep_complex(
            qhr, qhi, Sr, Si, acc_p, nt=nt, mt=mt, interpret=interpret
        )
        C = (cr[:p, :M] + 1j * ci[:p, :M]).astype(S.dtype)
    else:
        qh = _pad_to(_pad_to(Qnew.T.astype(S.dtype), pp, 0), Np, 1)
        Sp = _pad_to(_pad_to(S, Np, 0), Mp, 1)
        c, acc_out = _k.block_sweep_real(
            qh, Sp, acc_p, nt=nt, mt=mt, interpret=interpret
        )
        C = c[:p, :M]

    return C, acc_out[0, :M].astype(acc.dtype)


def batched_block_sweep(
    Qnew: jax.Array,
    S: jax.Array,
    acc: jax.Array,
    nt: int = 256,
    mt: int = 1024,
    interpret: bool | None = None,
):
    """B-lane blocked sweep: per lane ``C_b = Qnew_b^H S_b``,
    ``acc_b += sum_i |C_b,i|^2``.

    Args:
      Qnew: (B, N, p) one panel of new basis vectors per lane.
      S:    (B, N, M) stacked per-lane snapshots, or (N, M) shared.
      acc:  (B, M) per-lane accumulated sums (real).

    Returns ``(C, acc_out)`` with shapes ((B, p, M), (B, M)).

    Shared layout: the B panels stack along the panel axis into ONE
    (N, B*p) kernel call — a single fused HBM pass over S serves every
    lane (the batched amortization the lockstep driver exists for).  The
    kernel's fused per-column sum spans ALL B*p rows, so per-lane acc is
    recomputed from the returned C (each lane only sums its own p rows);
    the kernel is fed a zero acc and its cross-lane sum is discarded.

    Stacked layout: per-lane fused kernel calls (each lane reads its own
    S_b exactly once — there is no cross-lane traffic to amortize).
    """
    B, N, p = Qnew.shape
    if S.ndim == 2:
        panel = jnp.swapaxes(Qnew, 1, 2).reshape(B * p, N).T  # (N, B*p)
        C_flat, _ = block_sweep(
            panel, S, jnp.zeros_like(acc[0]), nt=nt, mt=mt,
            interpret=interpret,
        )
        C = C_flat.reshape(B, p, -1)
        acc_out = acc + jnp.sum(jnp.abs(C) ** 2, axis=1).astype(acc.dtype)
        return C, acc_out
    outs = [block_sweep(Qnew[b], S[b], acc[b], nt=nt, mt=mt,
                        interpret=interpret) for b in range(B)]
    return (jnp.stack([o[0] for o in outs]),
            jnp.stack([o[1] for o in outs]))
