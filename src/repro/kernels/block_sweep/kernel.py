"""Pallas TPU kernel for the blocked Eq.-(6.3) panel sweep (BLAS-3 greedy).

The stepwise greedy hot loop reads the whole snapshot shard S once per basis
vector at ~1 FLOP/byte — a pure DRAM-roof workload (see BENCH_greedy.json's
f32 hot-path rows).  Block pivoting (classical blocked column-pivoted QR:
[35] Quintana-Orti, [18] Demmel et al. CA-RRQR) selects p pivots per sweep,
so ONE read of S serves p bases.  This kernel is the fused device form of
that sweep:

  unfused: read S (panel GEMM) -> write C -> read C + acc (norm update)
  fused:   read S once; C and acc produced from VMEM in the same pass.

Layout mirrors :mod:`repro.kernels.greedy_update.kernel`: S is blocked
(Nt x Mt) with columns M as the outer (parallel) grid axis and rows N as
the inner (reduction) axis; the panel lives as its conjugate transpose
Qh = Qnew^H (p x N, real planes) so each grid step is one MXU
(p, Nt) x (Nt, Mt) GEMM accumulated into a (p, Mt) VMEM scratch.  The row
count p is padded to a sublane multiple by ops.py; padded rows are zero, so
their C rows are zero and contribute nothing to acc.

Complex snapshots (the GW production case) run as split re/im planes
(TPU MXUs are real): C = Qnew^H S becomes four real GEMMs in the same pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import PRECISION


def _kernel_real(qh_ref, s_ref, acc_ref, c_ref, acc_out_ref, c_scr):
    n_i = pl.program_id(1)
    n_blocks = pl.num_programs(1)

    @pl.when(n_i == 0)
    def _():
        c_scr[...] = jnp.zeros_like(c_scr)

    c_scr[...] += jnp.dot(
        qh_ref[...], s_ref[...], precision=PRECISION,
        preferred_element_type=jnp.float32
    )

    @pl.when(n_i == n_blocks - 1)
    def _():
        c = c_scr[...]
        c_ref[...] = c.astype(c_ref.dtype)
        acc_out_ref[...] = acc_ref[...] + jnp.sum(c * c, axis=0,
                                                  keepdims=True)


def _kernel_complex(qhr_ref, qhi_ref, sr_ref, si_ref, acc_ref,
                    cr_ref, ci_ref, acc_out_ref, cr_scr, ci_scr):
    n_i = pl.program_id(1)
    n_blocks = pl.num_programs(1)

    @pl.when(n_i == 0)
    def _():
        cr_scr[...] = jnp.zeros_like(cr_scr)
        ci_scr[...] = jnp.zeros_like(ci_scr)

    qhr = qhr_ref[...]
    qhi = qhi_ref[...]
    sr = sr_ref[...]
    si = si_ref[...]
    # C = Qnew^H S = (Qr - i Qi)^T (Sr + i Si); qh* hold Q*^T
    cr_scr[...] += jnp.dot(qhr, sr, precision=PRECISION,
                           preferred_element_type=jnp.float32)
    cr_scr[...] += jnp.dot(qhi, si, precision=PRECISION,
                           preferred_element_type=jnp.float32)
    ci_scr[...] += jnp.dot(qhr, si, precision=PRECISION,
                           preferred_element_type=jnp.float32)
    ci_scr[...] -= jnp.dot(qhi, sr, precision=PRECISION,
                           preferred_element_type=jnp.float32)

    @pl.when(n_i == n_blocks - 1)
    def _():
        cr = cr_scr[...]
        ci = ci_scr[...]
        cr_ref[...] = cr.astype(cr_ref.dtype)
        ci_ref[...] = ci.astype(ci_ref.dtype)
        acc_out_ref[...] = acc_ref[...] + jnp.sum(cr * cr + ci * ci,
                                                  axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("nt", "mt", "interpret"))
def block_sweep_real(qh, S, acc, nt: int = 256, mt: int = 1024,
                     interpret: bool = True):
    """Real-dtype fused panel sweep on padded inputs (see ops.py).

    qh: (p, N) = Qnew^T; S: (N, M); acc: (1, M) f32.
    p % 8 == 0, N % nt == 0 and M % mt == 0 must hold.
    """
    p, _ = qh.shape
    N, M = S.shape
    grid = (M // mt, N // nt)
    c, acc_out = pl.pallas_call(
        _kernel_real,
        grid=grid,
        in_specs=[
            pl.BlockSpec((p, nt), lambda m, n: (0, n)),
            pl.BlockSpec((nt, mt), lambda m, n: (n, m)),
            pl.BlockSpec((1, mt), lambda m, n: (0, m)),
        ],
        out_specs=[
            pl.BlockSpec((p, mt), lambda m, n: (0, m)),
            pl.BlockSpec((1, mt), lambda m, n: (0, m)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, M), S.dtype),
            jax.ShapeDtypeStruct((1, M), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, mt), jnp.float32)],
        interpret=interpret,
    )(qh, S, acc)
    return c, acc_out


@functools.partial(jax.jit, static_argnames=("nt", "mt", "interpret"))
def block_sweep_complex(qhr, qhi, Sr, Si, acc, nt: int = 256,
                        mt: int = 1024, interpret: bool = True):
    """Complex fused panel sweep on split re/im planes (padded; see ops.py)."""
    p, _ = qhr.shape
    N, M = Sr.shape
    grid = (M // mt, N // nt)
    cr, ci, acc_out = pl.pallas_call(
        _kernel_complex,
        grid=grid,
        in_specs=[
            pl.BlockSpec((p, nt), lambda m, n: (0, n)),
            pl.BlockSpec((p, nt), lambda m, n: (0, n)),
            pl.BlockSpec((nt, mt), lambda m, n: (n, m)),
            pl.BlockSpec((nt, mt), lambda m, n: (n, m)),
            pl.BlockSpec((1, mt), lambda m, n: (0, m)),
        ],
        out_specs=[
            pl.BlockSpec((p, mt), lambda m, n: (0, m)),
            pl.BlockSpec((p, mt), lambda m, n: (0, m)),
            pl.BlockSpec((1, mt), lambda m, n: (0, m)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, M), Sr.dtype),
            jax.ShapeDtypeStruct((p, M), Sr.dtype),
            jax.ShapeDtypeStruct((1, M), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((p, mt), jnp.float32),
            pltpu.VMEM((p, mt), jnp.float32),
        ],
        interpret=interpret,
    )(qhr, qhi, Sr, Si, acc)
    return cr, ci, acc_out
