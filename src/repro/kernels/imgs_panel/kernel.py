"""Pallas TPU kernel for one classical-GS PANEL projection pass (BLAS-3).

The blocked drivers' remaining serial residue after the PR-4 panel sweep is
orthogonalization: p sequential :mod:`repro.kernels.imgs_project` calls per
block, each a pair of k-by-N GEMVs plus a host-visible while_loop — the
per-basis bound the paper's Sec. 4 predicts for iterated MGS.  This kernel
is the panel factorization fix (cf. Quintana-Orti's BLAS-3 QR and Demmel et
al.'s CA-RRQR, both cited in PAPERS.md): project the WHOLE (N, p) candidate
panel against Q in one pass,

  proj:   C = Q^H V        (K, p)  — one read of Q per panel,
  update: V' = V - Q C     (N, p)  — rank-K panel update,

so k*p*N GEMM work replaces p separate k*N GEMV chains.  Two pallas_calls
(the reduction C needs all rows of Q before the update can start — a true
dependency), mirroring :mod:`repro.kernels.imgs_project.kernel` with the
candidate panel V^T (p, N) in place of the single row vector:

  proj:   grid (K/kt, N/nt), accumulate  c_tile += vt_blk @ Q_blk  in VMEM.
  update: grid (N/nt, K/kt), accumulate  p_tile += c_blk @ Q_blk^T; then
          v' = v - p at the last k-block.

Tiles default to (nt, kt) = (1024, 512): Q blocks are 2 MB f32 in VMEM; the
panel adds p rows per tile (p is padded to a sublane multiple by ops.py;
padded rows are zero and project to zero).  Complex inputs use the real
embedding in ops.py (see there), so the kernel itself is real-only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import PRECISION


def _proj_kernel(vt_ref, q_ref, c_ref, c_scr):
    n_i = pl.program_id(1)
    n_blocks = pl.num_programs(1)

    @pl.when(n_i == 0)
    def _():
        c_scr[...] = jnp.zeros_like(c_scr)

    # (p, nt) @ (nt, kt) -> (p, kt): the panel's C^T tile
    c_scr[...] += jnp.dot(
        vt_ref[...], q_ref[...], precision=PRECISION,
        preferred_element_type=jnp.float32
    )

    @pl.when(n_i == n_blocks - 1)
    def _():
        c_ref[...] = c_scr[...].astype(c_ref.dtype)


def _update_kernel(vt_ref, q_ref, c_ref, out_ref, p_scr):
    k_i = pl.program_id(1)
    k_blocks = pl.num_programs(1)

    @pl.when(k_i == 0)
    def _():
        p_scr[...] = jnp.zeros_like(p_scr)

    # (p, kt) @ (kt, nt) -> (p, nt): the rank-K panel update tile
    p_scr[...] += jnp.dot(
        c_ref[...], q_ref[...].T, precision=PRECISION,
        preferred_element_type=jnp.float32
    )

    @pl.when(k_i == k_blocks - 1)
    def _():
        out_ref[...] = vt_ref[...] - p_scr[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("nt", "kt", "interpret"))
def imgs_panel_real(vt, Q, nt: int = 1024, kt: int = 512,
                    interpret: bool = True):
    """One panel GS pass on padded real inputs: returns (vt', ct).

    vt: (p, N) = V^T; Q: (N, K); p % 8 == 0, N % nt == 0, K % kt == 0.
    ct is C^T with shape (p, K).
    """
    p, _ = vt.shape
    N, K = Q.shape
    ct = pl.pallas_call(
        _proj_kernel,
        grid=(K // kt, N // nt),
        in_specs=[
            pl.BlockSpec((p, nt), lambda k, n: (0, n)),
            pl.BlockSpec((nt, kt), lambda k, n: (n, k)),
        ],
        out_specs=pl.BlockSpec((p, kt), lambda k, n: (0, k)),
        out_shape=jax.ShapeDtypeStruct((p, K), Q.dtype),
        scratch_shapes=[pltpu.VMEM((p, kt), jnp.float32)],
        interpret=interpret,
    )(vt, Q)

    vt_out = pl.pallas_call(
        _update_kernel,
        grid=(N // nt, K // kt),
        in_specs=[
            pl.BlockSpec((p, nt), lambda n, k: (0, n)),
            pl.BlockSpec((nt, kt), lambda n, k: (n, k)),
            pl.BlockSpec((p, kt), lambda n, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((p, nt), lambda n, k: (0, n)),
        out_shape=jax.ShapeDtypeStruct((p, N), vt.dtype),
        scratch_shapes=[pltpu.VMEM((p, nt), jnp.float32)],
        interpret=interpret,
    )(vt, Q, ct)
    return vt_out, ct
