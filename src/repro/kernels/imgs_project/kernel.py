"""Pallas TPU kernel for one iterated-Gram-Schmidt projection pass.

The orthogonalization step (paper Fig. 6.1b) is the second hot-spot:
``c = Q^H v`` followed by the rank-k update ``v' = v - Q c``.  The paper
notes (§6.1.5) that its sequential-MGS formulation precludes BLAS-2; we use
the classical iterated form exactly so that both halves are matvecs that map
onto the MXU (the fix the paper itself suggests via Hoffmann's "CMGSI").

Two pallas_calls (the reduction c needs all rows of Q before the update can
start — a true dependency):

  proj:   grid (K/kt, N/nt), accumulate  c_tile += v_blk @ Q_blk  in VMEM.
  update: grid (N/nt, K/kt), accumulate  p_tile += c_blk @ Q_blk^T; then
          v' = v - p at the last k-block.

Tiles default to (nt, kt) = (1024, 512): Q blocks are 2 MB f32 in VMEM.
Complex inputs use split re/im planes (see greedy_update.kernel for the
rationale).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import PRECISION


def _proj_kernel(v_ref, q_ref, c_ref, c_scr):
    n_i = pl.program_id(1)
    n_blocks = pl.num_programs(1)

    @pl.when(n_i == 0)
    def _():
        c_scr[...] = jnp.zeros_like(c_scr)

    c_scr[...] += jnp.dot(
        v_ref[...], q_ref[...], precision=PRECISION,
        preferred_element_type=jnp.float32
    )

    @pl.when(n_i == n_blocks - 1)
    def _():
        c_ref[...] = c_scr[...].astype(c_ref.dtype)


def _update_kernel(v_ref, q_ref, c_ref, out_ref, p_scr):
    k_i = pl.program_id(1)
    k_blocks = pl.num_programs(1)

    @pl.when(k_i == 0)
    def _():
        p_scr[...] = jnp.zeros_like(p_scr)

    # (1, kt) @ (kt, nt) -> (1, nt)
    p_scr[...] += jnp.dot(
        c_ref[...], q_ref[...].T, precision=PRECISION,
        preferred_element_type=jnp.float32
    )

    @pl.when(k_i == k_blocks - 1)
    def _():
        out_ref[...] = v_ref[...] - p_scr[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("nt", "kt", "interpret"))
def imgs_project_real(v, Q, nt: int = 1024, kt: int = 512,
                      interpret: bool = True):
    """One GS pass on padded real inputs: returns (v', c).

    v: (1, N); Q: (N, K); N % nt == 0, K % kt == 0.
    """
    N, K = Q.shape
    c = pl.pallas_call(
        _proj_kernel,
        grid=(K // kt, N // nt),
        in_specs=[
            pl.BlockSpec((1, nt), lambda k, n: (0, n)),
            pl.BlockSpec((nt, kt), lambda k, n: (n, k)),
        ],
        out_specs=pl.BlockSpec((1, kt), lambda k, n: (0, k)),
        out_shape=jax.ShapeDtypeStruct((1, K), Q.dtype),
        scratch_shapes=[pltpu.VMEM((1, kt), jnp.float32)],
        interpret=interpret,
    )(v, Q)

    v_out = pl.pallas_call(
        _update_kernel,
        grid=(N // nt, K // kt),
        in_specs=[
            pl.BlockSpec((1, nt), lambda n, k: (0, n)),
            pl.BlockSpec((nt, kt), lambda n, k: (n, k)),
            pl.BlockSpec((1, kt), lambda n, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((1, nt), lambda n, k: (0, n)),
        out_shape=jax.ShapeDtypeStruct((1, N), v.dtype),
        scratch_shapes=[pltpu.VMEM((1, nt), jnp.float32)],
        interpret=interpret,
    )(v, Q, c)
    return v_out, c
