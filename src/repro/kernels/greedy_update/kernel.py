"""Pallas TPU kernel for the greedy pivot-search update (paper Fig. 6.1a).

The paper's hot loop is the per-iteration O(2MN) sweep: project every local
column onto the newly revealed basis vector (``c = q^H S``), update the
accumulated residual sums (Eq. 6.3) and find the local pivot (argmax).  The
serial code vectorizes this with AVX2; on TPU we fuse all three steps into
one Pallas kernel so the shard of S is read from HBM exactly once:

  unfused: read S (matvec) -> write c -> read c + acc (norm update + argmax)
  fused:   read S once; c, acc and per-block max/argmax produced in VMEM.

The sweep is memory-bound (arithmetic intensity ~2 FLOP per 4 bytes for f32,
~8 FLOP per 16 bytes for c64), so minimizing HBM traffic is the entire game
— the fusion is worth ~1.5x on the roofline (S is by far the dominant
stream; see the ``perf_greedy_fusion`` row in BENCH_greedy.json).

Complex snapshots (the GW production case) are handled as split re/im planes
(TPU MXUs are real): ``c = q^H S`` becomes four real matvecs evaluated in the
same pass.

Tiling: S is blocked (Nt x Mt) in VMEM with the column dimension M as the
outer (parallel) grid axis and the row dimension N as the inner (reduction)
axis, accumulating partial dot products into a VMEM scratch of width Mt.
Default (Nt, Mt) = (256, 1024): f32 S-blocks of 1 MB per plane, and
Mt = 1024 = 8 * 128 lanes keeps the MXU/VPU fully shaped.  The dots run at
full f32 (``common.PRECISION``), for which Mosaic splits each block into
bf16 parts in VMEM: at Nt = 512 the complex kernel needs ~25 MB, over the
16 MB scoped-VMEM default (tests/test_tpu_compile.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, PRECISION

NEG_LARGE = -1e30


def _write_block_max(res, m_i, bmax_ref, bidx_ref):
    """Store this column block's residual max and its global column index.

    Mosaic blocks must be (8, 128)-aligned or span the whole array, so the
    per-block outputs are one lane row wide: each (1, LANES) block holds
    its scalar broadcast across the lanes and ops.py reads lane 0.
    """
    mt = res.shape[1]
    local = jax.lax.argmax(res[0], 0, jnp.int32)
    bmax_ref[...] = jnp.full(bmax_ref.shape, jnp.max(res), bmax_ref.dtype)
    bidx_ref[...] = jnp.full(bidx_ref.shape, local + m_i * mt,
                             bidx_ref.dtype)


def _kernel_real(q_ref, s_ref, acc_ref, norms_ref,
                 c_ref, acc_out_ref, bmax_ref, bidx_ref, c_scr):
    m_i = pl.program_id(0)
    n_i = pl.program_id(1)
    n_blocks = pl.num_programs(1)

    @pl.when(n_i == 0)
    def _():
        c_scr[...] = jnp.zeros_like(c_scr)

    c_scr[...] += jnp.dot(
        q_ref[...], s_ref[...], precision=PRECISION,
        preferred_element_type=jnp.float32
    )

    @pl.when(n_i == n_blocks - 1)
    def _():
        c = c_scr[...]
        c_ref[...] = c.astype(c_ref.dtype)
        acc = acc_ref[...] + c * c
        acc_out_ref[...] = acc
        res = norms_ref[...] - acc
        _write_block_max(res, m_i, bmax_ref, bidx_ref)


def _kernel_complex(qr_ref, qi_ref, sr_ref, si_ref, acc_ref, norms_ref,
                    cr_ref, ci_ref, acc_out_ref, bmax_ref, bidx_ref,
                    cr_scr, ci_scr):
    m_i = pl.program_id(0)
    n_i = pl.program_id(1)
    n_blocks = pl.num_programs(1)

    @pl.when(n_i == 0)
    def _():
        cr_scr[...] = jnp.zeros_like(cr_scr)
        ci_scr[...] = jnp.zeros_like(ci_scr)

    qr = qr_ref[...]
    qi = qi_ref[...]
    sr = sr_ref[...]
    si = si_ref[...]
    # c = q^H S = (qr - i qi)^T (sr + i si)
    cr_scr[...] += jnp.dot(qr, sr, precision=PRECISION,
                           preferred_element_type=jnp.float32)
    cr_scr[...] += jnp.dot(qi, si, precision=PRECISION,
                           preferred_element_type=jnp.float32)
    ci_scr[...] += jnp.dot(qr, si, precision=PRECISION,
                           preferred_element_type=jnp.float32)
    ci_scr[...] -= jnp.dot(qi, sr, precision=PRECISION,
                           preferred_element_type=jnp.float32)

    @pl.when(n_i == n_blocks - 1)
    def _():
        cr = cr_scr[...]
        ci = ci_scr[...]
        cr_ref[...] = cr.astype(cr_ref.dtype)
        ci_ref[...] = ci.astype(ci_ref.dtype)
        acc = acc_ref[...] + cr * cr + ci * ci
        acc_out_ref[...] = acc
        res = norms_ref[...] - acc
        _write_block_max(res, m_i, bmax_ref, bidx_ref)


@functools.partial(
    jax.jit, static_argnames=("nt", "mt", "interpret")
)
def greedy_update_real(q, S, acc, norms_sq, nt: int = 256, mt: int = 1024,
                       interpret: bool = True):
    """Real-dtype fused update on padded inputs (see ops.py for padding).

    q: (1, N) f32; S: (N, M) f32; acc, norms_sq: (1, M) f32.
    N % nt == 0 and M % mt == 0 must hold.
    """
    N, M = S.shape
    grid = (M // mt, N // nt)
    c, acc_out, bmax, bidx = pl.pallas_call(
        _kernel_real,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, nt), lambda m, n: (0, n)),
            pl.BlockSpec((nt, mt), lambda m, n: (n, m)),
            pl.BlockSpec((1, mt), lambda m, n: (0, m)),
            pl.BlockSpec((1, mt), lambda m, n: (0, m)),
        ],
        out_specs=[
            pl.BlockSpec((1, mt), lambda m, n: (0, m)),
            pl.BlockSpec((1, mt), lambda m, n: (0, m)),
            pl.BlockSpec((1, LANES), lambda m, n: (0, m)),
            pl.BlockSpec((1, LANES), lambda m, n: (0, m)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, M), S.dtype),
            jax.ShapeDtypeStruct((1, M), jnp.float32),
            jax.ShapeDtypeStruct((1, M // mt * LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, M // mt * LANES), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, mt), jnp.float32)],
        interpret=interpret,
    )(q, S, acc, norms_sq)
    return c, acc_out, bmax, bidx


@functools.partial(
    jax.jit, static_argnames=("nt", "mt", "interpret")
)
def greedy_update_complex(qr, qi, Sr, Si, acc, norms_sq,
                          nt: int = 256, mt: int = 1024,
                          interpret: bool = True):
    """Complex fused update on split re/im planes (padded; see ops.py)."""
    N, M = Sr.shape
    grid = (M // mt, N // nt)
    cr, ci, acc_out, bmax, bidx = pl.pallas_call(
        _kernel_complex,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, nt), lambda m, n: (0, n)),
            pl.BlockSpec((1, nt), lambda m, n: (0, n)),
            pl.BlockSpec((nt, mt), lambda m, n: (n, m)),
            pl.BlockSpec((nt, mt), lambda m, n: (n, m)),
            pl.BlockSpec((1, mt), lambda m, n: (0, m)),
            pl.BlockSpec((1, mt), lambda m, n: (0, m)),
        ],
        out_specs=[
            pl.BlockSpec((1, mt), lambda m, n: (0, m)),
            pl.BlockSpec((1, mt), lambda m, n: (0, m)),
            pl.BlockSpec((1, mt), lambda m, n: (0, m)),
            pl.BlockSpec((1, LANES), lambda m, n: (0, m)),
            pl.BlockSpec((1, LANES), lambda m, n: (0, m)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, M), Sr.dtype),
            jax.ShapeDtypeStruct((1, M), Sr.dtype),
            jax.ShapeDtypeStruct((1, M), jnp.float32),
            jax.ShapeDtypeStruct((1, M // mt * LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, M // mt * LANES), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, mt), jnp.float32),
            pltpu.VMEM((1, mt), jnp.float32),
        ],
        interpret=interpret,
    )(qr, qi, Sr, Si, acc, norms_sq)
    return cr, ci, acc_out, bmax, bidx
