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

S is read at its own shape: the grid is ``cdiv(N, Nt) x cdiv(M, Mt)`` and
a ragged last tile is masked in VMEM rather than padded in HBM (a pad of S
would copy the whole shard on every sweep).  The part of a partial block
beyond the array holds whatever the buffer held before, possibly NaN, so
the last row tile's rows at or beyond N are zeroed before the dots (a zero
in q alone would not do: 0 * NaN = NaN), and columns at or beyond M get
residual ``NEG_LARGE`` so they never win the argmax; their ``c`` and
``acc`` fall outside the outputs, whose writes Pallas drops.  Both masks
are static: an aligned shape runs the unmasked code, and the row mask runs
in the last row tile only.
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


def _mask_cols(res, m_i, n_cols):
    """Residual ``NEG_LARGE`` for the columns at or beyond ``n_cols``."""
    if n_cols % res.shape[1] == 0:
        return res
    col = m_i * res.shape[1] + jax.lax.broadcasted_iota(
        jnp.int32, res.shape, 1)
    return jnp.where(col < n_cols, res, NEG_LARGE)


def _sweep_rows(accumulate, s_refs, n_i, n_rows):
    """``accumulate`` the S tiles of this step, with the rows at or beyond
    ``n_rows`` of a ragged last row tile zeroed; full tiles are unmasked."""
    nt = s_refs[0].shape[0]
    tail = n_rows % nt
    if tail == 0:
        accumulate(*(s[...] for s in s_refs))
        return
    last = pl.cdiv(n_rows, nt) - 1

    @pl.when(n_i < last)
    def _():
        accumulate(*(s[...] for s in s_refs))

    @pl.when(n_i == last)
    def _():
        keep = jax.lax.broadcasted_iota(jnp.int32, s_refs[0].shape, 0) < tail
        accumulate(*(jnp.where(keep, s[...], 0) for s in s_refs))


def _kernel_real(q_ref, s_ref, acc_ref, norms_ref,
                 c_ref, acc_out_ref, bmax_ref, bidx_ref, c_scr,
                 *, n_rows, n_cols):
    m_i = pl.program_id(0)
    n_i = pl.program_id(1)
    n_blocks = pl.num_programs(1)

    @pl.when(n_i == 0)
    def _():
        c_scr[...] = jnp.zeros_like(c_scr)

    def accumulate(s):
        c_scr[...] += jnp.dot(
            q_ref[...], s, precision=PRECISION,
            preferred_element_type=jnp.float32
        )

    _sweep_rows(accumulate, (s_ref,), n_i, n_rows)

    @pl.when(n_i == n_blocks - 1)
    def _():
        c = c_scr[...]
        c_ref[...] = c.astype(c_ref.dtype)
        acc = acc_ref[...] + c * c
        acc_out_ref[...] = acc
        res = _mask_cols(norms_ref[...] - acc, m_i, n_cols)
        _write_block_max(res, m_i, bmax_ref, bidx_ref)


def _kernel_complex(qr_ref, qi_ref, sr_ref, si_ref, acc_ref, norms_ref,
                    cr_ref, ci_ref, acc_out_ref, bmax_ref, bidx_ref,
                    cr_scr, ci_scr, *, n_rows, n_cols):
    m_i = pl.program_id(0)
    n_i = pl.program_id(1)
    n_blocks = pl.num_programs(1)

    @pl.when(n_i == 0)
    def _():
        cr_scr[...] = jnp.zeros_like(cr_scr)
        ci_scr[...] = jnp.zeros_like(ci_scr)

    def accumulate(sr, si):
        qr = qr_ref[...]
        qi = qi_ref[...]
        # c = q^H S = (qr - i qi)^T (sr + i si)
        cr_scr[...] += jnp.dot(qr, sr, precision=PRECISION,
                               preferred_element_type=jnp.float32)
        cr_scr[...] += jnp.dot(qi, si, precision=PRECISION,
                               preferred_element_type=jnp.float32)
        ci_scr[...] += jnp.dot(qr, si, precision=PRECISION,
                               preferred_element_type=jnp.float32)
        ci_scr[...] -= jnp.dot(qi, sr, precision=PRECISION,
                               preferred_element_type=jnp.float32)

    _sweep_rows(accumulate, (sr_ref, si_ref), n_i, n_rows)

    @pl.when(n_i == n_blocks - 1)
    def _():
        cr = cr_scr[...]
        ci = ci_scr[...]
        cr_ref[...] = cr.astype(cr_ref.dtype)
        ci_ref[...] = ci.astype(ci_ref.dtype)
        acc = acc_ref[...] + cr * cr + ci * ci
        acc_out_ref[...] = acc
        res = _mask_cols(norms_ref[...] - acc, m_i, n_cols)
        _write_block_max(res, m_i, bmax_ref, bidx_ref)


@functools.partial(
    jax.jit, static_argnames=("nt", "mt", "interpret")
)
def greedy_update_real(q, S, acc, norms_sq, nt: int = 256, mt: int = 1024,
                       interpret: bool = True):
    """Real-dtype fused update (see ops.py for the layout).

    q: (1, cdiv(N, nt) * nt) f32, zero beyond N; S: (N, M) f32 at any
    shape; acc, norms_sq: (1, M) f32.
    """
    N, M = S.shape
    grid = (pl.cdiv(M, mt), pl.cdiv(N, nt))
    c, acc_out, bmax, bidx = pl.pallas_call(
        functools.partial(_kernel_real, n_rows=N, n_cols=M),
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
            jax.ShapeDtypeStruct((1, grid[0] * LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, grid[0] * LANES), jnp.int32),
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
    """Complex fused update on split re/im planes (layout as
    :func:`greedy_update_real`; see ops.py)."""
    N, M = Sr.shape
    grid = (pl.cdiv(M, mt), pl.cdiv(N, nt))
    cr, ci, acc_out, bmax, bidx = pl.pallas_call(
        functools.partial(_kernel_complex, n_rows=N, n_cols=M),
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
            jax.ShapeDtypeStruct((1, grid[0] * LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, grid[0] * LANES), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, mt), jnp.float32),
            pltpu.VMEM((1, mt), jnp.float32),
        ],
        interpret=interpret,
    )(qr, qi, Sr, Si, acc, norms_sq)
    return cr, ci, acc_out, bmax, bidx
