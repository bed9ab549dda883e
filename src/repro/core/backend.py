"""Backend dispatch for the greedy hot-loop primitives.

Every greedy driver in this repo — single-device (:mod:`repro.core.greedy`),
blocked (:mod:`repro.core.block_greedy`) and column-sharded
(:mod:`repro.core.distributed`) — spends its time in exactly two primitives:

  pivot_update   the paper's Eq.-(6.3) sweep: ``c = q^H S``,
                 ``acc += |c|^2``, masked residual argmax — one read of the
                 snapshot shard per basis vector (Fig. 6.1a),
  project_pass   one classical-GS projection ``c = Q^H v``,
                 ``v' = v - Q c`` — the body of Hoffmann's iterated GS
                 (Fig. 6.1b).

This module is the single point where those primitives are routed to an
implementation:

  ``pallas``   the fused Pallas TPU kernels
               (:mod:`repro.kernels.greedy_update`,
               :mod:`repro.kernels.imgs_project`) — one HBM pass, argmax
               masking for padded columns, split re/im planes for complex;
               off-TPU they run in interpret mode (slow, parity-testing
               only),
  ``xla``      ``jnp`` ops fused by XLA — the fast path on CPU/GPU.
               Complex inputs run on split re/im planes (four real GEMVs),
               mirroring the Pallas kernels: XLA lowers a complex GEMV to a
               scalar loop ~10x slower than its real counterpart,
  ``xla_ref``  the literal reference ops (:mod:`..kernels.*.ref`, complex
               GEMV included) — the seed implementation, kept as the
               numerical oracle and the benchmark baseline.

Dispatch contract
-----------------

* Selection happens at **trace time** (it is a plain Python decision), so a
  backend choice is baked into each jitted computation; drivers thread
  ``backend=`` through as a static argument.
* Precedence: explicit ``backend=`` argument > ``REPRO_GREEDY_BACKEND``
  environment variable > :func:`set_default_backend` > ``"auto"``
  (``pallas`` iff the default JAX backend is TPU).
* Both implementations satisfy the same numerical contract (identical
  signatures and semantics, see ``kernels/*/ref.py``); pivot-for-pivot
  parity of whole drivers is asserted in ``tests/test_backend.py``.
* Six primitives are dispatched: ``pivot_update`` and ``project_pass``
  (above), the two blocked panel forms used by the block-pivoted
  drivers: ``block_sweep`` (the BLAS-3 Eq.-(6.3) sweep;
  :mod:`repro.kernels.block_sweep` — one read of S per p bases) and
  ``panel_project`` (the BLAS-3 classical-GS projection of a whole (N, p)
  candidate panel; :mod:`repro.kernels.imgs_panel` — one read of Q per
  panel instead of per candidate), plus the two sketch GEMMs the
  randomized range-finder (:mod:`repro.core.randomized`) streams tiles
  through: ``sketch_fold`` (``Y += T @ Omega``) and ``sketch_project``
  (``T^H @ Y``).  Both are pure GEMMs, already MXU/BLAS-3-shaped, so the
  ``pallas`` route shares the ``xla`` plane-split form (XLA emits the
  optimal GEMM; there is nothing left for a hand-written kernel to fuse)
  while keeping the no-complex-dot HLO guarantee.
* Each hot primitive also has a ``batched_*`` form carrying a leading
  B-lane axis (stacked per-lane snapshots, or one shared snapshot matrix
  swept by all lanes in a single fused GEMM) — the building blocks of the
  lockstep many-basis driver (:mod:`repro.core.batch_greedy`); see the
  "batched (B-lane) forms" section below.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels.block_sweep.ops import block_sweep as _pallas_block
from repro.kernels.common import matmul as _mm
from repro.kernels.block_sweep.ref import block_sweep_ref as _xla_block
from repro.kernels.greedy_update.ops import greedy_update as _pallas_pivot
from repro.kernels.greedy_update.ref import greedy_update_ref as _xla_pivot
from repro.kernels.imgs_panel.ops import imgs_panel as _pallas_panel
from repro.kernels.imgs_panel.ref import imgs_panel_ref as _xla_panel
from repro.kernels.imgs_project.ops import imgs_project as _pallas_project
from repro.kernels.imgs_project.ref import imgs_project_ref as _xla_project

VALID_BACKENDS = ("auto", "xla", "pallas", "xla_ref")

_ENV_VAR = "REPRO_GREEDY_BACKEND"
_default_backend = "auto"


def set_default_backend(name: str) -> None:
    """Set the process-wide default backend (overridden by env/explicit)."""
    global _default_backend
    if name not in VALID_BACKENDS:
        raise ValueError(
            f"unknown greedy backend {name!r}; valid: {VALID_BACKENDS}"
        )
    _default_backend = name


def default_backend() -> str:
    return _default_backend


def resolve_backend(backend: str | None = None) -> str:
    """Resolve a backend request to a concrete implementation name.

    Returns ``"pallas"``, ``"xla"`` or ``"xla_ref"``.  ``None`` consults
    the ``REPRO_GREEDY_BACKEND`` env var, then :func:`default_backend`; the
    ``"auto"`` policy picks the fused Pallas kernels exactly when running
    on TPU (interpret-mode Pallas is a debugging tool, not a fast path).
    """
    if backend is None:
        backend = os.environ.get(_ENV_VAR) or _default_backend
    if backend not in VALID_BACKENDS:
        raise ValueError(
            f"unknown greedy backend {backend!r}; valid: {VALID_BACKENDS}"
        )
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return backend


def _plane_split_pivot(q, S, acc, norms_sq):
    """Complex Eq.-(6.3) sweep as four real GEMVs on split re/im planes.

    Mirrors the Pallas kernel's plane decomposition (TPU MXUs are real) —
    and is the fast path on CPU/GPU too: XLA lowers a complex GEMV to a
    scalar loop that is an order of magnitude slower than its real GEMVs
    (measured 709 ms vs 66 ms for c64 at N=4096, M=16384 on 1 CPU core).
    Same math as ``q.conj() @ S`` up to float summation order.
    """
    qr, qi = q.real, q.imag
    Sr, Si = S.real, S.imag
    cr = _mm(qr, Sr) + _mm(qi, Si)   # Re(q^H S)
    ci = _mm(qr, Si) - _mm(qi, Sr)   # Im(q^H S)
    c = jax.lax.complex(cr, ci).astype(S.dtype)
    acc_out = acc + (cr * cr + ci * ci).astype(acc.dtype)
    res = norms_sq - acc_out
    return c, acc_out, jnp.max(res), jnp.argmax(res).astype(jnp.int32)


def _plane_split_project(v, Q):
    """Complex GS projection pass on split re/im planes (see
    :func:`_plane_split_pivot` for why)."""
    Qr, Qi = Q.real, Q.imag
    vr, vi = v.real, v.imag
    # c = Q^H v = (Qr - i Qi)^T (vr + i vi)
    cr = _mm(vr, Qr) + _mm(vi, Qi)
    ci = _mm(vi, Qr) - _mm(vr, Qi)
    # v' = v - Q c
    vr_out = vr - (_mm(Qr, cr) - _mm(Qi, ci))
    vi_out = vi - (_mm(Qr, ci) + _mm(Qi, cr))
    return (
        jax.lax.complex(vr_out, vi_out).astype(v.dtype),
        jax.lax.complex(cr, ci).astype(Q.dtype),
    )


def pivot_update(
    q: jax.Array,
    S: jax.Array,
    acc: jax.Array,
    norms_sq: jax.Array,
    backend: str | None = None,
):
    """Fused Eq.-(6.3) update: ``c = q^H S``, ``acc += |c|^2``, argmax.

    Returns ``(c, acc_out, max_res, argmax)`` — identical semantics on both
    backends (see :func:`repro.kernels.greedy_update.ref.greedy_update_ref`).
    ``max_res``/``argmax`` describe the residual AFTER this update, i.e. the
    next iteration's pivot; drivers that re-derive the pivot from
    ``norms_sq - acc`` may ignore them (XLA dead-code-eliminates the ref
    computation; the Pallas kernel produces them for free in the same pass).
    Complex snapshots run on split re/im planes under either backend.
    """
    resolved = resolve_backend(backend)
    if resolved == "pallas":
        return _pallas_pivot(q, S, acc, norms_sq)
    if resolved == "xla" and jnp.iscomplexobj(S):
        return _plane_split_pivot(q, S, acc, norms_sq)
    return _xla_pivot(q, S, acc, norms_sq)


def project_pass(
    v: jax.Array,
    Q: jax.Array,
    backend: str | None = None,
):
    """One classical-GS pass: returns ``(v - Q Q^H v, Q^H v)``."""
    resolved = resolve_backend(backend)
    if resolved == "pallas":
        return _pallas_project(v, Q)
    if resolved == "xla" and jnp.iscomplexobj(Q):
        return _plane_split_project(v, Q)
    return _xla_project(v, Q)


def _plane_split_panel_project(V, Q):
    """Complex classical-GS PANEL projection on split re/im planes (see
    :func:`_plane_split_pivot` for why: XLA lowers complex matmuls on CPU
    to scalar loops an order of magnitude slower than their real
    counterparts).  Same math as ``(V - Q (Q^H V), Q^H V)`` up to float
    summation order — four real GEMMs per half instead of two complex
    GEMMs."""
    Qr, Qi = Q.real, Q.imag
    Vr, Vi = V.real, V.imag
    # C = Q^H V = (Qr - i Qi)^T (Vr + i Vi)
    Cr = _mm(Qr.T, Vr) + _mm(Qi.T, Vi)
    Ci = _mm(Qr.T, Vi) - _mm(Qi.T, Vr)
    # V' = V - Q C
    Vr_out = Vr - (_mm(Qr, Cr) - _mm(Qi, Ci))
    Vi_out = Vi - (_mm(Qr, Ci) + _mm(Qi, Cr))
    return (
        jax.lax.complex(Vr_out, Vi_out).astype(V.dtype),
        jax.lax.complex(Cr, Ci).astype(Q.dtype),
    )


def panel_project(
    V: jax.Array,
    Q: jax.Array,
    backend: str | None = None,
):
    """One classical-GS PANEL pass: returns ``(V - Q Q^H V, Q^H V)``.

    The BLAS-3 form of :func:`project_pass` applied to a whole (N, p)
    candidate panel at once — one read of Q per panel instead of per
    candidate, so k*p*N GEMM work replaces p separate k*N GEMV chains
    (the panel-factorization idea of the blocked-QR literature; see
    :mod:`repro.kernels.imgs_panel`).  ``pallas`` routes to the fused
    panel kernel; ``xla`` runs the ``jnp`` GEMM form with complex inputs
    on split re/im planes (mirroring :func:`project_pass`); ``xla_ref``
    is the literal reference
    (:func:`repro.kernels.imgs_panel.ref.imgs_panel_ref`, complex GEMM
    included).
    """
    resolved = resolve_backend(backend)
    if resolved == "pallas":
        return _pallas_panel(V, Q)
    if resolved == "xla" and jnp.iscomplexobj(Q):
        return _plane_split_panel_project(V, Q)
    return _xla_panel(V, Q)


def _plane_split_block_sweep(Qnew, S, acc):
    """Complex blocked Eq.-(6.3) sweep as four real GEMMs on split re/im
    planes (see :func:`_plane_split_pivot` for why: XLA lowers complex
    matmuls on CPU to scalar loops an order of magnitude slower than their
    real counterparts).  Same math as ``Qnew.conj().T @ S`` up to float
    summation order."""
    Qr, Qi = Qnew.real, Qnew.imag
    Sr, Si = S.real, S.imag
    # C = Qnew^H S = (Qr - i Qi)^T (Sr + i Si)
    Cr = _mm(Qr.T, Sr) + _mm(Qi.T, Si)
    Ci = _mm(Qr.T, Si) - _mm(Qi.T, Sr)
    C = jax.lax.complex(Cr, Ci).astype(S.dtype)
    acc_out = acc + jnp.sum(Cr * Cr + Ci * Ci, axis=0).astype(acc.dtype)
    return C, acc_out


def block_sweep(
    Qnew: jax.Array,
    S: jax.Array,
    acc: jax.Array,
    backend: str | None = None,
):
    """Blocked Eq.-(6.3) sweep: ``C = Qnew^H S``, ``acc += sum_i |C_i|^2``.

    One read of S per p bases — the block-greedy amortization that turns
    the memory-roof-bound BLAS-2 pivot sweep into a BLAS-3 panel GEMM.
    ``pallas`` routes to the fused panel kernel
    (:mod:`repro.kernels.block_sweep`); ``xla`` runs the ``jnp`` GEMM form,
    with complex inputs on split re/im planes (four real GEMMs, mirroring
    :func:`pivot_update`); ``xla_ref`` is the literal reference
    (:func:`repro.kernels.block_sweep.ref.block_sweep_ref`, complex GEMM
    included).
    """
    resolved = resolve_backend(backend)
    if resolved == "pallas":
        return _pallas_block(Qnew, S, acc)
    if resolved == "xla" and jnp.iscomplexobj(S):
        return _plane_split_block_sweep(Qnew, S, acc)
    return _xla_block(Qnew, S, acc)


def _plane_split_sketch_fold(T, Omega, Y):
    """Complex sketch fold ``Y += T @ Omega`` as four real GEMMs on split
    re/im planes (see :func:`_plane_split_pivot` for why: XLA lowers
    complex matmuls on CPU to scalar loops an order of magnitude slower
    than their real counterparts).  Same math as ``Y + T @ Omega`` up to
    float summation order."""
    Tr, Ti = T.real, T.imag
    Or, Oi = Omega.real, Omega.imag
    Yr = Y.real + (_mm(Tr, Or) - _mm(Ti, Oi))
    Yi = Y.imag + (_mm(Tr, Oi) + _mm(Ti, Or))
    return jax.lax.complex(Yr, Yi).astype(Y.dtype)


def sketch_fold(
    T: jax.Array,
    Omega: jax.Array,
    Y: jax.Array,
    backend: str | None = None,
):
    """One tile's contribution to the randomized sketch: ``Y + T @ Omega``.

    ``T`` is an (N, m) snapshot tile, ``Omega`` the matching (m, ell) test
    block, ``Y`` the running (N, ell) sketch ``Y = S @ Omega`` — the
    single-pass range-finder accumulation of :mod:`repro.core.randomized`.
    ``xla``/``pallas`` run complex inputs on split re/im planes (four real
    GEMMs, mirroring :func:`block_sweep`; the sketch GEMM is already
    BLAS-3/MXU-shaped, so there is no dedicated Pallas kernel);
    ``xla_ref`` is the literal form, complex GEMM included.
    """
    resolved = resolve_backend(backend)
    if resolved != "xla_ref" and jnp.iscomplexobj(T):
        return _plane_split_sketch_fold(T, Omega, Y)
    return Y + _mm(T, Omega)


# ------------------------------------------------ batched (B-lane) forms ----
# Every primitive above gains a leading batch axis so B independent builds
# run as ONE dispatch (:mod:`repro.core.batch_greedy`).  Two layouts:
#
#   stacked   S: (B, N, M) — one snapshot matrix per lane.  The ``xla``
#             route is ``jax.vmap`` of the scalar route, which lowers to a
#             batched dot_general whose per-lane floats are BITWISE equal
#             to the scalar GEMV/GEMM (both operands carry the batch axis,
#             so XLA runs the same per-lane kernel; asserted in
#             tests/test_batch_greedy.py).
#   shared    S: (N, M) — one snapshot matrix, B basis states (e.g. a tau
#             sweep).  The ``xla`` route stacks the B query vectors (and,
#             for complex, their re/im planes) into ONE GEMM, so each
#             lockstep round reads S from DRAM once instead of B times —
#             the roofline win the batched driver exists for.  GEMM rows
#             are not bitwise-equal to the scalar GEMV (different float
#             summation order; same pivots — the blocked-driver precedent).
#
# ``xla_ref`` vmaps the literal reference ops in the stacked layout (vmap
# with BOTH operands batched runs the same per-lane kernel as the scalar
# call, hence bitwise; a per-lane Python loop is NOT — slicing fuses into
# the GEMV/GEMM lowering and changes its FMA pattern) and loops them per
# lane in the shared layout (the literal oracle; a shared-operand vmap
# would lower to one fused GEMM, i.e. the thing being tested).  ``pallas``
# loops the fused kernels per lane, except ``batched_block_sweep`` which
# routes to the dedicated batched Pallas variant.


def _barrier_lane_loop(op, nout: int, *batched_args):
    """Per-lane loop with an optimization barrier around each lane's
    operand slices.

    The barrier keeps XLA from merging the lanes' dots into one batched
    dot or fusing the slice into the GEMV lowering — either rewrite
    changes the float summation/FMA pattern, and the whole point of this
    route is that each lane compiles exactly like the scalar op.  Used
    for the complex stacked layout, where neither ``jax.vmap`` of the
    plane-split ops nor of the literal complex ops is bitwise per lane
    (XLA merges a scalar ``a @ b + c @ d`` into one concatenated dot but
    does not apply the same rewrite to the batched form).
    """
    B = batched_args[0].shape[0]
    outs = []
    for b in range(B):
        lane = jax.lax.optimization_barrier(
            tuple(a[b] for a in batched_args))
        outs.append(op(*lane))
    if nout == 1:
        return jnp.stack(outs)
    return tuple(jnp.stack([o[i] for o in outs]) for i in range(nout))


def _is_shared(S_or_stack, batch: int) -> bool:
    if S_or_stack.ndim == 2:
        return True
    if S_or_stack.ndim == 3:
        if S_or_stack.shape[0] != batch:
            raise ValueError(
                f"stacked snapshot batch {S_or_stack.shape[0]} != query "
                f"batch {batch}")
        return False
    raise ValueError(
        f"snapshot operand must be (N, M) shared or (B, N, M) stacked, "
        f"got shape {S_or_stack.shape}")


def _fused_shared_pivot(q, S, acc, norms_sq):
    """Shared-S batched Eq.-(6.3) sweep: one read of S for all B lanes.

    Complex planes of all B query vectors stack into L = [[Qr], [Qi]]
    (2B, N); two real GEMMs ``L @ Sr`` / ``L @ Si`` read each plane ONCE,
    then recombine:  cr = (L Sr)[:B] + (L Si)[B:],
                     ci = (L Si)[:B] - (L Sr)[B:].
    """
    B = q.shape[0]
    if jnp.iscomplexobj(S):
        L = jnp.concatenate([q.real, q.imag], axis=0)     # (2B, N)
        Sr, Si = S.real, S.imag
        A = _mm(L, Sr)                                    # (2B, M)
        Bm = _mm(L, Si)
        cr = A[:B] + Bm[B:]
        ci = Bm[:B] - A[B:]
        c = jax.lax.complex(cr, ci).astype(S.dtype)
        acc_out = acc + (cr * cr + ci * ci).astype(acc.dtype)
    else:
        c = _mm(q, S)                                     # (B, M) one GEMM
        acc_out = acc + (c * c).astype(acc.dtype)
    res = norms_sq - acc_out
    return (c, acc_out, jnp.max(res, axis=1),
            jnp.argmax(res, axis=1).astype(jnp.int32))


def batched_pivot_update(
    q: jax.Array,
    S: jax.Array,
    acc: jax.Array,
    norms_sq: jax.Array,
    backend: str | None = None,
):
    """B-lane Eq.-(6.3) sweep: per-lane ``c = q_b^H S_b``, acc, argmax.

    Args:
      q:        (B, N) one current basis vector per lane.
      S:        (B, N, M) stacked or (N, M) shared snapshots.
      acc:      (B, M) per-lane accumulated ``|c|^2``.
      norms_sq: (B, M) per-lane reference norms.

    Returns ``(c, acc_out, max_res, argmax)`` with shapes
    ((B, M), (B, M), (B,), (B,)) — lane b equals
    :func:`pivot_update` on its slice (bitwise in the stacked layout,
    pivot-for-pivot in the shared layout; see the section comment).
    """
    resolved = resolve_backend(backend)
    B = q.shape[0]
    shared = _is_shared(S, B)
    if resolved == "xla" and shared:
        return _fused_shared_pivot(q, S, acc, norms_sq)
    if resolved != "pallas" and not shared:
        if jnp.iscomplexobj(S):
            # complex lanes: barrier loop (see _barrier_lane_loop — no
            # vmapped form is bitwise per lane here)
            inner = (_plane_split_pivot if resolved == "xla"
                     else _xla_pivot)
            return _barrier_lane_loop(inner, 4, q, S, acc, norms_sq)
        # real lanes: vmap of the scalar op (BOTH operands batched) runs
        # the same per-lane kernel XLA picks for the scalar call —
        # bitwise per lane.  A bare per-lane Python loop is NOT: slicing
        # fuses into the GEMV lowering and changes its FMA pattern.
        return jax.vmap(_xla_pivot)(q, S, acc, norms_sq)
    op = _pallas_pivot if resolved == "pallas" else _xla_pivot
    outs = [op(q[b], S if shared else S[b], acc[b], norms_sq[b])
            for b in range(B)]
    return tuple(jnp.stack([o[i] for o in outs]) for i in range(4))


def batched_project_pass(
    v: jax.Array,
    Q: jax.Array,
    backend: str | None = None,
):
    """B-lane classical-GS pass: per lane ``(v_b - Q_b Q_b^H v_b, Q_b^H
    v_b)`` with ``v`` (B, N) and ``Q`` (B, N, k).  The basis is always
    per-lane (each lane orthogonalizes against its own Q), so there is no
    shared layout here; ``xla``/``xla_ref`` are the vmapped scalar routes
    (bitwise per-lane — see :func:`batched_pivot_update` for why a
    per-lane loop is not), ``pallas`` loops the fused kernel."""
    resolved = resolve_backend(backend)
    if resolved != "pallas":
        if jnp.iscomplexobj(Q):
            inner = (_plane_split_project if resolved == "xla"
                     else _xla_project)
            return _barrier_lane_loop(inner, 2, v, Q)
        return jax.vmap(_xla_project)(v, Q)
    outs = [_pallas_project(v[b], Q[b]) for b in range(v.shape[0])]
    return tuple(jnp.stack([o[i] for o in outs]) for i in range(2))


def batched_panel_project(
    V: jax.Array,
    Q: jax.Array,
    backend: str | None = None,
):
    """B-lane classical-GS PANEL pass: per lane ``(V_b - Q_b Q_b^H V_b,
    Q_b^H V_b)`` with ``V`` (B, N, p) and ``Q`` (B, N, k).  Routing as in
    :func:`batched_project_pass`."""
    resolved = resolve_backend(backend)
    if resolved != "pallas":
        if jnp.iscomplexobj(Q):
            inner = (_plane_split_panel_project if resolved == "xla"
                     else _xla_panel)
            return _barrier_lane_loop(inner, 2, V, Q)
        return jax.vmap(_xla_panel)(V, Q)
    outs = [_pallas_panel(V[b], Q[b]) for b in range(V.shape[0])]
    return tuple(jnp.stack([o[i] for o in outs]) for i in range(2))


def _fused_shared_block_sweep(Qnew, S, acc):
    """Shared-S batched blocked sweep: all B (N, p) panels stack into one
    (B*p, N) x (N, M) GEMM pair, reading each plane of S once.  The
    kernel-fused per-column sums are recomputed per lane from C (each
    lane's acc only sums its OWN p rows)."""
    B, N, p = Qnew.shape
    Qh = jnp.swapaxes(Qnew, 1, 2).reshape(B * p, N)       # (B*p, N)
    if jnp.iscomplexobj(S):
        L = jnp.concatenate([Qh.real, Qh.imag], axis=0)   # (2Bp, N)
        Sr, Si = S.real, S.imag
        A = _mm(L, Sr)
        Bm = _mm(L, Si)
        Cr = A[:B * p] + Bm[B * p:]
        Ci = Bm[:B * p] - A[B * p:]
        C = jax.lax.complex(Cr, Ci).astype(S.dtype).reshape(B, p, -1)
        sq = (Cr * Cr + Ci * Ci).reshape(B, p, -1)
    else:
        C = _mm(Qh, S).reshape(B, p, -1)
        sq = C * C
    acc_out = acc + jnp.sum(sq, axis=1).astype(acc.dtype)
    return C, acc_out


def batched_block_sweep(
    Qnew: jax.Array,
    S: jax.Array,
    acc: jax.Array,
    backend: str | None = None,
):
    """B-lane blocked Eq.-(6.3) sweep: per lane ``C_b = Qnew_b^H S_b``,
    ``acc_b += sum_i |C_b,i|^2``.

    Args:
      Qnew: (B, N, p) one panel of new basis vectors per lane.
      S:    (B, N, M) stacked or (N, M) shared snapshots.
      acc:  (B, M) per-lane accumulated sums.

    Returns ``(C, acc_out)`` with shapes ((B, p, M), (B, M)).  ``pallas``
    routes to the batched Pallas variant
    (:func:`repro.kernels.block_sweep.ops.batched_block_sweep`): per-lane
    fused kernels when stacked, one stacked-panel kernel call when shared.
    """
    resolved = resolve_backend(backend)
    B = Qnew.shape[0]
    shared = _is_shared(S, B)
    if resolved == "pallas":
        from repro.kernels.block_sweep.ops import (
            batched_block_sweep as _pallas_batched_block,
        )

        return _pallas_batched_block(Qnew, S, acc)
    if resolved == "xla" and shared:
        return _fused_shared_block_sweep(Qnew, S, acc)
    if not shared:
        if jnp.iscomplexobj(S):
            inner = (_plane_split_block_sweep if resolved == "xla"
                     else _xla_block)
            return _barrier_lane_loop(inner, 2, Qnew, S, acc)
        return jax.vmap(_xla_block)(Qnew, S, acc)
    outs = [_xla_block(Qnew[b], S, acc[b]) for b in range(B)]
    return tuple(jnp.stack([o[i] for o in outs]) for i in range(2))


def batched_sketch_fold(
    T: jax.Array,
    Omega: jax.Array,
    Y: jax.Array,
    backend: str | None = None,
):
    """B-lane sketch fold: per lane ``Y_b + T_b @ Omega_b``.

    ``T`` is (B, N, m) stacked or (N, m) shared; ``Omega`` (B, m, ell)
    stacked or (m, ell) shared (a shared test block sketches every lane
    against the same directions — comparable sketches across lanes);
    ``Y`` is always (B, N, ell).  Routing mirrors :func:`sketch_fold`
    (``pallas`` shares the ``xla`` plane-split GEMM form).
    """
    resolved = resolve_backend(backend)
    B = Y.shape[0]
    t_ax = None if _is_shared(T, B) else 0
    o_ax = None if Omega.ndim == 2 else 0
    if resolved != "xla_ref" or t_ax == 0:
        inner = (_plane_split_sketch_fold
                 if resolved != "xla_ref" and jnp.iscomplexobj(T)
                 else (lambda t, o, y: y + _mm(t, o)))
        return jax.vmap(inner, in_axes=(t_ax, o_ax, 0))(T, Omega, Y)
    outs = [Y[b] + _mm(T, Omega if o_ax is None else Omega[b])
            for b in range(B)]
    return jnp.stack(outs)


def _plane_split_sketch_project(T, Y):
    """Complex sketch co-range projection ``T^H @ Y`` as four real GEMMs
    on split re/im planes (see :func:`_plane_split_pivot`)."""
    Tr, Ti = T.real, T.imag
    Yr, Yi = Y.real, Y.imag
    # Z = T^H Y = (Tr - i Ti)^T (Yr + i Yi)
    Zr = _mm(Tr.T, Yr) + _mm(Ti.T, Yi)
    Zi = _mm(Tr.T, Yi) - _mm(Ti.T, Yr)
    return jax.lax.complex(Zr, Zi).astype(T.dtype)


def sketch_project(
    T: jax.Array,
    Y: jax.Array,
    backend: str | None = None,
):
    """One tile's co-range projection for the power pass: ``T^H @ Y``.

    ``T`` is an (N, m) snapshot tile, ``Y`` the current (N, ell) range
    estimate; the returned (m, ell) block is this tile's row slab of
    ``Z = S^H Y`` (the odd pass of a randomized power iteration).  Backend
    routing mirrors :func:`sketch_fold`.
    """
    resolved = resolve_backend(backend)
    if resolved != "xla_ref" and jnp.iscomplexobj(T):
        return _plane_split_sketch_project(T, Y)
    return _mm(T.conj().T, Y)
