"""Block RB-greedy: p pivots per sweep (beyond-paper §Perf optimization).

The paper's algorithm is memory-bound at one full pass over S per basis
vector (the Eq.-6.3 update c = q^H S dominates, arithmetic intensity ~1
FLOP/byte).  The committed perf trajectory confirms it: the float32
hot-path rows in BENCH_greedy.json sit at the DRAM roof (see the README
"Choosing a strategy" guide).

Block pivoting amortizes that read: select the top-p residual columns in
one sweep, orthogonalize them jointly (iterated GS, with a rank guard that
rejects candidates whose residual collapses once the earlier picks in the
block are added), then update ALL column residuals with ONE (p, N) x (N, M)
panel GEMM — one read of S per p bases, cutting the dominant memory term
by ~p.

The trade-off is pivot staleness: picks 2..p within a block are made
against residuals that ignore picks 1..i-1.  For fast-decaying (smooth /
GW) snapshot families the effect is a few extra bases at the same tau —
measured in tests/test_block_greedy.py; the blocked hot-path rows in
BENCH_greedy.json track the speedup.

This is the classical blocked column-pivoted QR idea (cf. the BLAS-3
literature the paper cites: [35] Quintana-Orti; [18] Demmel et al. CA-RRQR)
applied to the paper's Eq.-6.3 greedy bookkeeping.

Two drivers are provided, mirroring :mod:`repro.core.greedy`:

- the chunked device-resident hot path (the front door's
  ``strategy="block_greedy"``): ``chunk`` blocks run inside ONE jitted
  ``lax.while_loop`` — top-p selection, joint IMGS with the in-block rank
  guard, and the fused panel sweep
  (:func:`repro.core.backend.block_sweep`) all execute in the trace, and
  the host syncs only a stop-code scalar per chunk,
- :func:`rb_greedy_block_stepwise` — the eager per-block driver (one
  jitted block step + host sync per block), kept as the parity oracle.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as _backend
from repro.core.greedy import (
    GreedyResult,
    GreedyState,
    STOP_FLOOR,
    STOP_NONE,
    STOP_RANK,
    STOP_REFRESH,
    STOP_TAU,
    _validate_resident_tree,
    floor_estimate,
    greedy_init,
    greedy_refresh,
    imgs_orthogonalize,
    load_resident_checkpoint,
    panel_imgs_orthogonalize,
    resident_state_from_tree,
    save_resident_checkpoint,
)
from repro.spans import span, traced


def _ortho_block(S, Q, top_idx, slots, p, kappa, max_passes, eps, scale,
                 backend, panel):
    """Orthogonalize one block of p pivot candidates against ``Q`` (and
    against each other), with the in-block rank guard.

    ``panel=True`` (the default) runs the BLAS-3 panel path
    (:func:`repro.core.greedy.panel_imgs_orthogonalize`): one iterated
    (k, N) x (N, p) panel projection for the whole block plus a
    within-panel sequential sweep — k*p*N GEMM work instead of p separate
    k*N GEMV chains.  ``panel=False`` keeps the pre-panel path (p
    sequential :func:`imgs_orthogonalize` calls with fixed-slot writes);
    both span the same space and differ only in float summation order.

    Returns ``(Q, Qnew, oks, rnorms, n_passes)`` with the block written
    into ``Q`` at ``slots..slots+p-1`` (rejected candidates leave zero
    "hole" columns).
    """
    thresh = 50.0 * eps * scale
    if panel and p > 1:
        V = jnp.take(S, top_idx, axis=1)            # (N, p)
        Qnew, oks, rnorms, npasses = panel_imgs_orthogonalize(
            V, Q, kappa, max_passes, thresh=thresh, backend=backend
        )
        slots_i = jnp.asarray(slots, jnp.int32)
        Q = jax.lax.dynamic_update_slice(
            Q, Qnew, (jnp.zeros((), jnp.int32), slots_i)
        )
        return Q, Qnew, oks, rnorms, npasses
    qs, oks, rnorms, npasses = [], [], [], []
    for i in range(p):  # p is small and static
        v = jnp.take(S, top_idx[i], axis=1)
        q, _, rnorm, n_pass = imgs_orthogonalize(
            v, Q, kappa, max_passes, backend=backend
        )
        ok = rnorm > thresh
        q = jnp.where(ok, q, jnp.zeros_like(q))
        # fixed-slot write at slots+i; rejected candidates leave zero
        # columns ("holes") that the driver compacts at the end
        Q = Q.at[:, slots + i].set(q)
        qs.append(q)
        oks.append(ok)
        rnorms.append(rnorm)
        npasses.append(n_pass)
    return (
        Q,
        jnp.stack(qs, axis=1),                      # rejected cols zero
        jnp.asarray(oks),
        jnp.stack([jnp.asarray(r) for r in rnorms]),
        jnp.asarray(npasses, jnp.int32),
    )


def block_greedy_step(S, state: GreedyState, p: int, kappa: float = 2.0,
                      max_passes: int = 3,
                      backend: str | None = None,
                      scale=None, panel: bool = True) -> GreedyState:
    """Add up to p bases with a single Eq.-6.3 sweep over S.

    Block orthogonalization and the blocked sweep route through
    :mod:`repro.core.backend` (the sweep's fused kernel is
    :func:`repro.core.backend.block_sweep`; ``panel=True`` additionally
    runs the block's orthogonalization through the BLAS-3
    :func:`repro.core.backend.panel_project` panel — see
    :func:`_ortho_block`).  This is the eager per-block step used by
    :func:`rb_greedy_block_stepwise`; the chunked driver runs the same
    math inside a ``lax.while_loop`` (see :func:`_block_chunk_impl`).

    ``scale`` is the rank guard's reference column scale.  The greedy
    family fixes it at init (``sqrt(max |s_i|^2)``) so the guard measures
    candidates against the ORIGINAL data scale even after an Eq.-(6.3)
    refresh shrinks ``norms_sq``; ``None`` falls back to the in-state
    value (pre-PR-4 behavior, correct when no refresh has happened).
    """
    res_sq = jnp.maximum(state.norms_sq - state.acc, 0.0)
    top_vals, top_idx = jax.lax.top_k(res_sq, p)

    eps = jnp.finfo(state.norms_sq.dtype).eps
    if scale is None:
        scale = jnp.sqrt(jnp.max(state.norms_sq))

    k = state.k
    Q, Qnew, accepted, _, _ = _ortho_block(
        S, state.Q, top_idx, k, p, kappa, max_passes, eps, scale,
        backend, panel,
    )
    # ONE pass over S: (p, M) block sweep through the dispatch layer
    C, acc = _backend.block_sweep(Qnew, S, state.acc, backend=backend)

    R = jax.lax.dynamic_update_slice_in_dim(state.R, C, k, axis=0)
    pivots = jax.lax.dynamic_update_slice_in_dim(
        state.pivots,
        jnp.where(accepted, top_idx, -1).astype(jnp.int32),
        k, axis=0,
    )
    errs = jax.lax.dynamic_update_slice_in_dim(
        state.errs, jnp.sqrt(jnp.maximum(top_vals, 0.0)), k, axis=0
    )
    n_acc = jnp.sum(accepted.astype(jnp.int32))
    return state._replace(
        Q=Q, R=R, acc=acc, pivots=pivots, errs=errs, k=k + n_acc,
    )


@functools.partial(
    jax.jit, static_argnames=("p", "kappa", "max_passes", "backend", "panel")
)
def _jitted_block_step(S, state, p: int, kappa: float = 2.0,
                       max_passes: int = 3, backend: str | None = None,
                       scale=None, panel: bool = True):
    return block_greedy_step(S, state, p, kappa, max_passes,
                             backend=backend, scale=scale, panel=panel)


def rb_greedy_block(
    S,
    tau: float,
    p: int = 4,
    max_k: int | None = None,
    kappa: float = 2.0,
    max_passes: int = 3,
    refresh: str = "auto",
    refresh_safety: float = 100.0,
    backend: str | None = None,
) -> GreedyResult:
    """Deprecated entry point: use ``repro.api.build_basis(source=S,
    strategy="block_greedy", tau=tau, block_p=p)``.

    Block pivoting is an execution optimization of the same greedy
    reduction — as a *public* entry point it is redundant with the front
    door.  This wrapper delegates to the same chunked driver the front
    door uses.
    """
    warnings.warn(
        "rb_greedy_block is deprecated: call repro.api.build_basis("
        "source=S, strategy='block_greedy', tau=tau, block_p=p) instead "
        "(identical result, unified ReducedBasis artifact)",
        DeprecationWarning,
        stacklevel=2,
    )
    return _rb_greedy_block_impl(
        S, tau, p=p, max_k=max_k, kappa=kappa, max_passes=max_passes,
        refresh=refresh, refresh_safety=refresh_safety, backend=backend,
    )


# ------------------------------------------------ chunked blocked driver ----


def _block_chunk_impl(
    S,
    state,
    tau,
    scale,
    ref_sq,
    refresh_safety,
    chunk: int,
    p: int,
    kappa: float = 2.0,
    max_passes: int = 3,
    backend: str | None = None,
    check_refresh: bool = True,
    panel: bool = True,
):
    """Run up to ``chunk`` blocked greedy iterations device-resident.

    Each ``lax.while_loop`` round is one block: top-p residual selection,
    joint IMGS of the p pivot columns against Q and against the earlier
    in-block picks (by default through the BLAS-3 panel path — see
    :func:`_ortho_block`; ``panel=False`` keeps the p-sequential
    fixed-slot form), the in-block rank guard (a candidate whose
    orthogonalization residual is rounding noise becomes a zero "hole"
    column), and ONE fused panel sweep over S
    (:func:`repro.core.backend.block_sweep`).  ``state.k`` counts occupied
    SLOTS (holes included); the driver compacts at the end.

    Stops on the stepwise drivers' host events, reported as stop codes so
    the host syncs one scalar per chunk:

      STOP_TAU      the max residual fell below tau BEFORE a block — the
                    block is not added (no trailing drop needed),
      STOP_RANK     every candidate in a block was rank-rejected,
      STOP_REFRESH  the post-block residual neared the Eq.-(6.3)
                    cancellation floor.
    """
    max_slots = state.Q.shape[1]
    eps = jnp.finfo(state.norms_sq.dtype).eps
    rdt = state.norms_sq.dtype

    def cond(carry):
        st, n, stop = carry
        return (stop == STOP_NONE) & (n < chunk) & (st.k + p <= max_slots)

    def add_block(st, top_vals, top_idx):
        slots = st.k
        Q, Qnew, oks_arr, rnorms, npasses = _ortho_block(
            S, st.Q, top_idx, slots, p, kappa, max_passes, eps, scale,
            backend, panel,
        )
        C, acc = _backend.block_sweep(Qnew, S, st.acc, backend=backend)
        st = st._replace(
            Q=Q,
            R=jax.lax.dynamic_update_slice_in_dim(st.R, C, slots, axis=0),
            acc=acc,
            pivots=jax.lax.dynamic_update_slice_in_dim(
                st.pivots,
                jnp.where(oks_arr, top_idx, -1).astype(jnp.int32),
                slots, axis=0,
            ),
            errs=jax.lax.dynamic_update_slice_in_dim(
                st.errs,
                jnp.sqrt(jnp.maximum(top_vals, 0.0)).astype(rdt),
                slots, axis=0,
            ),
            rnorms=jax.lax.dynamic_update_slice_in_dim(
                st.rnorms, rnorms.astype(rdt), slots, axis=0,
            ),
            n_passes=jax.lax.dynamic_update_slice_in_dim(
                st.n_passes, npasses.astype(jnp.int32), slots, axis=0,
            ),
            k=slots + p,
        )
        n_ok = jnp.sum(oks_arr.astype(jnp.int32))
        res_after = jnp.maximum(jnp.max(st.norms_sq - st.acc), 0.0)
        # Post-block tau stop BEFORE the refresh trigger (the rb_greedy
        # family's precedence: a tracked residual below tau means
        # converged, even when it sits at the Eq.-(6.3) floor — matching
        # the stepwise oracle's `err_now < tau` break.  Without it a
        # floored-but-unconverged f32 build refreshes forever, each
        # refresh reviving a residual the orthogonalization noise floor
        # cannot actually reduce).
        tau_hit = res_after < tau * tau
        refresh_hit = check_refresh & (res_after
                                       < refresh_safety * eps * ref_sq)
        stop = jnp.where(
            n_ok == 0, STOP_RANK,
            jnp.where(tau_hit, STOP_TAU,
                      jnp.where(refresh_hit, STOP_REFRESH, STOP_NONE)),
        ).astype(jnp.int32)
        return st, stop

    def body(carry):
        st, n, _ = carry
        res_sq = jnp.maximum(st.norms_sq - st.acc, 0.0)
        top_vals, top_idx = jax.lax.top_k(res_sq, p)
        err = jnp.sqrt(top_vals[0])
        st, stop = jax.lax.cond(
            err >= tau,
            lambda s: add_block(s, top_vals, top_idx),
            lambda s: (s, jnp.asarray(STOP_TAU, jnp.int32)),
            st,
        )
        return (st, n + 1, stop)

    state, n_done, stop = jax.lax.while_loop(
        cond, body,
        (state, jnp.asarray(0, jnp.int32), jnp.asarray(STOP_NONE, jnp.int32)),
    )
    return state, n_done, stop


_BLOCK_CHUNK_STATICS = (
    "chunk", "p", "kappa", "max_passes", "backend", "check_refresh",
    "panel",
)

# Non-donating variant: supports repeated application to one state
# (benchmarks time the hot loop this way).
_block_chunk = jax.jit(_block_chunk_impl, static_argnames=_BLOCK_CHUNK_STATICS)

# The driver's variant donates the state pytree so Q/R/acc buffers are
# reused across chunks instead of copied (see repro.core.greedy).
_block_chunk_donated = jax.jit(
    _block_chunk_impl, static_argnames=_BLOCK_CHUNK_STATICS,
    donate_argnums=(1,),
)


def _compact_result(state, max_k: int, stop: int = STOP_NONE) -> GreedyResult:
    """Drop hole columns (rejected in-block candidates) from the slot
    buffers: keep unit columns of Q and their matching R rows / pivots /
    errs / diagnostics, capped at ``max_k`` accepted bases (the slot
    buffers carry +p overrun headroom, and the final block may push the
    accepted count past the cap — the basis is nested, so truncation is
    exact).

    Works on any state with Q/R/pivots/errs fields (GreedyState and the
    distributed DistGreedyState both qualify); per-basis diagnostics are
    compacted when present.
    """
    Qh = jnp.asarray(state.Q)
    norms = jnp.linalg.norm(Qh, axis=0)
    keep = jnp.where(norms > 0.5)[0][:max_k]  # unit columns, capped
    k = keep.shape[0]
    Qc = jnp.zeros_like(state.Q).at[:, :k].set(Qh[:, keep])
    R = jnp.asarray(state.R)
    Rc = jnp.zeros_like(R).at[:k, :].set(R[keep, :])
    piv = jnp.zeros_like(state.pivots).at[:k].set(state.pivots[keep])
    errs = jnp.zeros_like(state.errs).at[:k].set(state.errs[keep])
    rnorms_src = getattr(state, "rnorms", None)
    if rnorms_src is not None:
        rnorms = jnp.zeros_like(rnorms_src).at[:k].set(rnorms_src[keep])
        n_passes = jnp.zeros_like(state.n_passes).at[:k].set(
            state.n_passes[keep])
    else:
        rnorms = jnp.zeros_like(errs)
        n_passes = jnp.zeros_like(piv)
    return GreedyResult(
        Q=Qc, R=Rc, pivots=piv, errs=errs,
        k=jnp.asarray(k, jnp.int32),
        n_ortho_passes=n_passes,
        rnorms=rnorms,
        stop=stop,
    )


@traced("repro.driver")
def _rb_greedy_block_impl(
    S,
    tau: float,
    p: int = 4,
    max_k: int | None = None,
    kappa: float = 2.0,
    max_passes: int = 3,
    refresh: str = "auto",
    refresh_safety: float = 100.0,
    backend: str | None = None,
    chunk: int = 4,
    callback=None,
    panel: bool = True,
    adaptive: bool = False,
    diagnostics: dict | None = None,
    checkpoint_dir: str | None = None,
    resume: bool = False,
) -> GreedyResult:
    """Chunked device-resident blocked driver (the front door's
    ``strategy="block_greedy"``).

    ``chunk`` BLOCKS (i.e. up to ``chunk * p`` bases) run inside one jitted
    ``lax.while_loop``; the host syncs only the (n_done, stop) scalars at
    chunk boundaries.  Selects the same pivots as
    :func:`rb_greedy_block_stepwise` (asserted in
    tests/test_block_greedy.py) at ~chunk x fewer dispatches.

    ``panel`` (default True) routes each block's orthogonalization through
    the BLAS-3 panel path (:func:`_ortho_block`); ``panel=False`` keeps
    the pre-panel p-sequential form (same span, different float summation
    order).

    ``adaptive`` treats ``p`` as a CEILING and retunes the live panel
    width between chunks from the in-block rank guard's rejection rate —
    the stale-pivot signal: rejections mean picks 2..p were made against
    residuals that ignored picks 1..i-1 and collapsed once they arrived,
    so the width halves; a clean chunk grows it back (doubling, capped at
    ``p``).  The width trajectory is recorded in ``diagnostics`` (key
    ``"p_trajectory"``: one ``{slots, p, rejected}`` entry per chunk)
    when a dict is passed — the front door forwards it into the artifact
    provenance.

    ``callback(state)`` fires once per chunk (the slot arrays carry the
    per-slot history up to ``state.k``, holes included); with a callback
    set the chunk does not donate the state buffers, mirroring
    :func:`repro.core.greedy.rb_greedy`.

    ``checkpoint_dir``/``resume`` mirror :func:`repro.core.greedy.rb_greedy`
    (state + done/stop persisted after each chunk's stop handling; the
    adaptive live width rides along, the diagnostics trajectory does not —
    it is provenance, not replay state).

    Note: rejected in-block candidates leave zero "hole" columns inside the
    Q slot buffer during the build; the driver compacts them away at the
    end and caps the result at ``max_k``, so the returned ``k`` counts
    accepted bases only and never exceeds ``max_k``.
    """
    from repro.data.providers import materialize_source

    S = materialize_source(S)
    N, M = S.shape
    if p < 1:
        raise ValueError(f"block_p must be >= 1, got {p}")
    p = min(p, min(N, M))
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    if max_k is None:
        max_k = min(N, M)
    max_k = min(max_k, N, M)  # the accepted-basis cap
    max_slots = min(max_k + p, min(N, M) + p)  # + hole headroom (max p)
    # resolve pre-jit so the cache keys on the concrete backend name
    backend = _backend.resolve_backend(backend)
    state = greedy_init(S, max_slots)
    rdt = state.norms_sq.dtype
    eps = float(jnp.finfo(rdt).eps)
    ref_sq = float(jnp.max(state.norms_sq))
    scale = ref_sq ** 0.5  # fixed global column scale for the rank guard
    done = False
    final_stop = STOP_NONE
    p_live = p  # adaptive: current width, halved/regrown between chunks
    seq = 0
    if checkpoint_dir is not None:
        from repro.checkpoint.io import latest_step

        tree = load_resident_checkpoint(checkpoint_dir) if resume else None
        if tree is not None:
            _validate_resident_tree(tree, N, M, max_slots, state.Q.dtype,
                                    "resume checkpoint")
            st_host, ref_sq, scale, done, final_stop = \
                resident_state_from_tree(tree)
            state = GreedyState(*(jnp.asarray(x) for x in st_host))
            p_live = int(tree.get("p_live", p))
        seq = latest_step(checkpoint_dir) or 0
    tau_d = jnp.asarray(tau, rdt)
    scale_d = jnp.asarray(scale, rdt)
    safety_d = jnp.asarray(refresh_safety, rdt)
    ref_sq_d = jnp.asarray(ref_sq, rdt)
    # a callback may retain states (checkpointing); donation would
    # invalidate those retained buffers on accelerators
    chunk_fn = _block_chunk if callback is not None else \
        _block_chunk_donated
    trajectory = [] if diagnostics is not None else None
    while not done and int(state.k) + p_live <= max_slots:
        slots_before = int(state.k)
        with span("repro.driver.chunk", k=slots_before):
            state, n_done, stop = chunk_fn(
                S, state, tau_d, scale_d, ref_sq_d, safety_d,
                chunk=chunk, p=p_live, kappa=kappa, max_passes=max_passes,
                backend=backend, check_refresh=(refresh == "auto"),
                panel=panel,
            )
            if callback is not None:
                callback(state)
            stop = int(stop)
            if adaptive or trajectory is not None:
                slots_added = int(state.k) - slots_before
                rejected = (
                    int(np.count_nonzero(np.asarray(
                        state.pivots[slots_before:slots_before + slots_added]
                    ) < 0)) if slots_added else 0
                )
                if trajectory is not None:
                    trajectory.append({"slots": slots_before, "p": p_live,
                                       "rejected": rejected})
                if adaptive and slots_added:
                    rate = rejected / slots_added
                    if rate > 0.25 and p_live > 1:
                        # staleness bites: most in-block picks collapse once
                        # the earlier ones land — narrow the panel
                        p_live = max(1, p_live // 2)
                    elif rejected == 0 and p_live < p:
                        p_live = min(p, p_live * 2)
            if stop == STOP_TAU or stop == STOP_RANK:
                done, final_stop = True, stop
            elif stop == STOP_REFRESH:
                state = greedy_refresh(S, state)
                ref_sq = max(float(jnp.max(state.norms_sq)), 1e-300)
                ref_sq_d = jnp.asarray(ref_sq, rdt)
                if ref_sq ** 0.5 < tau:
                    done, final_stop = True, STOP_TAU
                elif ref_sq ** 0.5 <= floor_estimate(eps, scale, int(state.k)):
                    done, final_stop = True, STOP_FLOOR
            if not done and int(state.k) + p_live > max_slots:
                done = True  # out of slots; final_stop stays STOP_NONE
            if checkpoint_dir is not None:
                seq = save_resident_checkpoint(
                    checkpoint_dir, seq, state, ref_sq, scale, done,
                    final_stop, extra={"p_live": p_live})
    if diagnostics is not None:
        diagnostics["p_trajectory"] = trajectory
    return _compact_result(state, max_k, final_stop)


# --------------------------------------------------- stepwise block oracle --


def rb_greedy_block_stepwise(
    S,
    tau: float,
    p: int = 4,
    max_k: int | None = None,
    kappa: float = 2.0,
    max_passes: int = 3,
    refresh: str = "auto",
    refresh_safety: float = 100.0,
    backend: str | None = None,
    panel: bool = True,
) -> GreedyResult:
    """The eager per-block driver: one jitted block step + host syncs per
    block.  Kept verbatim as the parity oracle for the chunked driver
    (mirroring :func:`repro.core.greedy.rb_greedy_stepwise`).

    Note: rejected in-block candidates leave zero columns inside the Q
    buffer; ``k`` counts accepted bases but their slots are the first
    ``k + holes`` columns.  For simplicity the driver compacts Q at the end.
    """
    from repro.data.providers import materialize_source

    S = materialize_source(S)
    N, M = S.shape
    if max_k is None:
        max_k = min(N, M)
    max_k_req = min(max_k, N, M)  # the accepted-basis cap
    max_k = min(max_k + p, min(N, M) + p)  # slot buffer incl. hole headroom
    # resolve pre-jit so the cache keys on the concrete backend name
    backend = _backend.resolve_backend(backend)
    state = greedy_init(S, max_k)
    eps = float(jnp.finfo(state.norms_sq.dtype).eps)
    ref_sq = float(jnp.max(state.norms_sq))
    # fixed global column scale for the rank guard (the greedy-family
    # convention; see block_greedy_step's docstring)
    scale_d = jnp.asarray(ref_sq ** 0.5, state.norms_sq.dtype)
    scale = ref_sq ** 0.5
    final_stop = STOP_NONE
    slots = 0  # occupied slots including holes
    while slots + p <= max_k:
        prev_k = int(state.k)
        state = state._replace(k=jnp.asarray(slots, jnp.int32))
        state = _jitted_block_step(S, state, p=p, kappa=kappa,
                                   max_passes=max_passes, backend=backend,
                                   scale=scale_d, panel=panel)
        n_acc = int(state.k) - slots
        slots += p
        err = float(state.errs[slots - p])  # max residual before this block
        state = state._replace(k=jnp.asarray(prev_k + n_acc, jnp.int32))
        if err < tau:
            final_stop = STOP_TAU
            break
        res_now = jnp.max(jnp.maximum(state.norms_sq - state.acc, 0.0))
        err_now = float(jnp.sqrt(res_now))
        if refresh == "auto" and err_now ** 2 < refresh_safety * eps * ref_sq:
            state = greedy_refresh(S, state)
            ref_sq = max(float(jnp.max(state.norms_sq)), 1e-300)
            # the post-refresh EXACT residual decides convergence (same
            # check as rb_greedy_stepwise; the pre-PR-4 block driver
            # missed it and appended one below-tau block after a refresh)
            if ref_sq ** 0.5 < tau:
                final_stop = STOP_TAU
                break
            if ref_sq ** 0.5 <= floor_estimate(eps, scale,
                                               int(state.k)):
                final_stop = STOP_FLOOR
                break
        if err_now < tau or n_acc == 0:
            final_stop = STOP_TAU if err_now < tau else STOP_RANK
            break

    # compact: drop zero columns from Q / matching rows of R, cap at the
    # requested max_k (shared with the chunked driver)
    return _compact_result(state, max_k_req, final_stop)
