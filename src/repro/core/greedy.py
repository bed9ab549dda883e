"""Algorithm 3: RB-greedy with well-conditioned iterated Gram-Schmidt.

This is the paper's workhorse (the algorithm ``greedycpp`` implements).  The
per-iteration structure follows Sec. 6.1.2 exactly:

  pivot search:      sigma_k^2(s_i) = |s_i|^2 - sum_j |c_j|^2,  c_j = q_j^H s_i
                     (Eq. 6.3 — squared form, monotone accumulated sum, no
                     square roots, avoids catastrophic cancellation),
  orthogonalization: Hoffmann's iterated Gram-Schmidt with kappa = 2.

Orthogonalization note (hardware adaptation, see DESIGN.md §2): the paper's
serial code uses Hoffmann's iterated *modified* GS ("MGSCI", kappa=2) and
notes in §6.1.5 that its sequential column sweeps preclude BLAS-2/matvec
execution, suggesting the classical iterated variant ("CMGSI") for parallel
hardware.  We take that suggestion: orthogonalization is iterated *classical*
GS (two matvecs per pass, MXU-friendly), with the same kappa=2 re-run test
and the same conjectured orthogonality level |I - Q^H Q| ~ kappa eps sqrt(M).

Hot-loop primitives (the Eq.-6.3 sweep and the GS projection pass) are
routed through :mod:`repro.core.backend`, which dispatches to the fused
Pallas TPU kernels or the pure-``jnp`` XLA path (``backend=`` on every
entry point; default ``auto``).

Three drivers are provided:

- :func:`rb_greedy` — chunked device-resident driver: runs ``chunk``
  iterations inside ONE jitted ``lax.while_loop`` and only syncs with the
  host at chunk boundaries (stop codes for tau / rank-guard / refresh), so
  per-iteration dispatch + device->host transfer is amortized by ~chunk.
  ``callback(state)`` fires once per chunk; the state arrays carry the full
  per-step history (``chunk=1`` restores exact per-iteration callbacks).
- :func:`rb_greedy_stepwise` — the seed per-step driver (one jitted step +
  host sync per basis vector).  Kept as the parity oracle and benchmark
  baseline; semantics are identical pivot-for-pivot.
- :func:`rb_greedy_scan` — a single ``lax.scan`` over ``max_k`` iterations
  with masked dynamic stopping (embeddable inside a larger jit).
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as _backend
from repro.kernels.common import matmul as _mm
from repro.spans import span, traced


class GreedyResult(NamedTuple):
    """Result of Algorithm 3 / Algorithm 2 (they are equivalent, Prop 5.3).

    Attributes:
      Q:      (N, max_k) orthonormal basis; columns >= k are zero.
      R:      (max_k, M) rows of the triangular factor in ORIGINAL column
              order: R[j] = q_j^H S.  The pivoted-order diagonal is
              ``R[j, pivots[j]]`` (non-increasing, Prop 5.3).
      pivots: (max_k,) int32 selected column indices (the permutation Pi).
      errs:   (max_k,) greedy error *before* adding basis j, i.e.
              max_i |s_i - Q_j Q_j^H s_i|_2 with j bases (Cor. 5.6: equals
              R(j+1, j+1) in the paper's 1-based pivoted notation).
      k:      number of valid bases (first k with errs >= tau).
      n_ortho_passes: (max_k,) iterated-GS pass count per basis (paper: nu_j).
      rnorms: (max_k,) orthogonalization residual norms |v - Q Q^H v|_2 of
              each pivot column.  In exact arithmetic rnorms[j] == errs[j]
              (Cor. 5.6); their divergence signals numerical-rank exhaustion
              and drives the driver's rank guard.
      stop:   why the build terminated (one of the STOP_* codes; see
              ``STOP_NAMES``).  ``STOP_NONE`` means it ran to ``max_k``.
    """

    Q: jax.Array
    R: jax.Array
    pivots: jax.Array
    errs: jax.Array
    k: jax.Array
    n_ortho_passes: jax.Array
    rnorms: jax.Array
    stop: int = 0


def imgs_orthogonalize(
    v: jax.Array,
    Q: jax.Array,
    kappa: float = 2.0,
    max_passes: int = 3,
    backend: str | None = None,
):
    """Hoffmann iterated (classical) Gram-Schmidt with ratio test kappa.

    Orthogonalizes ``v`` against the columns of ``Q`` (zero columns are
    harmless no-ops, so a zero-padded basis needs no masking).  Re-runs the
    projection while the norm dropped by more than a factor ``kappa``
    (Hoffmann's criterion; "twice is almost always enough", nu_j <= 3).
    Each projection pass goes through :func:`repro.core.backend.project_pass`
    (fused Pallas kernel on TPU, ``jnp`` under XLA).

    Returns ``(q, coeffs, rnorm, n_passes)`` with
    ``v = Q @ coeffs + rnorm * q`` and ``|q|_2 = 1`` (when rnorm > 0).
    """
    norm0 = jnp.linalg.norm(v)

    def one_pass(v):
        v_out, c = _backend.project_pass(v, Q, backend=backend)
        return v_out, c

    # First pass is unconditional.
    v1, c1 = one_pass(v)

    def cond(state):
        v_cur, _, norm_prev, norm_cur, n = state
        return (norm_cur < norm_prev / kappa) & (n < max_passes)

    def body(state):
        v_cur, coeffs, _, norm_cur, n = state
        v_next, c = one_pass(v_cur)
        return (v_next, coeffs + c, norm_cur, jnp.linalg.norm(v_next), n + 1)

    v_fin, coeffs, _, rnorm, n_passes = jax.lax.while_loop(
        cond, body, (v1, c1, norm0, jnp.linalg.norm(v1), jnp.asarray(1))
    )
    safe = jnp.maximum(rnorm, jnp.finfo(rnorm.dtype).tiny)
    q = v_fin / safe.astype(v_fin.dtype)
    return q, coeffs, rnorm, n_passes


def panel_imgs_orthogonalize(
    V: jax.Array,
    Q: jax.Array,
    kappa: float = 2.0,
    max_passes: int = 3,
    thresh=0.0,
    backend: str | None = None,
):
    """BLAS-3 panel orthogonalization: p candidates against Q in one pass.

    The blocked drivers' panel ortho hot path (classical panel
    factorization, cf. Quintana-Orti's BLAS-3 QR / Demmel et al. CA-RRQR):

    1. iterated classical-GS projection of the WHOLE (N, p) panel against
       ``Q`` through :func:`repro.core.backend.panel_project` — one
       (k, N) x (N, p) GEMM pair per pass instead of p GEMV chains — with
       Hoffmann's kappa re-run test evaluated PER COLUMN on the panel's
       post-update norms (converged columns are masked out of later
       passes),
    2. a within-panel sequential orthogonalization among the p candidates
       themselves (candidate i against the finalized panel columns < i,
       each via :func:`imgs_orthogonalize`'s iterated passes — O(p^2 N)
       work, negligible next to step 1's O(k p N)),
    3. the rank guard: a candidate whose final residual norm is not
       strictly above ``thresh`` becomes a zero "hole" column, so later
       candidates never orthogonalize against junk directions (zero
       columns are exact no-ops in every projection),
    4. a re-orthogonalization cycle (a second vs-Q panel pass + one
       within-panel sweep) on the NORMALIZED panel — the BCGS2 "twice is
       enough" pass, gated by Hoffmann's criterion applied to the
       within-panel drop: it runs exactly when some accepted candidate
       lost more than a ``kappa`` factor in step 2.  Step 2's large
       within-panel subtractions reintroduce O(eps * |c|) components
       along Q that step 1 cannot see, and normalizing a
       marginally-accepted candidate amplifies them by ``|v| / rnorm``
       (measured: percent-level defect on near-degenerate blocks);
       re-projecting the unit columns removes them at O(k p N) extra —
       the sequential path gets this for free because its iterated loop
       projects against Q and the earlier picks jointly.  Well-separated
       blocks (no within-panel cancellation) skip the cycle.

    Returns ``(P, oks, rnorms, n_passes)``:
      P:        (N, p) panel, orthonormal against Q and within itself;
                rejected candidates are zero columns.
      oks:      (p,) bool rank-guard verdicts (``rnorm > thresh``).
      rnorms:   (p,) real residual norms after steps 1-3 (recorded even
                when rejected, matching the stepwise drivers'
                diagnostics; the step-4 renormalization is an O(eps)
                correction on accepted columns).
      n_passes: (p,) int32 — vs-Q panel passes (incl. the re-ortho cycle)
                plus within-panel re-runs beyond the first (the
                per-candidate nu_j analogue).

    Spans the same space as p sequential :func:`imgs_orthogonalize` calls
    with fixed-slot writes (the pre-panel blocked path): candidate i is
    projected off Q and off the earlier in-block picks either way; only
    the float summation order differs (parity asserted in
    tests/test_block_greedy.py).
    """
    p = V.shape[1]
    norms0 = jnp.linalg.norm(V, axis=0)                       # (p,) real

    # First panel pass is unconditional (as in imgs_orthogonalize).
    V1, _ = _backend.panel_project(V, Q, backend=backend)
    norms1 = jnp.linalg.norm(V1, axis=0)

    def rerun_mask(norm_prev, norm_cur, n_col):
        return (norm_cur < norm_prev / kappa) & (n_col < max_passes)

    def cond(state):
        _, norm_prev, norm_cur, n_col = state
        return jnp.any(rerun_mask(norm_prev, norm_cur, n_col))

    def body(state):
        V_cur, norm_prev, norm_cur, n_col = state
        rerun = rerun_mask(norm_prev, norm_cur, n_col)
        # Full panel re-projection; converged columns keep their value
        # (the masked where below), so the per-column semantics match the
        # scalar driver's — the extra FLOPs on converged columns are free
        # next to the panel GEMM itself.
        V_next, _ = _backend.panel_project(V_cur, Q, backend=backend)
        norm_next = jnp.linalg.norm(V_next, axis=0)
        return (
            jnp.where(rerun[None, :], V_next, V_cur),
            jnp.where(rerun, norm_cur, norm_prev),
            jnp.where(rerun, norm_next, norm_cur),
            n_col + rerun.astype(n_col.dtype),
        )

    V_fin, _, norms_q, n_col = jax.lax.while_loop(
        cond, body, (V1, norms0, norms1, jnp.ones((p,), jnp.int32))
    )

    # Within-panel sequential orthogonalization (p is small and static):
    # candidate i against the finalized panel columns < i.  Zero columns
    # (later slots, rejected candidates) are exact no-ops.
    P = jnp.zeros_like(V)
    oks, rnorms, extra = [], [], []
    for i in range(p):
        q, _, rnorm, n_pass = imgs_orthogonalize(
            V_fin[:, i], P, kappa, max_passes, backend=backend
        )
        ok = rnorm > thresh
        q = jnp.where(ok, q, jnp.zeros_like(q))
        P = P.at[:, i].set(q)
        oks.append(ok)
        rnorms.append(rnorm)
        extra.append(n_pass - 1)  # re-runs beyond the unconditional pass
    oks = jnp.asarray(oks)
    rnorms = jnp.stack(rnorms)

    # Re-orthogonalization cycle (step 4), gated per block: some accepted
    # candidate dropped by more than kappa through the within-panel sweep
    # — its normalization amplified rounding noise along Q/panel by the
    # same factor.  Rejected (zero) columns project to zero and stay zero.
    need_reortho = jnp.any(oks & (rnorms * kappa < norms_q))

    def reortho(P_in):
        P2, _ = _backend.panel_project(P_in, Q, backend=backend)
        P_out = jnp.zeros_like(P_in)
        for i in range(p):
            v, _ = _backend.project_pass(P2[:, i], P_out, backend=backend)
            nrm = jnp.linalg.norm(v)
            safe = jnp.maximum(nrm, jnp.finfo(nrm.dtype).tiny)
            q = jnp.where(oks[i], v / safe.astype(v.dtype),
                          jnp.zeros_like(v))
            P_out = P_out.at[:, i].set(q)
        return P_out

    P = jax.lax.cond(need_reortho, reortho, lambda P_in: P_in, P)

    return (
        P,
        oks,
        rnorms,
        n_col + need_reortho.astype(jnp.int32) + jnp.asarray(extra,
                                                             jnp.int32),
    )


class GreedyState(NamedTuple):
    """Carried state of the greedy iteration (checkpointable pytree).

    ``norms_sq``/``acc`` implement the paper's Eq. (6.3) residual tracking:
    residual_i^2 = norms_sq_i - acc_i.  After an exact *refresh* (see
    :func:`greedy_refresh`) ``norms_sq`` holds the exact residuals at the
    refresh point and ``acc`` restarts from zero — same algebra, new (much
    smaller) reference scale, which removes the sqrt(eps)*|s| cancellation
    floor inherent to Eq. (6.3).
    """

    Q: jax.Array        # (N, max_k) basis, zero-padded
    R: jax.Array        # (max_k, M)
    norms_sq: jax.Array  # (M,)   reference residual^2 at last refresh (real)
    acc: jax.Array       # (M,)   sum_j |c_j|^2 since refresh (real, monotone)
    pivots: jax.Array    # (max_k,) int32
    errs: jax.Array      # (max_k,) real
    n_passes: jax.Array  # (max_k,) int32
    rnorms: jax.Array    # (max_k,) real — true residual norm of each pivot
    k: jax.Array         # () int32


@functools.partial(jax.jit, static_argnames=("max_k",))
def greedy_init(S: jax.Array, max_k: int) -> GreedyState:
    """Initial greedy state.  Jitted: eager ``jnp.abs(S) ** 2`` would
    materialize a full S-sized temporary before the norm reduction — at the
    production shape that is an extra multi-hundred-MB allocation and two
    memory passes per driver call."""
    N, M = S.shape
    rdtype = jnp.zeros((), S.dtype).real.dtype
    return GreedyState(
        Q=jnp.zeros((N, max_k), S.dtype),
        R=jnp.zeros((max_k, M), S.dtype),
        norms_sq=jnp.sum(jnp.abs(S) ** 2, axis=0).astype(rdtype),
        acc=jnp.zeros((M,), rdtype),
        pivots=jnp.zeros((max_k,), jnp.int32),
        errs=jnp.zeros((max_k,), rdtype),
        n_passes=jnp.zeros((max_k,), jnp.int32),
        rnorms=jnp.zeros((max_k,), rdtype),
        k=jnp.asarray(0, jnp.int32),
    )


def greedy_step(
    S: jax.Array,
    state: GreedyState,
    kappa: float = 2.0,
    max_passes: int = 3,
    backend: str | None = None,
) -> GreedyState:
    """One iteration of Algorithm 3 (pivot search + orthogonalization).

    The residuals are the paper's Eq. (6.3): ``norms_sq - acc``; the argmax
    over columns is the pivot.  The selected column is orthogonalized with
    iterated GS and appended; the new row of R is ``q_k^H S`` which also
    updates the accumulated sums for every column at O(NM) — constant per
    iteration (paper Fig. 6.1a).  The sweep runs through
    :func:`repro.core.backend.pivot_update` (fused Pallas kernel on TPU).
    """
    k = state.k
    res_sq = jnp.maximum(state.norms_sq - state.acc, 0.0)
    j = jnp.argmax(res_sq)
    err = jnp.sqrt(res_sq[j])

    v = jax.lax.dynamic_slice_in_dim(S, j, 1, axis=1)[:, 0]
    q, _, rnorm, n_pass = imgs_orthogonalize(
        v, state.Q, kappa, max_passes, backend=backend
    )

    # Row k of R and the Eq.-(6.3) update in one fused S pass.  The fused
    # kernel's post-update max/argmax belong to the NEXT pivot; this step
    # re-derives them from norms_sq - acc above, so they are unused here
    # (free in the Pallas pass, dead-code-eliminated under XLA).
    c, acc, _, _ = _backend.pivot_update(
        q, S, state.acc, state.norms_sq, backend=backend
    )

    return GreedyState(
        Q=state.Q.at[:, k].set(q),
        R=state.R.at[k, :].set(c),
        norms_sq=state.norms_sq,
        acc=acc,
        pivots=state.pivots.at[k].set(j.astype(jnp.int32)),
        errs=state.errs.at[k].set(err),
        n_passes=state.n_passes.at[k].set(n_pass.astype(jnp.int32)),
        rnorms=state.rnorms.at[k].set(rnorm.astype(state.rnorms.dtype)),
        k=k + 1,
    )


@functools.partial(
    jax.jit, static_argnames=("kappa", "max_passes", "backend")
)
def _jitted_step(S, state, kappa: float = 2.0, max_passes: int = 3,
                 backend: str | None = None):
    return greedy_step(S, state, kappa, max_passes, backend=backend)


@jax.jit
def greedy_refresh(S: jax.Array, state: GreedyState) -> GreedyState:
    """Exact residual recomputation (beyond-paper deep-tolerance mode).

    Eq. (6.3) tracks residual^2 = |s|^2 - sum|c|^2, whose subtraction has an
    absolute error floor of eps * |s|^2 — i.e. the *reported* greedy error
    can never drop below ~sqrt(eps) * |s| even though the true residual does
    (the paper's code shares this property; its taus sit above the floor).
    This refresh recomputes E = S - Q (Q^H S) exactly (O(kNM), done O(log)
    times), storing the exact residual^2 as the new reference so subsequent
    Eq.-(6.3) updates are accurate relative to the *refreshed* scale.
    """
    C = _mm(state.Q.conj().T, S)        # (max_k, M); zero rows are no-ops
    E = S - _mm(state.Q, C)
    res = jnp.sum(jnp.abs(E) ** 2, axis=0).astype(state.norms_sq.dtype)
    return state._replace(norms_sq=res, acc=jnp.zeros_like(state.acc))


# Stop codes reported by a device-resident chunk (host reads ONE scalar per
# chunk instead of err/rnorm floats per iteration).  STOP_FLOOR is a
# host-side verdict only (the post-refresh floor gate), never an in-chunk
# code.
STOP_NONE, STOP_RANK, STOP_TAU, STOP_REFRESH, STOP_FLOOR = 0, 1, 2, 3, 4

STOP_NAMES = {
    STOP_NONE: "STOP_NONE",        # ran to max_k (or slot capacity)
    STOP_RANK: "STOP_RANK",        # numerical-rank exhaustion (rank guard)
    STOP_TAU: "STOP_TAU",          # converged below tau
    STOP_REFRESH: "STOP_REFRESH",  # internal chunk code, never final
    STOP_FLOOR: "STOP_FLOOR",      # estimated achievable floor reached
}

# Safety factor of the achievable-floor gate.  After an exact refresh the
# residuals are trustworthy; if the max residual sits within FLOOR_SAFETY
# of the estimated floor the build cannot meaningfully improve and further
# bases would be noise-amplified directions.
FLOOR_SAFETY = 10.0


def floor_estimate(eps: float, scale: float, k: int) -> float:
    """Estimated achievable residual floor of a k-basis build.

    Each of the k orthogonalization/projection stages contributes O(eps)
    rounding relative to the data scale ``scale`` (= max column norm, the
    rank guard's reference); the contributions accumulate stochastically,
    giving ~eps * |s| * sqrt(k).  ``FLOOR_SAFETY`` absorbs the constants.
    A post-refresh exact residual at or below this value is indistinguishable
    from orthogonalization noise — the principled stop point PR 5's
    tau-before-refresh precedence only papered over.
    """
    return FLOOR_SAFETY * eps * scale * max(k, 1) ** 0.5


def _drop_last(state: GreedyState, k: int) -> GreedyState:
    """Remove the most recently added basis (tau-stop / rank-guard drop)."""
    return state._replace(
        k=jnp.asarray(k, jnp.int32),
        Q=state.Q.at[:, k].set(0),
        R=state.R.at[k, :].set(0),
        pivots=state.pivots.at[k].set(-1),
    )


# ------------------------------------------- resident checkpoint/resume ----
# The chunked resident drivers (rb_greedy here; the blocked/distributed
# siblings reuse these helpers) persist their GreedyState at chunk
# boundaries through repro.checkpoint.io.  The tree carries the host-side
# loop variables too (ref_sq changes at refresh; scale is fixed at init but
# must survive a restart) plus a ``done``/``stop`` pair saved AFTER the
# host's stop handling: the jitted chunk always runs >= 1 iteration, so
# resuming a finished build into the loop would add extra bases — a done
# checkpoint short-circuits straight to the result instead.

_RESIDENT_STATE_VERSION = 1


def resident_state_tree(state, ref_sq: float, scale: float, done: bool,
                        stop: int, extra: dict | None = None) -> dict:
    """Flat numpy tree of a resident GreedyState + host loop variables.

    Only the first ``k`` rows of R are saved (checkpoint traffic scales
    with k*M, not max_k*M); :func:`resident_state_from_tree` zero-pads
    them back.
    """
    k = int(state.k)
    tree = {
        "version": np.asarray(_RESIDENT_STATE_VERSION, np.int64),
        "Q": np.asarray(jax.device_get(state.Q)),
        "R": np.asarray(jax.device_get(state.R))[:k],
        "norms_sq": np.asarray(jax.device_get(state.norms_sq)),
        "acc": np.asarray(jax.device_get(state.acc)),
        "pivots": np.asarray(jax.device_get(state.pivots)),
        "errs": np.asarray(jax.device_get(state.errs)),
        "n_passes": np.asarray(jax.device_get(state.n_passes)),
        "rnorms": np.asarray(jax.device_get(state.rnorms)),
        "k": np.asarray(k, np.int64),
        "ref_sq": np.asarray(ref_sq, np.float64),
        "scale": np.asarray(scale, np.float64),
        "done": np.asarray(int(done), np.int64),
        "stop": np.asarray(int(stop), np.int64),
    }
    for key, val in (extra or {}).items():
        tree[key] = np.asarray(val)
    return tree


def resident_state_from_tree(tree: dict):
    """Inverse of :func:`resident_state_tree`.

    Returns ``(state, ref_sq, scale, done, stop)`` with the state's array
    leaves as host numpy (callers device_put / shard as needed).
    """
    version = int(tree["version"])
    if version != _RESIDENT_STATE_VERSION:
        raise ValueError(
            f"resident checkpoint version {version} != supported "
            f"{_RESIDENT_STATE_VERSION}"
        )
    max_k = tree["Q"].shape[1]
    M = tree["norms_sq"].shape[0]
    R = np.zeros((max_k, M), tree["R"].dtype)
    R[:tree["R"].shape[0]] = tree["R"]
    state = GreedyState(
        Q=tree["Q"], R=R, norms_sq=tree["norms_sq"], acc=tree["acc"],
        pivots=tree["pivots"], errs=tree["errs"],
        n_passes=tree["n_passes"], rnorms=tree["rnorms"],
        k=np.asarray(int(tree["k"]), np.int32),
    )
    return (state, float(tree["ref_sq"]), float(tree["scale"]),
            bool(int(tree["done"])), int(tree["stop"]))


def save_resident_checkpoint(directory: str, seq: int, state, ref_sq, scale,
                             done: bool, stop: int,
                             extra: dict | None = None, keep: int = 2) -> int:
    """Persist one resident-driver step; returns the new sequence number."""
    from repro.checkpoint.io import prune_steps, save_checkpoint

    seq += 1
    save_checkpoint(
        resident_state_tree(state, ref_sq, scale, done, stop, extra),
        directory, seq,
    )
    prune_steps(directory, keep)
    return seq


def load_resident_checkpoint(directory: str):
    """Latest intact resident checkpoint tree, or None if none exists."""
    from repro.checkpoint.io import latest_step, load_checkpoint_raw

    if latest_step(directory) is None:
        return None
    return load_checkpoint_raw(directory)


def _validate_resident_tree(tree, N, M, max_k, dtype, what="checkpoint"):
    if tree["Q"].shape != (N, max_k) or tree["norms_sq"].shape != (M,):
        raise ValueError(
            f"{what} shape mismatch: Q {tree['Q'].shape} / M "
            f"{tree['norms_sq'].shape[0]} vs requested ({N}, {max_k}) / {M}"
        )
    if tree["Q"].dtype != np.dtype(dtype):
        raise ValueError(
            f"{what} dtype mismatch: saved {tree['Q'].dtype}, "
            f"requested {np.dtype(dtype)}"
        )


def _greedy_chunk_impl(
    S,
    state,
    tau,
    scale,
    ref_sq,
    refresh_safety,
    chunk: int,
    kappa: float = 2.0,
    max_passes: int = 3,
    backend: str | None = None,
    check_refresh: bool = True,
):
    """Run up to ``chunk`` greedy iterations device-resident.

    A ``lax.while_loop`` applies :func:`greedy_step` until a host-relevant
    event fires (rank-guard, tau, refresh trigger — checked in the seed
    driver's order) or ``chunk``/``max_k`` iterations elapse.  Returns
    ``(state, n_done, stop_code)``; the host only ever syncs these, so
    dispatch + transfer cost is paid once per chunk, not per basis vector.
    """
    max_k = state.Q.shape[1]
    eps = jnp.finfo(state.norms_sq.dtype).eps

    def cond(carry):
        st, n, stop = carry
        return (stop == STOP_NONE) & (n < chunk) & (st.k < max_k)

    def body(carry):
        st, n, _ = carry
        st = greedy_step(S, st, kappa, max_passes, backend=backend)
        k = st.k
        err = st.errs[k - 1]
        rnorm = st.rnorms[k - 1]
        refresh_hit = check_refresh & (err * err < refresh_safety * eps
                                       * ref_sq)
        stop = jnp.where(
            rnorm < 50.0 * eps * scale,
            STOP_RANK,
            jnp.where(err < tau, STOP_TAU,
                      jnp.where(refresh_hit, STOP_REFRESH, STOP_NONE)),
        ).astype(jnp.int32)
        return (st, n + 1, stop)

    state, n_done, stop = jax.lax.while_loop(
        cond, body,
        (state, jnp.asarray(0, jnp.int32), jnp.asarray(STOP_NONE, jnp.int32)),
    )
    return state, n_done, stop


_CHUNK_STATICS = ("chunk", "kappa", "max_passes", "backend", "check_refresh")

# Non-donating variant: supports repeated application to one state
# (benchmarks time the hot loop this way).
_greedy_chunk = jax.jit(_greedy_chunk_impl, static_argnames=_CHUNK_STATICS)

# The driver's variant donates the state pytree so Q/R/acc buffers are
# reused across chunks instead of copied (matters on accelerators; CPU
# ignores donation).  The previous state is never touched again by the
# driver, so donation is safe there.
_greedy_chunk_donated = jax.jit(
    _greedy_chunk_impl, static_argnames=_CHUNK_STATICS, donate_argnums=(1,)
)


@traced("repro.driver")
def rb_greedy(
    S,
    tau: float,
    max_k: int | None = None,
    kappa: float = 2.0,
    max_passes: int = 3,
    callback=None,
    refresh: str = "auto",
    refresh_safety: float = 100.0,
    chunk: int = 16,
    backend: str | None = None,
    checkpoint_dir: str | None = None,
    resume: bool = False,
) -> GreedyResult:
    """Algorithm 3 driver: iterate until ``err < tau`` or ``k == max_k``.

    Chunked device-resident hot loop: ``chunk`` iterations run inside one
    jitted ``lax.while_loop`` and the host syncs only the (n_done, stop)
    scalars at chunk boundaries — identical pivots/bases to
    :func:`rb_greedy_stepwise` (asserted in tests/test_chunked_driver.py),
    ~chunk x fewer dispatches and device->host transfers.

    ``callback(state)`` fires once per chunk (the state arrays hold the full
    per-step history up to ``state.k``); pass ``chunk=1`` to restore the
    seed driver's exact per-iteration callback cadence.  When a callback is
    set the chunk does NOT donate the state buffers, so retained states
    (checkpoint histories) stay valid on accelerators; without one the
    state is donated and Q/R/acc buffers are reused across chunks.

    Stop thresholds are compared ON DEVICE in the residual dtype: with x64
    disabled (f32/c64 inputs) an err within ~1 ulp of ``tau`` can round the
    stopping decision differently from the stepwise driver's float64 host
    comparison — one basis at the boundary, nothing else.

    refresh: "auto" triggers :func:`greedy_refresh` when the tracked residual
    nears the Eq.-(6.3) cancellation floor (err^2 < safety * eps * ref^2);
    "never" is the paper-faithful mode.  If the post-refresh exact residual
    is still above tau but at or below :func:`floor_estimate`, the build
    stops with ``STOP_FLOOR`` instead of accepting noise-amplified
    directions.

    ``checkpoint_dir``/``resume``: with a directory set the driver persists
    its full state (plus a done/stop marker) after every chunk's stop
    handling; ``resume=True`` picks up from the newest intact step and a
    finished checkpoint short-circuits straight to the result, so killing
    the process at any point and re-running yields a bit-identical build.

    ``S`` may be anything :func:`repro.data.providers.as_provider` accepts
    (arrays pass through; paths/providers are materialized — use
    :func:`repro.core.streaming.rb_greedy_streamed` for sources that do
    not fit on device).
    """
    from repro.data.providers import materialize_source

    S = materialize_source(S)
    N, M = S.shape
    if max_k is None:
        max_k = min(N, M)
    max_k = min(max_k, min(N, M))
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    # Resolve here, NOT at trace time: the jit cache is keyed on the static
    # backend argument, so a still-None backend would freeze whatever the
    # env/default resolved to at first trace.
    backend = _backend.resolve_backend(backend)
    state = greedy_init(S, max_k)
    rdt = state.norms_sq.dtype
    eps = float(jnp.finfo(rdt).eps)
    ref_sq = float(jnp.max(state.norms_sq))
    scale = ref_sq ** 0.5  # fixed global column scale for the rank guard
    done = False
    final_stop = STOP_NONE
    seq = 0
    if checkpoint_dir is not None:
        from repro.checkpoint.io import latest_step

        tree = load_resident_checkpoint(checkpoint_dir) if resume else None
        if tree is not None:
            _validate_resident_tree(tree, N, M, max_k, state.Q.dtype,
                                    "resume checkpoint")
            st_host, ref_sq, scale, done, final_stop = \
                resident_state_from_tree(tree)
            state = GreedyState(*(jnp.asarray(x) for x in st_host))
        # Fresh build into a dir with older steps: continue the sequence so
        # prune/latest never interleave with stale numbering.
        seq = latest_step(checkpoint_dir) or 0
    # A callback may retain states (checkpointing); donation would
    # invalidate those retained buffers on accelerators.
    chunk_fn = _greedy_chunk if callback is not None else \
        _greedy_chunk_donated
    # invariant thresholds device-placed once; only ref_sq changes (refresh)
    tau_d = jnp.asarray(tau, rdt)
    scale_d = jnp.asarray(scale, rdt)
    safety_d = jnp.asarray(refresh_safety, rdt)
    ref_sq_d = jnp.asarray(ref_sq, rdt)
    k = int(state.k)
    while not done and k < max_k:
        with span("repro.driver.chunk", k=k):
            state, n_done, stop = chunk_fn(
                S, state, tau_d, scale_d, ref_sq_d, safety_d,
                chunk=chunk, kappa=kappa, max_passes=max_passes,
                backend=backend, check_refresh=(refresh == "auto"),
            )
            k = int(state.k)
            if callback is not None:
                callback(state)
            stop = int(stop)
            if stop == STOP_RANK:
                # Numerical-rank exhaustion: the pivot's true orthogonalization
                # residual is rounding noise — adding it would inject a junk,
                # non-orthogonal direction (Cor. 5.6 says rnorm == err in exact
                # arithmetic; their divergence is the symptom).  Drop and stop.
                k -= 1
                state = _drop_last(state, k)
                done, final_stop = True, STOP_RANK
            elif stop == STOP_TAU:
                # Last added basis was selected at an error already below tau:
                # drop it to match Algorithm 3's while-condition semantics.
                k -= 1
                state = _drop_last(state, k)
                done, final_stop = True, STOP_TAU
            elif stop == STOP_REFRESH:
                # Approaching the Eq.-(6.3) cancellation floor while still
                # above tau: recompute exact residuals and rescale the
                # reference.
                state = greedy_refresh(S, state)
                ref_sq = max(float(jnp.max(state.norms_sq)), 1e-300)
                ref_sq_d = jnp.asarray(ref_sq, rdt)
                # The recorded err was floor noise; the *post-add* exact error
                # decides whether any further basis is needed (keep this one).
                if ref_sq ** 0.5 < tau:
                    done, final_stop = True, STOP_TAU
                elif ref_sq ** 0.5 <= floor_estimate(eps, scale, k):
                    # Exact residual parked at the achievable floor: tau is
                    # unreachable in this precision — stop gracefully rather
                    # than accept noise-amplified directions.
                    done, final_stop = True, STOP_FLOOR
            if not done and k >= max_k:
                done = True  # ran to capacity; final_stop stays STOP_NONE
            # (no n_done check: the chunk cond guarantees >= 1 iteration, and
            # reading it back would add a host sync per chunk)
            if checkpoint_dir is not None:
                # Save AFTER stop handling: the chunk always runs >= 1
                # iteration, so a pre-handling snapshot of a finished build
                # would grow extra bases on resume.
                seq = save_resident_checkpoint(
                    checkpoint_dir, seq, state, ref_sq, scale, done,
                    final_stop)
    return GreedyResult(
        Q=state.Q, R=state.R, pivots=state.pivots, errs=state.errs,
        k=state.k, n_ortho_passes=state.n_passes, rnorms=state.rnorms,
        stop=final_stop,
    )


def rb_greedy_stepwise(
    S,
    tau: float,
    max_k: int | None = None,
    kappa: float = 2.0,
    max_passes: int = 3,
    callback=None,
    refresh: str = "auto",
    refresh_safety: float = 100.0,
    backend: str | None = None,
) -> GreedyResult:
    """The seed per-step driver: one jitted step + host sync per iteration.

    Pays one dispatch plus ``float(errs[k-1])``/``float(rnorms[k-1])``
    device->host syncs per basis vector.  Kept verbatim as (a) the parity
    oracle for :func:`rb_greedy` and (b) the benchmark baseline the chunked
    driver is measured against; ``callback(state)`` fires every iteration.
    """
    from repro.data.providers import materialize_source

    S = materialize_source(S)
    N, M = S.shape
    if max_k is None:
        max_k = min(N, M)
    max_k = min(max_k, min(N, M))
    backend = _backend.resolve_backend(backend)  # see rb_greedy
    state = greedy_init(S, max_k)
    eps = float(jnp.finfo(state.norms_sq.dtype).eps)
    ref_sq = float(jnp.max(state.norms_sq))
    scale = ref_sq ** 0.5  # fixed global column scale for the rank guard
    final_stop = STOP_NONE
    k = 0
    while k < max_k:
        state = _jitted_step(S, state, kappa=kappa, max_passes=max_passes,
                             backend=backend)
        k = int(state.k)
        if callback is not None:
            callback(state)
        err = float(state.errs[k - 1])
        rnorm = float(state.rnorms[k - 1])
        if rnorm < 50.0 * eps * scale:
            k -= 1
            state = _drop_last(state, k)
            final_stop = STOP_RANK
            break
        if err < tau:
            k -= 1
            state = _drop_last(state, k)
            final_stop = STOP_TAU
            break
        if refresh == "auto" and err * err < refresh_safety * eps * ref_sq:
            state = greedy_refresh(S, state)
            ref_sq = max(float(jnp.max(state.norms_sq)), 1e-300)
            if float(jnp.sqrt(ref_sq)) < tau:
                final_stop = STOP_TAU
                break
            if ref_sq ** 0.5 <= floor_estimate(eps, scale, k):
                final_stop = STOP_FLOOR
                break
    return GreedyResult(
        Q=state.Q, R=state.R, pivots=state.pivots, errs=state.errs,
        k=state.k, n_ortho_passes=state.n_passes, rnorms=state.rnorms,
        stop=final_stop,
    )


def rb_greedy_scan(
    S: jax.Array,
    tau: float,
    max_k: int,
    kappa: float = 2.0,
    max_passes: int = 3,
    backend: str | None = None,
) -> GreedyResult:
    """Fixed-length ``lax.scan`` variant (embeddable inside jit).

    Runs exactly ``max_k`` iterations; iterations whose pre-add error is
    already below ``tau`` are masked out (the basis column stays zero), so
    the result matches :func:`rb_greedy` semantics with static shapes.
    """
    # resolve pre-jit so the cache keys on the concrete backend name
    return _rb_greedy_scan(S, tau, max_k, kappa, max_passes,
                           _backend.resolve_backend(backend))


@functools.partial(
    jax.jit, static_argnames=("max_k", "kappa", "max_passes", "backend")
)
def _rb_greedy_scan(
    S: jax.Array,
    tau: float,
    max_k: int,
    kappa: float = 2.0,
    max_passes: int = 3,
    backend: str | None = None,
) -> GreedyResult:

    state0 = greedy_init(S, max_k)
    eps = jnp.finfo(state0.norms_sq.dtype).eps
    scale = jnp.sqrt(jnp.max(state0.norms_sq))

    def body(state, _):
        res_sq = jnp.maximum(state.norms_sq - state.acc, 0.0)
        j = jnp.argmax(res_sq)
        err = jnp.sqrt(res_sq[j])

        v = jax.lax.dynamic_slice_in_dim(S, j, 1, axis=1)[:, 0]
        q, _, rnorm, n_pass = imgs_orthogonalize(
            v, state.Q, kappa, max_passes, backend=backend
        )
        # Mask out both converged iterations and numerical-rank-exhausted
        # pivots (junk directions whose residual is rounding noise).
        active = (err >= tau) & (rnorm >= 50.0 * eps * scale)
        q = jnp.where(active, q, jnp.zeros_like(q))
        c, acc_out, _, _ = _backend.pivot_update(
            q, S, state.acc, state.norms_sq, backend=backend
        )

        k = state.k
        new = GreedyState(
            Q=state.Q.at[:, k].set(q),
            R=state.R.at[k, :].set(c),
            norms_sq=state.norms_sq,
            acc=acc_out,
            pivots=state.pivots.at[k].set(
                jnp.where(active, j.astype(jnp.int32), -1)
            ),
            errs=state.errs.at[k].set(err),
            n_passes=state.n_passes.at[k].set(n_pass.astype(jnp.int32)),
            rnorms=state.rnorms.at[k].set(rnorm.astype(state.rnorms.dtype)),
            k=k + active.astype(jnp.int32),
        )
        return new, None

    state, _ = jax.lax.scan(body, state0, None, length=max_k)
    return GreedyResult(
        Q=state.Q, R=state.R, pivots=state.pivots, errs=state.errs,
        k=state.k, n_ortho_passes=state.n_passes, rnorms=state.rnorms,
    )
