"""Column-distributed RB-greedy (the paper's Sec. 6 system on a TPU mesh).

Data decomposition is exactly greedycpp's: the snapshot matrix S is sharded
by COLUMNS over every mesh axis (each device owns an (N, M/P) slice and its
residual bookkeeping), while the basis Q (N x max_k) is replicated.  One
iteration (cf. Sec. 6.1.3):

  paper (MPI)                          |  here (SPMD collectives)
  -------------------------------------------------------------------------
  bcast q_k to P_pivot workers         |  Q replicated (no transfer)
  local residual update + local argmax |  same, fused (Pallas greedy_update)
  MPI_Allreduce (max, loc)             |  all_gather of (P, 2) pairs + local
                                       |  argmax — O(P) bytes
  owner MPI_Sends pivot column;        |  one psum of the owner-masked
  master MPI_Bcasts new basis          |  column — a single N-vector
                                       |  allreduce replaces send+bcast
  master core orthogonalizes (serial   |  every device runs IMGS redundantly
  bottleneck, Eq. 6.6)                 |  on the replicated Q — the Amdahl
                                       |  term of Eq. 6.6 disappears

The per-iteration state is a pytree (column-sharded residual trackers,
replicated basis), so the Python driver checkpoints/restores it with the
standard checkpoint machinery, and restores onto a *different* mesh
(elastic re-shard) because restore_checkpoint re-places leaves by target
sharding.

Hot-loop primitives route through :mod:`repro.core.backend` (fused Pallas
kernels on TPU, ``jnp`` under XLA), and the driver runs CHUNKED: ``chunk``
iterations execute inside one jitted ``lax.while_loop`` (collectives and
all) with the host syncing only a (n_done, stop_code) scalar pair per
chunk — the per-iteration ``float(errs[k-1])`` sync of the seed driver is
gone.  ``chunk=1`` restores the seed cadence exactly.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import backend as _backend
from repro.kernels.common import matmul as _mm
from repro.core.greedy import (
    GreedyResult,
    STOP_FLOOR,
    STOP_NONE,
    STOP_RANK,
    STOP_REFRESH,
    STOP_TAU,
    _validate_resident_tree,
    floor_estimate,
    imgs_orthogonalize,
    load_resident_checkpoint,
    panel_imgs_orthogonalize,
)
from repro.spans import span, traced


class DistGreedyState(NamedTuple):
    """Column-sharded greedy state (sharding noted per leaf)."""

    Q: jax.Array        # (N, max_k) REPLICATED
    R: jax.Array        # (max_k, M) col-sharded
    norms_sq: jax.Array  # (M,) col-sharded — reference residual^2
    acc: jax.Array       # (M,) col-sharded
    pivots: jax.Array    # (max_k,) replicated
    errs: jax.Array      # (max_k,) replicated
    k: jax.Array         # () replicated


def state_specs(mesh: Mesh):
    cols = P(tuple(mesh.axis_names))
    rep = P()
    return DistGreedyState(
        Q=P(None, None),
        R=P(None, tuple(mesh.axis_names)),
        norms_sq=cols,
        acc=cols,
        pivots=rep,
        errs=rep,
        k=rep,
    )


def state_shardings(mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), state_specs(mesh),
        is_leaf=lambda x: isinstance(x, P),
    )


@jax.jit
def _column_norms_sq(S):
    # jitted: eager abs(S)**2 would materialize an S-sized temporary
    return jnp.sum(jnp.abs(S) ** 2, axis=0)


def dist_greedy_init(S: jax.Array, max_k: int, mesh: Mesh) -> DistGreedyState:
    N, M = S.shape
    rdtype = jnp.zeros((), S.dtype).real.dtype
    sh = state_shardings(mesh)
    return DistGreedyState(
        Q=jax.device_put(jnp.zeros((N, max_k), S.dtype), sh.Q),
        R=jax.device_put(jnp.zeros((max_k, M), S.dtype), sh.R),
        norms_sq=jax.device_put(
            _column_norms_sq(S).astype(rdtype), sh.norms_sq
        ),
        acc=jax.device_put(jnp.zeros((M,), rdtype), sh.acc),
        pivots=jax.device_put(jnp.zeros((max_k,), jnp.int32), sh.pivots),
        errs=jax.device_put(jnp.zeros((max_k,), rdtype), sh.errs),
        k=jax.device_put(jnp.zeros((), jnp.int32), sh.k),
    )


# --------------------------------------------- checkpoint/resume support ---
# Distributed sibling of repro.core.greedy's resident checkpoint helpers;
# DistGreedyState has no per-basis diagnostics (n_passes/rnorms), so it
# gets its own tree layout.  Leaves are gathered to host numpy on save and
# re-placed with the CURRENT mesh's shardings on restore, so a checkpoint
# written on one mesh resumes on a different device count (elastic).

_DIST_STATE_VERSION = 1


def _dist_state_tree(state: DistGreedyState, ref_sq: float, scale: float,
                     done: bool, stop: int) -> dict:
    k = int(state.k)
    return {
        "version": np.asarray(_DIST_STATE_VERSION, np.int64),
        "Q": np.asarray(jax.device_get(state.Q)),
        "R": np.asarray(jax.device_get(state.R))[:k],
        "norms_sq": np.asarray(jax.device_get(state.norms_sq)),
        "acc": np.asarray(jax.device_get(state.acc)),
        "pivots": np.asarray(jax.device_get(state.pivots)),
        "errs": np.asarray(jax.device_get(state.errs)),
        "k": np.asarray(k, np.int64),
        "ref_sq": np.asarray(ref_sq, np.float64),
        "scale": np.asarray(scale, np.float64),
        "done": np.asarray(int(done), np.int64),
        "stop": np.asarray(int(stop), np.int64),
    }


def _dist_state_from_tree(tree: dict, mesh: Mesh):
    version = int(tree["version"])
    if version != _DIST_STATE_VERSION:
        raise ValueError(
            f"distributed checkpoint version {version} != supported "
            f"{_DIST_STATE_VERSION}"
        )
    max_k = tree["Q"].shape[1]
    M = tree["norms_sq"].shape[0]
    R = np.zeros((max_k, M), tree["R"].dtype)
    R[:tree["R"].shape[0]] = tree["R"]
    sh = state_shardings(mesh)
    state = DistGreedyState(
        Q=jax.device_put(tree["Q"], sh.Q),
        R=jax.device_put(R, sh.R),
        norms_sq=jax.device_put(tree["norms_sq"], sh.norms_sq),
        acc=jax.device_put(tree["acc"], sh.acc),
        pivots=jax.device_put(tree["pivots"], sh.pivots),
        errs=jax.device_put(tree["errs"], sh.errs),
        k=jax.device_put(np.asarray(int(tree["k"]), np.int32), sh.k),
    )
    return (state, float(tree["ref_sq"]), float(tree["scale"]),
            bool(int(tree["done"])), int(tree["stop"]))


def _save_dist_checkpoint(directory: str, seq: int, state, ref_sq, scale,
                          done: bool, stop: int, keep: int = 2) -> int:
    from repro.checkpoint.io import prune_steps, save_checkpoint

    seq += 1
    save_checkpoint(_dist_state_tree(state, ref_sq, scale, done, stop),
                    directory, seq)
    prune_steps(directory, keep)
    return seq


def _axis_index(axes: Sequence[str]):
    """Flattened device rank over (possibly several) mesh axes."""
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _axis_count(axes: Sequence[str]):
    n = 1
    for a in axes:
        n *= jax.lax.axis_size(a)
    return n


def _make_local_step(axes, kappa: float, max_passes: int,
                     backend: str | None):
    """Per-device body of one distributed greedy iteration (SPMD).

    ``backend`` should already be resolved (the factories resolve it so
    their lru_cache keys on the concrete name, not on a None that would
    freeze whatever the env/default said at first build)."""

    def local_step(S_loc, state):
        # ---- local pivot search (the greedy_update fusion target) ----
        res_sq = jnp.maximum(state.norms_sq - state.acc, 0.0)  # (M_loc,)
        j_loc = jnp.argmax(res_sq)
        val_loc = res_sq[j_loc]
        m_loc = res_sq.shape[0]
        rank = _axis_index(axes)
        j_glob = rank * m_loc + j_loc

        # ---- global argmax: all_gather the (val, idx) pairs ----
        vals = jax.lax.all_gather(val_loc, axes, tiled=False)  # (P,)
        idxs = jax.lax.all_gather(j_glob, axes, tiled=False)
        vals = vals.reshape(-1)
        idxs = idxs.reshape(-1)
        win = jnp.argmax(vals)
        err = jnp.sqrt(vals[win])
        j_global = idxs[win]
        owner = win == rank

        # ---- pivot column broadcast: one psum of the masked column ----
        col = jax.lax.dynamic_slice_in_dim(S_loc, j_loc, 1, axis=1)[:, 0]
        contrib = jnp.where(owner, col, jnp.zeros_like(col))
        v = jax.lax.psum(contrib, axes)  # (N,) replicated

        # ---- replicated orthogonalization (no master core) ----
        q, _, rnorm, _ = imgs_orthogonalize(
            v, state.Q, kappa=kappa, max_passes=max_passes, backend=backend
        )

        # ---- fused Eq. (6.3) update over the local shard ----
        c, acc, _, _ = _backend.pivot_update(
            q, S_loc, state.acc, state.norms_sq, backend=backend
        )
        k = state.k
        return DistGreedyState(
            Q=state.Q.at[:, k].set(q),
            R=state.R.at[k, :].set(c),
            norms_sq=state.norms_sq,
            acc=acc,
            pivots=state.pivots.at[k].set(j_global.astype(jnp.int32)),
            errs=state.errs.at[k].set(err),
            k=k + 1,
        )

    return local_step


def make_dist_greedy_step(
    mesh: Mesh, kappa: float = 2.0, max_passes: int = 3,
    backend: str | None = None,
):
    """Build the jitted SPMD greedy step for a mesh (cached per signature)."""
    return _make_dist_greedy_step(
        mesh, kappa, max_passes, _backend.resolve_backend(backend)
    )


@functools.lru_cache(maxsize=None)
def _make_dist_greedy_step(mesh, kappa, max_passes, backend):
    axes = tuple(mesh.axis_names)
    specs = state_specs(mesh)
    s_spec = P(None, axes)

    sharded = jax.shard_map(
        _make_local_step(axes, kappa, max_passes, backend),
        mesh=mesh,
        in_specs=(s_spec, specs),
        out_specs=specs,
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(1,))


def make_dist_greedy_chunk(
    mesh: Mesh, chunk: int, kappa: float = 2.0, max_passes: int = 3,
    backend: str | None = None, check_refresh: bool = True,
    donate: bool = True,
):
    """Build the jitted device-resident chunk for a mesh.

    Runs up to ``chunk`` SPMD iterations (collectives included) inside one
    ``lax.while_loop``; stops early on the seed driver's host events —
    checked in ITS order (tau before rank guard) — and reports them as a
    replicated ``(state, n_done, stop_code)`` so the host syncs two scalars
    per chunk instead of one error float per basis vector.
    """
    return _make_dist_greedy_chunk(
        mesh, chunk, kappa, max_passes,
        _backend.resolve_backend(backend), check_refresh, donate,
    )


@functools.lru_cache(maxsize=None)
def _make_dist_greedy_chunk(mesh, chunk, kappa, max_passes, backend,
                            check_refresh, donate):
    axes = tuple(mesh.axis_names)
    specs = state_specs(mesh)
    s_spec = P(None, axes)
    local_step = _make_local_step(axes, kappa, max_passes, backend)

    def local_chunk(S_loc, state, tau, scale, ref_sq, refresh_safety):
        max_k = state.Q.shape[1]
        eps = jnp.finfo(state.norms_sq.dtype).eps

        def cond(carry):
            st, n, stop = carry
            return (stop == STOP_NONE) & (n < chunk) & (st.k < max_k)

        def body(carry):
            st, n, _ = carry
            st = local_step(S_loc, st)
            err = st.errs[st.k - 1]
            refresh_hit = check_refresh & (
                err * err < refresh_safety * eps * ref_sq
            )
            stop = jnp.where(
                err < tau,
                STOP_TAU,
                jnp.where(err < 50.0 * eps * scale, STOP_RANK,
                          jnp.where(refresh_hit, STOP_REFRESH, STOP_NONE)),
            ).astype(jnp.int32)
            return (st, n + 1, stop)

        state, n_done, stop = jax.lax.while_loop(
            cond, body,
            (state, jnp.asarray(0, jnp.int32),
             jnp.asarray(STOP_NONE, jnp.int32)),
        )
        return state, n_done, stop

    sharded = jax.shard_map(
        local_chunk,
        mesh=mesh,
        in_specs=(s_spec, specs, P(), P(), P(), P()),
        out_specs=(specs, P(), P()),
        check_vma=False,
    )
    # donate=False supports repeated application to one state (benchmarks)
    return jax.jit(sharded, donate_argnums=(1,) if donate else ())


# ------------------------------------------------- blocked (BLAS-3) sweep --


def _make_local_block_chunk(axes, chunk, p, kappa, max_passes, backend,
                            check_refresh, panel=True):
    """Per-device body of up to ``chunk`` BLOCKED greedy iterations (SPMD).

    One iteration selects the global top-p residual columns (local top-p +
    all-gather of the (value, column) pairs — the paper's
    ``MPI_Allreduce(MAXLOC)`` generalized to p winners), fetches the p
    pivot columns with one owner-masked psum, orthogonalizes them jointly
    (by default through the BLAS-3 panel path
    :func:`repro.core.greedy.panel_imgs_orthogonalize`, replicated on
    every device exactly like the stepwise driver's redundant IMGS;
    in-block rank guard — rejected candidates leave zero "hole" columns),
    and updates the LOCAL shard's residuals with ONE fused panel sweep
    (:func:`repro.core.backend.block_sweep`) — one read of the shard per p
    bases.

    The tau gate is mask-based rather than branch-based so no collective
    sits inside a ``lax.cond``: a converged iteration computes a zero
    panel (exact no-ops everywhere) and reports STOP_TAU without
    advancing ``k``.
    """

    def local_chunk(S_loc, state, tau, scale, ref_sq, refresh_safety):
        max_slots = state.Q.shape[1]
        eps = jnp.finfo(state.norms_sq.dtype).eps
        rdt = state.norms_sq.dtype

        def body(carry):
            st, n, _ = carry
            # ---- global top-p selection ----
            res_sq = jnp.maximum(st.norms_sq - st.acc, 0.0)
            l_vals, l_idx = jax.lax.top_k(res_sq, p)     # local top-p
            m_loc = res_sq.shape[0]
            rank = _axis_index(axes)
            g_idx = rank * m_loc + l_idx
            vals = jax.lax.all_gather(l_vals, axes).reshape(-1)  # (P*p,)
            idxs = jax.lax.all_gather(g_idx, axes).reshape(-1)
            top_vals, top_pos = jax.lax.top_k(vals, p)           # global
            top_idx = idxs[top_pos]
            err = jnp.sqrt(top_vals[0])
            go = err >= tau

            # ---- fetch the p pivot columns: one (N, p) masked psum ----
            owned = (top_idx // m_loc == rank) & go
            local_cols = jnp.where(
                owned[None, :],
                jnp.take(S_loc, top_idx % m_loc, axis=1),
                jnp.zeros((S_loc.shape[0], p), S_loc.dtype),
            )
            V = jax.lax.psum(local_cols, axes)           # (N, p) replicated

            # ---- joint IMGS with the in-block rank guard ----
            slots = st.k
            Q = st.Q
            if panel:
                Qnew, oks_p, _, _ = panel_imgs_orthogonalize(
                    V, Q, kappa=kappa, max_passes=max_passes,
                    thresh=50.0 * eps * scale, backend=backend,
                )
                # converged iterations (~go) compute a zero panel: V is
                # all-zero (the owner mask includes go), so every rnorm
                # is 0 and the guard already rejected — the explicit
                # mask keeps the no-op invariant obvious
                oks_arr = oks_p & go
                Qnew = jnp.where(go, Qnew, jnp.zeros_like(Qnew))
                Q = jax.lax.dynamic_update_slice(
                    Q, Qnew, (jnp.zeros((), slots.dtype), slots)
                )
            else:
                qs, oks = [], []
                for i in range(p):
                    q, _, rnorm, _ = imgs_orthogonalize(
                        V[:, i], Q, kappa=kappa, max_passes=max_passes,
                        backend=backend,
                    )
                    ok = go & (rnorm > 50.0 * eps * scale)
                    q = jnp.where(ok, q, jnp.zeros_like(q))
                    Q = Q.at[:, slots + i].set(q)
                    qs.append(q)
                    oks.append(ok)
                Qnew = jnp.stack(qs, axis=1)  # (N, p), rejected cols zero
                oks_arr = jnp.asarray(oks)
            # ---- ONE fused pass over the local shard ----
            C, acc = _backend.block_sweep(Qnew, S_loc, st.acc,
                                          backend=backend)
            st = st._replace(
                Q=Q,
                R=jax.lax.dynamic_update_slice_in_dim(st.R, C, slots,
                                                      axis=0),
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
                k=jnp.where(go, slots + p, slots),
            )
            n_ok = jnp.sum(oks_arr.astype(jnp.int32))
            res_loc = jnp.maximum(jnp.max(st.norms_sq - st.acc), 0.0)
            res_after = jax.lax.pmax(res_loc, axes)
            # post-block tau stop BEFORE the refresh trigger — the
            # rb_greedy family precedence (see the resident blocked
            # chunk): a floored-but-unconverged build must not refresh
            # forever
            tau_hit = res_after < tau * tau
            refresh_hit = check_refresh & (
                res_after < refresh_safety * eps * ref_sq
            )
            stop = jnp.where(
                ~go, STOP_TAU,
                jnp.where(n_ok == 0, STOP_RANK,
                          jnp.where(tau_hit, STOP_TAU,
                                    jnp.where(refresh_hit, STOP_REFRESH,
                                              STOP_NONE))),
            ).astype(jnp.int32)
            return (st, n + 1, stop)

        def cond(carry):
            st, n, stop = carry
            return (stop == STOP_NONE) & (n < chunk) & (st.k + p <= max_slots)

        state, n_done, stop = jax.lax.while_loop(
            cond, body,
            (state, jnp.asarray(0, jnp.int32),
             jnp.asarray(STOP_NONE, jnp.int32)),
        )
        return state, n_done, stop

    return local_chunk


def make_dist_block_greedy_chunk(
    mesh: Mesh, chunk: int, p: int, kappa: float = 2.0, max_passes: int = 3,
    backend: str | None = None, check_refresh: bool = True,
    donate: bool = True, panel: bool = True,
):
    """Build the jitted device-resident BLOCKED chunk for a mesh: up to
    ``chunk`` blocked SPMD iterations (collectives included) per host
    round-trip, p bases per shard read."""
    return _make_dist_block_greedy_chunk(
        mesh, chunk, p, kappa, max_passes,
        _backend.resolve_backend(backend), check_refresh, donate, panel,
    )


@functools.lru_cache(maxsize=None)
def _make_dist_block_greedy_chunk(mesh, chunk, p, kappa, max_passes,
                                  backend, check_refresh, donate, panel):
    axes = tuple(mesh.axis_names)
    specs = state_specs(mesh)
    s_spec = P(None, axes)

    sharded = jax.shard_map(
        _make_local_block_chunk(axes, chunk, p, kappa, max_passes, backend,
                                check_refresh, panel),
        mesh=mesh,
        in_specs=(s_spec, specs, P(), P(), P(), P()),
        out_specs=(specs, P(), P()),
        check_vma=False,
    )
    # donate=False supports repeated application to one state (benchmarks)
    return jax.jit(sharded, donate_argnums=(1,) if donate else ())


@functools.lru_cache(maxsize=None)
def make_dist_refresh(mesh: Mesh):
    """Exact residual recomputation (deep-tolerance mode), column-local."""
    axes = tuple(mesh.axis_names)
    specs = state_specs(mesh)
    s_spec = P(None, axes)

    def local_refresh(S_loc, state):
        C = _mm(state.Q.conj().T, S_loc)
        E = S_loc - _mm(state.Q, C)
        res = jnp.sum(jnp.abs(E) ** 2, axis=0).astype(state.norms_sq.dtype)
        return state._replace(norms_sq=res, acc=jnp.zeros_like(state.acc))

    sharded = jax.shard_map(
        local_refresh, mesh=mesh, in_specs=(s_spec, specs),
        out_specs=specs, check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(1,))


def _place_columns(S, mesh: Mesh):
    """``S`` (anything :func:`repro.data.providers.as_provider` accepts)
    as an array column-sharded over every mesh axis."""
    from repro.data.providers import materialize_source

    S = materialize_source(S)
    s_sharding = NamedSharding(mesh, P(None, tuple(mesh.axis_names)))
    if getattr(S, "sharding", None) != s_sharding:
        S = jax.device_put(S, s_sharding)
    return S


@traced("repro.driver")
def distributed_greedy(
    S,
    tau: float,
    max_k: int,
    mesh: Mesh,
    callback=None,
    refresh: str = "auto",
    refresh_safety: float = 100.0,
    kappa: float = 2.0,
    max_passes: int = 3,
    chunk: int = 16,
    backend: str | None = None,
    block_p: int = 1,
    panel_ortho: bool = True,
    checkpoint_dir: str | None = None,
    resume: bool = False,
) -> GreedyResult:
    """Driver mirroring :func:`repro.core.greedy.rb_greedy` on a mesh.

    ``S`` should be placed with columns sharded over all mesh axes (the
    driver places it if not).  Column count must divide the device count.

    Chunked device-resident hot loop: ``chunk`` SPMD iterations run inside
    one jitted ``lax.while_loop`` per host round-trip.  ``callback(state)``
    fires once per chunk (state arrays carry the per-step history); pass
    ``chunk=1`` for the seed per-iteration cadence.  With a callback set
    the chunk does not donate state buffers (retained checkpoint states
    stay valid); see :func:`repro.core.greedy.rb_greedy` for that and for
    the on-device stop-threshold dtype caveat.

    ``block_p > 1`` runs the BLOCKED sweep (the distributed sibling of
    :mod:`repro.core.block_greedy`): global top-p pivot selection per
    iteration (the paper's ``MPI_Allreduce(MAXLOC)`` generalized to p
    winners) and one fused panel GEMM per shard read — each device reads
    its S shard once per p bases instead of once per basis.  The usual
    blocked trade-off applies (pivot staleness: a few extra bases on
    fast-decaying families; rank-rejected in-block candidates are
    compacted away, so ``k`` counts accepted bases).  ``panel_ortho``
    (default True) runs each block's replicated orthogonalization through
    the BLAS-3 panel path (see :mod:`repro.core.block_greedy`).

    ``checkpoint_dir``/``resume`` mirror
    :func:`repro.core.greedy.rb_greedy` (state + done/stop persisted after
    each chunk's stop handling; leaves are saved as host numpy and
    re-placed with THIS mesh's shardings on resume, so a run restores onto
    a different device count).

    ``S`` may be anything :func:`repro.data.providers.as_provider`
    accepts; non-array sources are materialized before placement.
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    if block_p < 1:
        raise ValueError(f"block_p must be >= 1, got {block_p}")
    if block_p > 1:
        return _distributed_block_greedy(
            S, tau, max_k, mesh, block_p, callback=callback,
            refresh=refresh, refresh_safety=refresh_safety, kappa=kappa,
            max_passes=max_passes, chunk=chunk, backend=backend,
            panel=panel_ortho, checkpoint_dir=checkpoint_dir, resume=resume,
        )

    with span("repro.driver.init"):
        S = _place_columns(S, mesh)
        chunk_fn = make_dist_greedy_chunk(
            mesh, chunk, kappa, max_passes, backend,
            check_refresh=(refresh == "auto"),
            donate=(callback is None),
        )
        refresh_fn = make_dist_refresh(mesh)
        state = dist_greedy_init(S, max_k, mesh)

        rdt = state.norms_sq.dtype
        eps = float(jnp.finfo(rdt).eps)
        ref_sq = float(jnp.max(state.norms_sq))
        scale = ref_sq ** 0.5
        done = False
        final_stop = STOP_NONE
        seq = 0
        if checkpoint_dir is not None:
            from repro.checkpoint.io import latest_step

            tree = load_resident_checkpoint(checkpoint_dir) if resume \
                else None
            if tree is not None:
                _validate_resident_tree(tree, S.shape[0], S.shape[1], max_k,
                                        S.dtype, "resume checkpoint")
                state, ref_sq, scale, done, final_stop = \
                    _dist_state_from_tree(tree, mesh)
            seq = latest_step(checkpoint_dir) or 0
        # invariant thresholds device-placed once; only ref_sq changes
        # (refresh)
        tau_d = jnp.asarray(tau, rdt)
        scale_d = jnp.asarray(scale, rdt)
        safety_d = jnp.asarray(refresh_safety, rdt)
        ref_sq_d = jnp.asarray(ref_sq, rdt)
        k = int(state.k)
    while not done and k < max_k:
        with span("repro.driver.chunk", k=k):
            state, n_done, stop = chunk_fn(
                S, state, tau_d, scale_d, ref_sq_d, safety_d,
            )
            k = int(state.k)
            if callback is not None:
                callback(state)
            stop = int(stop)
            if stop == STOP_TAU:
                k -= 1
                state = state._replace(
                    k=jnp.asarray(k, jnp.int32),
                    Q=state.Q.at[:, k].set(0),
                    pivots=state.pivots.at[k].set(-1),
                )
                done, final_stop = True, STOP_TAU
            elif stop == STOP_RANK:
                k -= 1
                state = state._replace(k=jnp.asarray(k, jnp.int32))
                done, final_stop = True, STOP_RANK
            elif stop == STOP_REFRESH:
                with span("repro.driver.refresh"):
                    state = refresh_fn(S, state)
                    ref_sq = max(float(jnp.max(state.norms_sq)), 1e-300)
                    ref_sq_d = jnp.asarray(ref_sq, rdt)
                if ref_sq ** 0.5 < tau:
                    done, final_stop = True, STOP_TAU
                elif ref_sq ** 0.5 <= floor_estimate(eps, scale, k):
                    done, final_stop = True, STOP_FLOOR
            if not done and k >= max_k:
                done = True  # ran to capacity; final_stop stays STOP_NONE
            # (no n_done check: the chunk cond guarantees >= 1 iteration, and
            # reading it back would add a host sync per chunk)
            if checkpoint_dir is not None:
                seq = _save_dist_checkpoint(
                    checkpoint_dir, seq, state, ref_sq, scale, done,
                    final_stop)
    return GreedyResult(
        Q=state.Q, R=state.R, pivots=state.pivots, errs=state.errs,
        k=state.k, n_ortho_passes=jnp.zeros_like(state.pivots),
        rnorms=jnp.zeros_like(state.errs),
        stop=final_stop,
    )


def _distributed_block_greedy(
    S,
    tau: float,
    max_k: int,
    mesh: Mesh,
    p: int,
    callback=None,
    refresh: str = "auto",
    refresh_safety: float = 100.0,
    kappa: float = 2.0,
    max_passes: int = 3,
    chunk: int = 4,
    backend: str | None = None,
    panel: bool = True,
    checkpoint_dir: str | None = None,
    resume: bool = False,
) -> GreedyResult:
    """Blocked distributed driver body (see :func:`distributed_greedy`,
    ``block_p > 1``).  ``chunk`` counts BLOCKS per host round-trip;
    ``callback(state)`` fires once per chunk (non-donating, as in the
    stepwise driver)."""
    with span("repro.driver.init"):
        S = _place_columns(S, mesh)
        N, M = S.shape
        n_dev = int(mesh.devices.size)
        m_loc = M // n_dev
        p = min(p, min(N, M))
        if p > m_loc:
            raise ValueError(
                f"block_p={p} exceeds the per-device column count {m_loc} "
                f"(M={M} over {n_dev} devices) — the local top-p selection "
                f"needs p candidates per shard"
            )
        max_k = min(max_k, N, M)  # the accepted-basis cap
        max_slots = min(max_k + p, min(N, M) + p)  # + hole headroom
        chunk_fn = make_dist_block_greedy_chunk(
            mesh, chunk, p, kappa, max_passes, backend,
            check_refresh=(refresh == "auto"), donate=(callback is None),
            panel=panel,
        )
        refresh_fn = make_dist_refresh(mesh)
        state = dist_greedy_init(S, max_slots, mesh)

        rdt = state.norms_sq.dtype
        eps = float(jnp.finfo(rdt).eps)
        ref_sq = float(jnp.max(state.norms_sq))
        scale = ref_sq ** 0.5  # fixed global column scale for the rank guard
        done = False
        final_stop = STOP_NONE
        seq = 0
        if checkpoint_dir is not None:
            from repro.checkpoint.io import latest_step

            tree = load_resident_checkpoint(checkpoint_dir) if resume \
                else None
            if tree is not None:
                _validate_resident_tree(tree, N, M, max_slots, S.dtype,
                                        "resume checkpoint")
                state, ref_sq, scale, done, final_stop = \
                    _dist_state_from_tree(tree, mesh)
            seq = latest_step(checkpoint_dir) or 0
        tau_d = jnp.asarray(tau, rdt)
        scale_d = jnp.asarray(scale, rdt)
        safety_d = jnp.asarray(refresh_safety, rdt)
        ref_sq_d = jnp.asarray(ref_sq, rdt)
    while not done and int(state.k) + p <= max_slots:
        with span("repro.driver.chunk", k=int(state.k)):
            state, n_done, stop = chunk_fn(
                S, state, tau_d, scale_d, ref_sq_d, safety_d,
            )
            if callback is not None:
                callback(state)
            stop = int(stop)
            if stop == STOP_TAU or stop == STOP_RANK:
                done, final_stop = True, stop
            elif stop == STOP_REFRESH:
                with span("repro.driver.refresh"):
                    state = refresh_fn(S, state)
                    ref_sq = max(float(jnp.max(state.norms_sq)), 1e-300)
                    ref_sq_d = jnp.asarray(ref_sq, rdt)
                if ref_sq ** 0.5 < tau:
                    done, final_stop = True, STOP_TAU
                elif ref_sq ** 0.5 <= floor_estimate(eps, scale, int(state.k)):
                    done, final_stop = True, STOP_FLOOR
            if not done and int(state.k) + p > max_slots:
                done = True  # out of slots; final_stop stays STOP_NONE
            if checkpoint_dir is not None:
                seq = _save_dist_checkpoint(
                    checkpoint_dir, seq, state, ref_sq, scale, done,
                    final_stop)
    # compact holes + cap at max_k: shared with the resident blocked driver
    from repro.core.block_greedy import _compact_result

    with span("repro.driver.compact"):
        return _compact_result(state, max_k, final_stop)
