"""`build_basis`: the one front door to every reduction strategy.

Dispatches a :class:`~repro.api.spec.ReductionSpec` to the matching driver
in :mod:`repro.core` and wraps the result as a
:class:`~repro.api.artifact.ReducedBasis`.  Strategy ``"auto"`` picks the
driver from the problem shape, a device-memory budget and a DRAM-roofline
machine model:

  mesh given                         -> "distributed"
  roof-bound, max_k set, greedy pass
    count > 2x the sketch's          -> "randomized" (one-pass range-finder)
  fits budget, sweep roof-bound      -> "block_greedy" (BLAS-3 panel sweep)
  fits budget otherwise              -> "greedy"   (resident chunked)
  too big, sweep roof-bound          -> "streamed" + block_p (blocked)
  too big otherwise                  -> "streamed" (tile-streamed)

"Roof-bound" means the Eq.-(6.3) pivot sweep's arithmetic intensity sits
below the machine balance (peak FLOP/s over DRAM bandwidth) AND one sweep
over S exceeds the last-level cache — i.e. every basis vector pays a full
DRAM read of S, which block pivoting amortizes by block_p.  The model's
knobs come from the spec (``bandwidth_gbps`` / ``peak_gflops`` /
``cache_bytes``), the ``REPRO_DRAM_BW_GBPS`` / ``REPRO_PEAK_GFLOPS`` /
``REPRO_LLC_BYTES`` env vars, or per-platform defaults, in that order.

The choice (and the roofline numbers behind it) is logged on logger
``repro.api``.  Every strategy goes through the same drivers the legacy
entry points use, so results are bit-for-bit identical to calling those
drivers directly (asserted in ``tests/test_api.py``).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.artifact import ReducedBasis
from repro.api.spec import STRATEGIES, ReductionSpec

logger = logging.getLogger("repro.api")

_ENV_BUDGET = "REPRO_DEVICE_MEM_BUDGET"
_FALLBACK_BUDGET = 4 << 30  # 4 GiB when nothing else is detectable


def device_memory_budget() -> int:
    """Device-memory budget (bytes) the ``"auto"`` strategy plans against.

    Precedence: ``REPRO_DEVICE_MEM_BUDGET`` env var > the default device's
    reported memory (``memory_stats()["bytes_limit"]``, TPU/GPU) > half of
    host MemAvailable (CPU devices share host RAM) > 4 GiB.  A TPU must
    report its limit: there this raises instead of budgeting against the
    host's RAM.
    """
    env = os.environ.get(_ENV_BUDGET)
    if env:
        return int(float(env))
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        limit = (dev.memory_stats() or {}).get("bytes_limit")
        if not limit:
            raise RuntimeError(
                f"{dev.device_kind} reports no memory_stats()['bytes_limit']")
        return int(limit)
    try:
        stats = dev.memory_stats()
        if stats and stats.get("bytes_limit"):
            return int(stats["bytes_limit"])
    except Exception:  # memory_stats unimplemented on some backends
        pass
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024 // 2
    except OSError:
        pass
    return _FALLBACK_BUDGET


def _resident_bytes(shape, dtype, max_k: Optional[int]) -> int:
    """Device footprint of a resident greedy build: S + Q + R (+ M-vectors)."""
    N, M = shape
    mk = min(N, M) if max_k is None else min(max_k, N, M)
    itemsize = jnp.dtype(dtype).itemsize
    return itemsize * (N * M + mk * (N + M)) + 4 * M * itemsize


# --------------------------------------------------- DRAM roofline model ----

_ENV_BW = "REPRO_DRAM_BW_GBPS"
_ENV_FLOPS = "REPRO_PEAK_GFLOPS"
_ENV_CACHE = "REPRO_LLC_BYTES"

# Conservative per-platform roofs for when nothing is measured/configured:
# (DRAM bandwidth GB/s, peak GFLOP/s, last-level cache bytes).  The point
# is the RATIO (machine balance) and the cache cutoff, not precision —
# override with the spec fields or REPRO_* env vars for a measured box.
_PLATFORM_ROOFS = {
    "cpu": (25.0, 80.0, 64 << 20),
    "gpu": (900.0, 30_000.0, 64 << 20),
    "tpu": (800.0, 100_000.0, 128 << 20),
}

# Panel width "auto" applies when it decides blocking pays and the spec
# left block_p at the stepwise default: one S read per 8 bases cuts the
# dominant DRAM term ~8x while the staleness cost stays a few extra bases
# on fast-decaying families (tests/test_block_greedy.py).
_AUTO_BLOCK_P = 8


def machine_roofline(spec: Optional[ReductionSpec] = None):
    """(bandwidth GB/s, peak GFLOP/s, cache bytes) the ``"auto"`` roofline
    model plans against.  Precedence per knob: spec field >
    ``REPRO_DRAM_BW_GBPS`` / ``REPRO_PEAK_GFLOPS`` / ``REPRO_LLC_BYTES``
    env var > one-time on-device measurement
    (:func:`repro.api.roofline.measured_roofline` for bandwidth/FLOPs,
    :func:`repro.api.roofline.measured_cache_bytes` for the LLC
    working-set sweep; all skipped under ``REPRO_ROOFLINE_MEASURE=0``) >
    per-platform default."""
    from repro.api.roofline import (
        measured_cache_bytes,
        measured_roofline,
        roofline_measurement_enabled,
    )

    defaults = _PLATFORM_ROOFS.get(jax.default_backend(),
                                   _PLATFORM_ROOFS["cpu"])

    def pinned(field, env):
        if field is not None:
            return float(field)
        raw = os.environ.get(env)
        return float(raw) if raw else None

    bw = pinned(getattr(spec, "bandwidth_gbps", None), _ENV_BW)
    gf = pinned(getattr(spec, "peak_gflops", None), _ENV_FLOPS)
    if (bw is None or gf is None) and roofline_measurement_enabled():
        # only knobs nobody pinned are filled from the measurement (a
        # failed calibration reports 0.0 and falls through to defaults)
        m_bw, m_gf = measured_roofline()
        if bw is None and m_bw > 0:
            bw = m_bw
        if gf is None and m_gf > 0:
            gf = m_gf

    cache_field = getattr(spec, "cache_bytes", None)
    if cache_field is not None:
        cache = int(cache_field)
    else:
        raw = os.environ.get(_ENV_CACHE)
        if raw:
            cache = int(float(raw))
        else:
            cache = defaults[2]
            if roofline_measurement_enabled():
                m_cache = measured_cache_bytes()
                if m_cache > 0:
                    cache = m_cache

    return (
        defaults[0] if bw is None else bw,
        defaults[1] if gf is None else gf,
        cache,
    )


def _sweep_roofline(shape, dtype, spec: Optional[ReductionSpec] = None):
    """Classify the Eq.-(6.3) pivot sweep for this problem.

    Returns ``(roof_bound, why)``: one sweep reads S once (``N*M*itemsize``
    bytes) for 2 real FLOPs per element (8 for complex, on the plane-split
    path).  The sweep is DRAM-roof-bound when that intensity sits below the
    machine balance AND the sweep exceeds the last-level cache — exactly
    the regime where block pivoting (one read per block_p bases) is the
    lever.
    """
    bw, gflops, cache = machine_roofline(spec)
    N, M = shape
    dt = jnp.dtype(dtype)
    sweep_bytes = N * M * dt.itemsize
    flops = (8 if jnp.issubdtype(dt, jnp.complexfloating) else 2) * N * M
    intensity = flops / sweep_bytes
    balance = gflops / bw
    roof_bound = intensity < balance and sweep_bytes > cache
    why = (f"sweep ~{sweep_bytes / 1e6:.0f} MB at {intensity:.2f} FLOP/B "
           f"vs balance {balance:.2f} FLOP/B, cache ~{cache / 1e6:.0f} MB"
           f" -> {'roof-bound' if roof_bound else 'not roof-bound'}")
    return roof_bound, why


def _estimated_max_k(spec: ReductionSpec, shape):
    """Sketch-estimate a ``max_k`` for planning when the caller gave none.

    Costs a few cheap streamed passes over the source
    (:func:`repro.core.randomized.estimate_rank`), so it runs only where
    the answer changes the plan (roof-bound sweeps, where the
    greedy-vs-sketch pass-count comparison needs a rank) and only when
    on-device probing is enabled (``REPRO_ROOFLINE_MEASURE=0`` — the CI
    determinism knob — also opts out of this).  Returns None when the
    source can't be probed (decision-level callers pass placeholder
    sources) or the estimate saturated (a lower bound must not become a
    cap).  The returned cap carries 25% + sketch_p headroom: the build's
    own tau stop remains the authority, the cap just bounds planning and
    the Q allocation.
    """
    from repro.core.randomized import estimate_rank

    try:
        est = estimate_rank(spec.source, tau=float(spec.tau),
                            seed=spec.sketch_seed, kind=spec.sketch_kind,
                            tile_m=spec.tile_m, backend=spec.backend)
    except Exception as e:
        logger.info("rank estimation skipped (%s)", e)
        return None
    if est.saturated:
        logger.info("rank estimate saturated at ell=%d; not capping",
                    est.ell)
        return None
    cap = -(-est.k * 5 // 4) + spec.sketch_p
    cap = min(cap, int(shape[0]), int(shape[1]))
    logger.info("sketch-estimated rank ~%d (ell=%d, %d pass(es)) -> "
                "planning max_k=%d", est.k, est.ell, est.passes, cap)
    return cap


def _auto_strategy(spec: ReductionSpec, shape, dtype):
    """Resolve ``"auto"`` to ``(strategy, block_p, max_k)`` and log the
    decision.  ``max_k`` is ``spec.max_k`` unless the caller gave none
    and a sketch-based rank estimate filled one in
    (:func:`_estimated_max_k`)."""
    block_p = spec.block_p
    max_k = spec.max_k
    if spec.mesh is not None:
        choice, why = "distributed", "a mesh was passed"
    else:
        need = _resident_bytes(shape, dtype, spec.max_k)
        budget = (spec.memory_budget_bytes
                  if spec.memory_budget_bytes is not None
                  else device_memory_budget())
        roof_bound, roof_why = _sweep_roofline(shape, dtype, spec)
        fits = need <= budget
        fit_why = (f"resident footprint ~{need / 1e6:.0f} MB "
                   f"{'fits' if fits else 'exceeds'} the device budget "
                   f"~{budget / 1e6:.0f} MB")
        if roof_bound and block_p == 1:
            block_p = _AUTO_BLOCK_P
        if fits:
            choice = "block_greedy" if roof_bound else "greedy"
        else:
            choice = "streamed"
        why = f"{fit_why}; {roof_why}"
        if roof_bound:
            why += f"; blocked sweep, block_p={block_p}"
        # On a roof-bound sweep every basis costs ~1/block_p of a DRAM
        # read of S, so a greedy build streams S ~ceil(max_k / block_p)
        # times; the one-pass sketch pays 1 + 2*sketch_power passes
        # regardless of k.  When a rank target exists (given, or — the
        # PR-9 follow-on — sketch-estimated when probing is enabled) and
        # greedy's pass count exceeds TWICE the sketch's, the
        # range-finder wins even after paying its probabilistic-vs-exact
        # error margin.
        if roof_bound and max_k is None:
            from repro.api.roofline import roofline_measurement_enabled

            if roofline_measurement_enabled():
                max_k = _estimated_max_k(spec, shape)
                if max_k is not None:
                    why += f"; sketch-estimated max_k={max_k}"
        if roof_bound and max_k is not None:
            greedy_passes = -(-max_k // max(block_p, 1))
            sketch_passes = 1 + 2 * spec.sketch_power
            if greedy_passes > 2 * sketch_passes:
                choice = "randomized"
                block_p = spec.block_p  # blocking is a greedy-only knob
                why += (f"; ~{greedy_passes} greedy passes over S vs "
                        f"{sketch_passes} sketch pass(es) -> randomized")
    logger.info(
        "auto strategy -> %r for shape %s %s (%s)",
        choice, tuple(shape), jnp.dtype(dtype).name, why,
    )
    return choice, block_p, max_k


# ------------------------------------------------------- strategy bodies ----
# Each returns (Q, pivots, errs, R, k, extras) with the arrays TRIMMED to
# the accepted rank and bit-identical to the corresponding legacy driver's
# (sliced) output; ``extras`` is a JSON-serializable dict merged into the
# artifact provenance (e.g. the adaptive driver's panel-width trajectory,
# the greedy family's terminal stop code).  ``ckpt_dir`` is the resolved
# mid-build checkpoint directory (the workdir's ``build/`` scratch, or
# ``spec.checkpoint_dir``); ``pod``/``mgs`` are single-shot factorizations
# with nothing to checkpoint and ignore it.


def _trim_greedy(res, extras=None):
    from repro.core.greedy import STOP_NAMES

    k = int(res.k)
    extras = dict(extras or {})
    stop = getattr(res, "stop", None)
    if stop is not None:
        extras["stop"] = STOP_NAMES.get(int(stop), str(int(stop)))
    return (res.Q[:, :k], np.asarray(res.pivots[:k]),
            np.asarray(res.errs[:k]),
            None if res.R is None else np.asarray(res.R[:k]), k,
            extras)


def _build_greedy(spec, S, ckpt_dir=None):
    from repro.core.greedy import rb_greedy

    return _trim_greedy(rb_greedy(
        S, tau=spec.tau, max_k=spec.max_k, kappa=spec.kappa,
        max_passes=spec.max_passes, callback=spec.callback,
        refresh=spec.refresh, refresh_safety=spec.refresh_safety,
        chunk=spec.chunk, backend=spec.backend,
        checkpoint_dir=ckpt_dir, resume=spec.resume,
    ))


def _build_block_greedy(spec, S, ckpt_dir=None):
    from repro.core.block_greedy import _rb_greedy_block_impl

    # spec.chunk counts greedy ITERATIONS per device-resident chunk; the
    # blocked driver's chunk counts BLOCKS of block_p, so divide to keep
    # the host-sync cadence the user configured.
    diag = {} if spec.adaptive_block else None
    res = _rb_greedy_block_impl(
        S, tau=spec.tau, p=spec.block_p, max_k=spec.max_k,
        kappa=spec.kappa, max_passes=spec.max_passes, refresh=spec.refresh,
        refresh_safety=spec.refresh_safety, backend=spec.backend,
        chunk=max(1, spec.chunk // max(spec.block_p, 1)),
        callback=spec.callback, panel=spec.panel_ortho,
        adaptive=spec.adaptive_block, diagnostics=diag,
        checkpoint_dir=ckpt_dir, resume=spec.resume,
    )
    return _trim_greedy(res, diag)


def _build_distributed(spec, S, ckpt_dir=None):
    from repro.core.distributed import distributed_greedy

    if spec.mesh is None:
        raise ValueError('strategy "distributed" requires spec.mesh')
    N, M = S.shape
    max_k = min(N, M) if spec.max_k is None else spec.max_k
    return _trim_greedy(distributed_greedy(
        S, tau=spec.tau, max_k=max_k, mesh=spec.mesh,
        callback=spec.callback, refresh=spec.refresh,
        refresh_safety=spec.refresh_safety, kappa=spec.kappa,
        max_passes=spec.max_passes, chunk=spec.chunk, backend=spec.backend,
        block_p=spec.block_p, panel_ortho=spec.panel_ortho,
        checkpoint_dir=ckpt_dir, resume=spec.resume,
    ))


def _build_streamed(spec, _S_unused=None, ckpt_dir=None):
    from repro.core.streaming import rb_greedy_streamed

    res = rb_greedy_streamed(
        spec.source, tau=spec.tau, max_k=spec.max_k, tile_m=spec.tile_m,
        block_p=spec.block_p, kappa=spec.kappa,
        max_passes=spec.max_passes, refresh=spec.refresh,
        refresh_safety=spec.refresh_safety, backend=spec.backend,
        panel_ortho=spec.panel_ortho,
        keep_R=spec.keep_R, checkpoint_dir=ckpt_dir,
        checkpoint_every_tiles=spec.checkpoint_every_tiles,
        resume=spec.resume, callback=spec.callback,
    )
    return _trim_greedy(res)


def _build_mgs(spec, S, ckpt_dir=None):
    from repro.core.mgs import _mgs_pivoted_qr_impl

    res = _mgs_pivoted_qr_impl(S, tau=spec.tau, max_k=spec.max_k)
    return (res.Q, np.asarray(res.pivots), np.asarray(res.r_diag),
            np.asarray(res.R), int(res.k), {})


def _build_pod(spec, S, ckpt_dir=None):
    from repro.core.pod import pod

    res = pod(S, tau=spec.tau)
    k = int(res.k)
    if spec.max_k is not None:
        k = min(k, spec.max_k)
    return (res.basis[:, :k], np.zeros((0,), np.int32),
            np.asarray(res.sigmas[:k]), None, k, {})


def _sketch_extras(res):
    """Randomized provenance: sketch params + singular-value estimates."""
    return {
        "sketch": {
            "ell": int(res.ell),
            "p": int(res.sketch_p),
            "power": int(res.power),
            "seed": int(res.seed),
            "kind": res.kind,
            "n_passes": int(res.n_passes),
            "n_tiles": int(res.n_tiles),
        },
        "sigma_estimates": [float(s) for s in res.svals],
    }


def _run_sketch(spec, ckpt_dir):
    from repro.core.randomized import rb_randomized_streamed

    return rb_randomized_streamed(
        spec.source, tau=spec.tau, max_k=spec.max_k,
        sketch_p=spec.sketch_p, power=spec.sketch_power,
        seed=spec.sketch_seed, kind=spec.sketch_kind,
        tile_m=spec.tile_m, backend=spec.backend,
        checkpoint_dir=ckpt_dir,
        checkpoint_every_tiles=spec.checkpoint_every_tiles,
        resume=spec.resume and ckpt_dir is not None,
    )


def _build_randomized(spec, _S_unused=None, ckpt_dir=None):
    res = _run_sketch(spec, ckpt_dir)
    k = int(res.k)
    # POD-shaped result: no pivots (the basis spans a sketched range, not
    # selected columns), errs are the spectrum estimates.
    return (res.Q, np.zeros((0,), np.int32),
            np.asarray(res.svals[:k]), None, k, _sketch_extras(res))


def _build_sketch_greedy(spec, _S_unused=None, ckpt_dir=None):
    """One-pass sketch initializes Q; streamed greedy refines to tau.

    The sketch's basis enters :func:`repro.core.streaming.
    rb_greedy_streamed` through the PR-6 ``warm_start=`` seam with
    sentinel pivots (-1: these columns were not selected from S), and the
    greedy loop extends it with whatever directions the sketch missed —
    typically zero-to-few sweeps on well-sketched families, at tau's
    EXACT Eq.-(6.3) error control rather than the probabilistic bound.
    Refinement runs stepwise (block_p=1): the blocked compaction path
    drops pivot==-1 slots, which would evict the warm columns.
    """
    from repro.core.streaming import rb_greedy_streamed

    sketch_dir = os.path.join(ckpt_dir, "sketch") if ckpt_dir else None
    refine_dir = os.path.join(ckpt_dir, "refine") if ckpt_dir else None
    res = _run_sketch(spec, sketch_dir)
    k0 = int(res.k)
    warm = {
        "Q": res.Q,
        "pivots": np.full((k0,), -1, np.int32),
        "errs": np.asarray(res.svals[:k0]),
    }
    refined = rb_greedy_streamed(
        spec.source, tau=spec.tau, max_k=spec.max_k, tile_m=spec.tile_m,
        block_p=1, kappa=spec.kappa, max_passes=spec.max_passes,
        refresh=spec.refresh, refresh_safety=spec.refresh_safety,
        backend=spec.backend, panel_ortho=spec.panel_ortho,
        keep_R=spec.keep_R, checkpoint_dir=refine_dir,
        checkpoint_every_tiles=spec.checkpoint_every_tiles,
        resume=spec.resume, callback=spec.callback, warm_start=warm,
    )
    out = _trim_greedy(refined, _sketch_extras(res))
    out[5]["sketch"]["k0"] = k0
    out[5]["sketch"]["refined_k"] = out[4]
    return out


_BUILDERS = {
    "greedy": _build_greedy,
    "block_greedy": _build_block_greedy,
    "distributed": _build_distributed,
    "streamed": _build_streamed,
    "randomized": _build_randomized,
    "sketch+greedy": _build_sketch_greedy,
    "mgs": _build_mgs,
    "pod": _build_pod,
}
# "batched" is absent deliberately: it returns a ReducedBasisSet, not a
# single basis, so build_basis delegates to build_basis_set before the
# single-basis pipeline starts (see _is_batched_workload).

# Strategies that stream the provider directly and never materialize the
# source on device (build_basis skips materialize_source for these).
_STREAMING_STRATEGIES = ("streamed", "randomized", "sketch+greedy")


def _is_batched_workload(spec: ReductionSpec) -> bool:
    """Does this spec describe a many-basis (B-lane) build?

    True when ``spec.batch`` is set, or the source is inherently
    B-laned: a (B, N, M) stacked array, a list/tuple of per-lane
    sources, or a :class:`~repro.data.bands.BandSplit`.
    """
    if spec.batch is not None:
        return True
    from repro.data.bands import BandSplit

    src = spec.source
    if isinstance(src, BandSplit) or isinstance(src, (list, tuple)):
        return True
    return getattr(src, "ndim", None) == 3


def build_basis(spec: ReductionSpec | None = None,
                **kwargs) -> ReducedBasis:
    """Build a reduced basis: the front door to every strategy.

    Call with a :class:`ReductionSpec`, keyword arguments, or both (the
    keywords override spec fields)::

        basis = build_basis(source=S, tau=1e-6)              # auto strategy
        basis = build_basis(ReductionSpec(source=S, strategy="pod"))
        basis = build_basis(spec, tau=1e-8)                  # override

    Returns a :class:`ReducedBasis` whose arrays are bit-identical to the
    corresponding legacy driver's output, trimmed to the accepted rank,
    with build provenance attached.  A many-basis workload —
    ``strategy="batched"``, or ``"auto"`` with ``spec.batch`` / a stacked
    (B, N, M) / list / :class:`~repro.data.bands.BandSplit` source —
    delegates to :func:`build_basis_set` and returns its
    :class:`~repro.api.basis_set.ReducedBasisSet` of B children instead.
    """
    if spec is None:
        spec = ReductionSpec(**kwargs)
    elif kwargs:
        spec = dataclasses.replace(spec, **kwargs)
    if not isinstance(spec, ReductionSpec):
        raise TypeError(
            f"build_basis takes a ReductionSpec (or keyword args), got "
            f"{type(spec).__name__}"
        )

    # Many-basis workloads return a set; decide BEFORE touching providers
    # (a stacked 3-D source is not a valid single-basis provider).
    if spec.strategy == "batched":
        return build_basis_set(spec)
    if spec.strategy == "auto" and _is_batched_workload(spec):
        logger.info(
            "auto strategy -> 'batched' (batch=%s, %s source)",
            spec.batch, type(spec.source).__name__)
        return build_basis_set(spec)

    from repro.core.backend import resolve_backend
    from repro.data.providers import as_provider, materialize_source

    # ------------------------------------------- workdir build lifecycle --
    # A workdir owns the whole build: mid-build checkpoints in
    # <workdir>/build/, the finished basis finalized atomically into
    # <workdir> itself, scratch removed on success.  Crash anywhere +
    # relaunch with resume=True lands on the identical artifact.
    build_dir = None
    if spec.workdir is not None:
        build_dir = os.path.join(spec.workdir, "build")
        if spec.resume:
            try:
                basis = ReducedBasis.load(spec.workdir)
            except (FileNotFoundError, IOError):
                pass  # nothing finalized yet: (re)build below
            else:
                # Already finalized (e.g. the previous run died between
                # finalize and scratch cleanup): return it, finish the GC.
                import shutil

                shutil.rmtree(build_dir, ignore_errors=True)
                logger.info("workdir %s already holds a finalized basis; "
                            "returning it", spec.workdir)
                return basis
        else:
            # A fresh (non-resume) build must not splice onto a previous
            # run's checkpoints.
            import shutil

            shutil.rmtree(build_dir, ignore_errors=True)
    ckpt_dir = build_dir if build_dir is not None else spec.checkpoint_dir

    strategy = spec.strategy
    if strategy in _STREAMING_STRATEGIES:
        shape, dtype = (p := as_provider(spec.source)).shape, p.dtype
        S = None
    else:
        # Every resident strategy accepts anything as_provider accepts
        # (small sources are materialized); "auto" decides BEFORE
        # materializing so an out-of-core source never lands on device.
        if strategy == "auto":
            prov = as_provider(spec.source)
            shape, dtype = prov.shape, prov.dtype
            strategy, auto_p, auto_k = _auto_strategy(spec, shape, dtype)
            if auto_p != spec.block_p:
                # the roofline model opted into blocking: the chosen panel
                # width must reach the driver (and the provenance)
                spec = dataclasses.replace(spec, block_p=auto_p)
            if auto_k != spec.max_k:
                # a sketch-estimated rank cap (with headroom) must reach
                # the chosen driver — the randomized builder sizes its
                # sketch from it, the greedy family bounds Q with it
                spec = dataclasses.replace(spec, max_k=auto_k)
        if strategy in _STREAMING_STRATEGIES:
            S = None
        else:
            S = materialize_source(spec.source)
            shape, dtype = S.shape, S.dtype

    build = _BUILDERS[strategy]
    t0 = time.perf_counter()
    Q, pivots, errs, R, k, extras = build(spec, S, ckpt_dir)
    jax.block_until_ready(Q)
    wall = time.perf_counter() - t0

    provenance = {
        "strategy": strategy,
        "requested_strategy": spec.strategy,
        "backend": (None if strategy in ("pod", "mgs")
                    else resolve_backend(spec.backend)),
        "dtype": jnp.dtype(dtype).name,
        "shape": [int(shape[0]), int(shape[1])],
        "tau": spec.tau,
        "max_k": spec.max_k,
        "block_p": spec.block_p,
        "wall_time_s": wall,
        "spec": spec.describe(),
        "repro_version": _repro_version(),
        **extras,
    }
    basis = ReducedBasis(Q=Q, pivots=pivots, errs=errs, k=k, R=R,
                         provenance=provenance)
    if spec.workdir is not None:
        # Finalize: atomic save into the workdir, THEN drop the build
        # scratch.  A crash between the two leaves a finalized artifact
        # plus orphan scratch, which the resume path above garbage-collects
        # on the next launch.
        import shutil

        basis.save(spec.workdir)
        shutil.rmtree(build_dir, ignore_errors=True)
    return basis


def build_basis_set(spec: ReductionSpec | None = None, **kwargs):
    """Build B reduced bases in one lockstep batched pass.

    The many-basis front door: accepts a stacked (B, N, M) array, a
    list/tuple of per-lane sources, a
    :class:`~repro.data.bands.BandSplit` (banded workload), or a shared
    (N, M) source with ``batch=B`` / a length-B ``tau`` sequence
    (tau-sweep over one matrix).  Runs
    :func:`repro.core.batch_greedy.batch_rb_greedy` — one fused pass over
    the snapshots for all B lanes — and returns a
    :class:`~repro.api.basis_set.ReducedBasisSet` whose children are
    bit-identical (stacked layouts) to B sequential
    :func:`~repro.core.greedy.rb_greedy` builds.

    With ``workdir=`` the finished set finalizes there atomically
    (``resume=True`` returns an already-finalized set without
    rebuilding).  ``build_basis`` delegates here for
    ``strategy="batched"`` (and for ``"auto"`` on batched workloads), so
    calling this directly is optional.
    """
    if spec is None:
        spec = ReductionSpec(**kwargs)
    elif kwargs:
        spec = dataclasses.replace(spec, **kwargs)
    if spec.strategy not in ("batched", "auto"):
        raise ValueError(
            f"build_basis_set builds the batched strategy, got "
            f"{spec.strategy!r}")

    from repro.api.basis_set import ReducedBasisSet
    from repro.core.backend import resolve_backend
    from repro.core.batch_greedy import batch_rb_greedy
    from repro.data.bands import BandSplit
    from repro.data.providers import materialize_source

    if spec.workdir is not None and spec.resume:
        try:
            bset = ReducedBasisSet.load(spec.workdir)
        except (FileNotFoundError, IOError):
            pass  # nothing finalized yet: build below
        else:
            logger.info("workdir %s already holds a finalized basis set; "
                        "returning it", spec.workdir)
            return bset

    src = spec.source
    bands_meta = None
    if isinstance(src, BandSplit):
        bands_meta = {
            "edges": [[int(lo), int(hi)] for lo, hi in src.edges],
            "n_freq": int(src.n_freq),
            "from_real": bool(src.from_real),
        }
        src = src.stack
    elif isinstance(src, (list, tuple)):
        src = [materialize_source(s) for s in src]
    else:
        src = materialize_source(src)
        if src.ndim not in (2, 3):
            raise ValueError(
                f"batched strategy needs an (N, M), (B, N, M), list, or "
                f"BandSplit source, got shape {src.shape}")

    t0 = time.perf_counter()
    res = batch_rb_greedy(
        src, spec.tau, max_k=spec.max_k, batch=spec.batch,
        kappa=spec.kappa, max_passes=spec.max_passes,
        refresh=spec.refresh, refresh_safety=spec.refresh_safety,
        chunk=spec.chunk, backend=spec.backend, callback=spec.callback,
    )
    jax.block_until_ready(res.Q)
    wall = time.perf_counter() - t0

    B = res.batch
    taus = np.broadcast_to(
        np.atleast_1d(np.asarray(spec.tau, dtype=np.float64)), (B,))
    layout = "stacked" if getattr(src, "ndim", 3) == 3 or \
        isinstance(src, list) else "shared"
    base = {
        "strategy": "batched",
        "requested_strategy": spec.strategy,
        "backend": resolve_backend(spec.backend),
        "batch": B,
        "layout": layout,
        "dtype": jnp.dtype(res.Q.dtype).name,
        "shape": [int(res.Q.shape[1]), int(res.R.shape[2])],
        "tau": [float(t) for t in taus],
        "max_k": spec.max_k,
        "wall_time_s": wall,
        "spec": spec.describe(),
        "repro_version": _repro_version(),
        **({"bands": bands_meta} if bands_meta is not None else {}),
    }
    children = []
    for b in range(B):
        Q, pivots, errs, R, k, extras = _trim_greedy(res.lane(b))
        prov = dict(base)
        prov["lane"] = {"index": b, "tau": float(taus[b]), **extras}
        children.append(ReducedBasis(Q=Q, pivots=pivots, errs=errs, k=k,
                                     R=R, provenance=prov))
    bset = ReducedBasisSet(children=tuple(children), provenance=base)
    if spec.workdir is not None:
        bset.save(spec.workdir)
    return bset


def _repro_version() -> str:
    import repro

    return getattr(repro, "__version__", "unknown")
