"""The front door (repro.api): strategy parity, auto selection, artifact
save/load round trips, and deprecation hygiene of the legacy wrappers.

Acceptance contract of the API PR: ``build_basis`` with every strategy
returns a ReducedBasis whose Q/pivots/errs match the corresponding legacy
driver bit-for-bit, ``"auto"`` picks resident vs streamed vs distributed
correctly, and a saved basis reloads to working ``eim()``/``roq_weights()``.
"""

import logging
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_smooth_matrix
from repro.api import STRATEGIES, ReducedBasis, ReductionSpec, build_basis
from repro.core import pod, rb_greedy, rb_greedy_streamed
from repro.core.block_greedy import _rb_greedy_block_impl
from repro.core.mgs import _mgs_pivoted_qr_impl

TAU = 1e-3


def _S(dtype=np.complex64):
    return jnp.asarray(make_smooth_matrix(dtype=dtype))


def _assert_bitwise(basis, Q, pivots, errs, k):
    assert basis.k == k
    assert basis.Q.shape == (Q.shape[0], k)
    assert np.array_equal(np.asarray(basis.Q), np.asarray(Q))
    assert np.array_equal(basis.pivots, np.asarray(pivots))
    assert np.array_equal(basis.errs, np.asarray(errs))


# ------------------------------------------------------------ parity ----


@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
def test_greedy_strategy_matches_legacy(dtype):
    S = _S(dtype)
    basis = build_basis(source=S, strategy="greedy", tau=TAU)
    ref = rb_greedy(S, tau=TAU)
    k = int(ref.k)
    _assert_bitwise(basis, ref.Q[:, :k], ref.pivots[:k], ref.errs[:k], k)
    assert np.array_equal(np.asarray(basis.R), np.asarray(ref.R[:k]))


@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
def test_streamed_strategy_matches_legacy(dtype):
    S = _S(dtype)
    basis = build_basis(source=S, strategy="streamed", tau=TAU, tile_m=40)
    ref = rb_greedy_streamed(S, tau=TAU, tile_m=40)
    k = int(ref.k)
    _assert_bitwise(basis, ref.Q[:, :k], ref.pivots[:k], ref.errs[:k], k)


def test_mgs_strategy_matches_legacy():
    S = _S(np.complex128)
    basis = build_basis(source=S, strategy="mgs", tau=TAU)
    ref = _mgs_pivoted_qr_impl(S, tau=TAU)
    _assert_bitwise(basis, ref.Q, ref.pivots, ref.r_diag, int(ref.k))


def test_pod_strategy_matches_legacy():
    S = _S(np.complex64)
    basis = build_basis(source=S, strategy="pod", tau=TAU)
    ref = pod(S, tau=TAU)
    k = int(ref.k)
    _assert_bitwise(basis, ref.basis[:, :k], np.zeros((0,), np.int32),
                    ref.sigmas[:k], k)
    assert basis.R is None


def test_block_greedy_strategy_matches_legacy():
    S = _S(np.complex64)
    basis = build_basis(source=S, strategy="block_greedy", tau=TAU,
                        block_p=2)
    ref = _rb_greedy_block_impl(S, tau=TAU, p=2)
    k = int(ref.k)
    _assert_bitwise(basis, ref.Q[:, :k], ref.pivots[:k], ref.errs[:k], k)


def test_distributed_strategy_matches_legacy():
    """Single-device mesh in-process (the 8-device parity suite lives in
    test_distributed_greedy.py); the front door must hand back exactly
    what distributed_greedy produces."""
    from repro.compat import make_auto_mesh
    from repro.core.distributed import distributed_greedy

    S = _S(np.complex64)
    mesh = make_auto_mesh((1,), ("cols",))
    basis = build_basis(source=S, strategy="distributed", tau=TAU,
                        mesh=mesh)
    ref = distributed_greedy(S, tau=TAU, max_k=min(*S.shape), mesh=mesh)
    k = int(ref.k)
    _assert_bitwise(basis, ref.Q[:, :k], ref.pivots[:k], ref.errs[:k], k)


def test_distributed_requires_mesh():
    with pytest.raises(ValueError, match="mesh"):
        build_basis(source=_S(), strategy="distributed", tau=TAU)


# ----------------------------------------------------- auto selection ----


def test_auto_picks_resident_when_it_fits(caplog):
    S = _S(np.complex64)
    with caplog.at_level(logging.INFO, logger="repro.api"):
        basis = build_basis(source=S, tau=TAU)
    assert basis.provenance["strategy"] == "greedy"
    assert basis.provenance["requested_strategy"] == "auto"
    assert any("auto strategy" in r.getMessage() for r in caplog.records)
    ref = rb_greedy(S, tau=TAU)
    k = int(ref.k)
    _assert_bitwise(basis, ref.Q[:, :k], ref.pivots[:k], ref.errs[:k], k)


def test_auto_picks_streamed_on_forced_small_budget():
    S = _S(np.complex64)
    basis = build_basis(source=S, tau=TAU, memory_budget_bytes=1024,
                        tile_m=40)
    assert basis.provenance["strategy"] == "streamed"
    ref = rb_greedy_streamed(S, tau=TAU, tile_m=40)
    k = int(ref.k)
    _assert_bitwise(basis, ref.Q[:, :k], ref.pivots[:k], ref.errs[:k], k)


def test_auto_picks_distributed_with_mesh():
    from repro.compat import make_auto_mesh

    S = _S(np.complex64)
    basis = build_basis(source=S, tau=TAU,
                        mesh=make_auto_mesh((1,), ("cols",)))
    assert basis.provenance["strategy"] == "distributed"


def test_auto_respects_env_budget(monkeypatch):
    from repro.api.build import device_memory_budget

    monkeypatch.setenv("REPRO_DEVICE_MEM_BUDGET", "12345")
    assert device_memory_budget() == 12345


@pytest.mark.parametrize("stats", [{"bytes_limit": 16 << 30}, {}, None])
def test_tpu_budget_comes_from_the_device(monkeypatch, stats):
    """On a TPU the budget is the device's bytes_limit, or an error: never
    the host-RAM fallback."""
    import repro.api.build as build_mod

    class FakeTpu:
        platform = "tpu"
        device_kind = "TPU v5 lite"

        def memory_stats(self):
            return stats

    monkeypatch.delenv("REPRO_DEVICE_MEM_BUDGET", raising=False)
    monkeypatch.setattr(build_mod.jax, "devices", lambda: [FakeTpu()])
    if stats:
        assert build_mod.device_memory_budget() == 16 << 30
    else:
        with pytest.raises(RuntimeError, match="bytes_limit"):
            build_mod.device_memory_budget()


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and is left to JAX; otherwise the
    cache sits at the fixed <repo>/.jax_cache."""
    import os

    from repro.compat import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = enable_compile_cache()
        if env_dir:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            repo = os.path.dirname(os.path.dirname(os.path.abspath(
                __file__)))
            assert got == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# --------------------------------------- auto DRAM-roofline (PR 4) ----


def test_auto_picks_block_greedy_on_roof_bound_shape():
    """Acceptance: the bandwidth model must select block_greedy for the
    paper benchmark's roof-bound f32 resident shape (N=4096, M=16384) —
    the shape whose committed BENCH rows sat BELOW 1x before blocking.
    Decision-level: the spec's source is never touched."""
    from repro.api.build import _auto_strategy

    spec = ReductionSpec(source="unused", strategy="auto")
    choice, block_p, _k = _auto_strategy(spec, (4096, 16384), jnp.float32)
    assert choice == "block_greedy"
    assert block_p > 1  # the model raised the stepwise default


def test_auto_block_greedy_end_to_end(caplog):
    """Forcing the roofline knobs makes a small matrix classify as
    roof-bound: auto must build THROUGH the blocked driver (logged),
    bit-identical to calling it directly."""
    from repro.core.block_greedy import _rb_greedy_block_impl

    S = _S(np.float32)
    with caplog.at_level(logging.INFO, logger="repro.api"):
        basis = build_basis(source=S, tau=TAU, block_p=2, cache_bytes=1)
    assert basis.provenance["strategy"] == "block_greedy"
    assert basis.provenance["requested_strategy"] == "auto"
    assert basis.provenance["block_p"] == 2
    assert any("roof-bound" in r.getMessage() for r in caplog.records)
    ref = _rb_greedy_block_impl(S, tau=TAU, p=2)
    k = int(ref.k)
    _assert_bitwise(basis, ref.Q[:, :k], ref.pivots[:k], ref.errs[:k], k)


def test_auto_blocked_streamed_when_too_big():
    """Too big for the budget AND roof-bound -> blocked-streamed: the
    block_p the model picked reaches the streamed driver."""
    from repro.core.streaming import rb_greedy_streamed

    S = _S(np.complex64)
    basis = build_basis(source=S, tau=TAU, memory_budget_bytes=1024,
                        tile_m=40, cache_bytes=1)
    assert basis.provenance["strategy"] == "streamed"
    assert basis.provenance["block_p"] > 1
    ref = rb_greedy_streamed(S, tau=TAU, tile_m=40,
                             block_p=basis.provenance["block_p"])
    k = int(ref.k)
    _assert_bitwise(basis, ref.Q[:, :k], ref.pivots[:k], ref.errs[:k], k)


def test_auto_roofline_env_overrides(monkeypatch):
    """REPRO_DRAM_BW_GBPS / REPRO_PEAK_GFLOPS / REPRO_LLC_BYTES feed the
    model; spec fields win over the env (and both win over any
    measurement, which pinned knobs skip entirely)."""
    from repro.api.build import machine_roofline

    monkeypatch.setenv("REPRO_DRAM_BW_GBPS", "10")
    monkeypatch.setenv("REPRO_PEAK_GFLOPS", "100")
    monkeypatch.setenv("REPRO_LLC_BYTES", "1000")
    monkeypatch.setenv("REPRO_ROOFLINE_MEASURE", "1")  # pinned knobs win
    assert machine_roofline(None) == (10.0, 100.0, 1000)
    spec = ReductionSpec(source="unused", bandwidth_gbps=5.0)
    assert machine_roofline(spec) == (5.0, 100.0, 1000)


# ------------------------------------- measured roofline (PR 5) ----


def test_roofline_measurement_disabled_by_default_in_tests(monkeypatch):
    """Under REPRO_ROOFLINE_MEASURE=0 (the conftest/CI default) the model
    falls back to the per-platform defaults — no measurement runs, so
    auto decisions stay deterministic on the noisy box."""
    import repro.api.roofline as R
    from repro.api.build import _PLATFORM_ROOFS, machine_roofline

    assert not R.roofline_measurement_enabled()
    monkeypatch.delenv("REPRO_DRAM_BW_GBPS", raising=False)
    monkeypatch.delenv("REPRO_PEAK_GFLOPS", raising=False)
    monkeypatch.delenv("REPRO_LLC_BYTES", raising=False)

    def boom():  # measurement must not even be consulted
        raise AssertionError("measured_roofline called despite opt-out")

    monkeypatch.setattr(R, "measured_roofline", boom)
    monkeypatch.setattr(R, "measured_cache_bytes", boom)
    bw, gf, cache = machine_roofline(None)
    assert (bw, gf, cache) == _PLATFORM_ROOFS["cpu"]


def test_measured_roofline_feeds_model_when_enabled(monkeypatch, caplog):
    """REPRO_ROOFLINE_MEASURE=1 with no pinned knobs: the one-time
    on-device calibration fills bandwidth/FLOPs (positive, finite,
    logged) AND the LLC knob (the PR-9 working-set sweep — stubbed here;
    its own tests exercise the measurement).  Cached per process: the
    second model call must not re-measure."""
    import repro.api.roofline as R
    from repro.api.build import machine_roofline

    monkeypatch.setenv("REPRO_ROOFLINE_MEASURE", "1")
    monkeypatch.delenv("REPRO_DRAM_BW_GBPS", raising=False)
    monkeypatch.delenv("REPRO_PEAK_GFLOPS", raising=False)
    monkeypatch.delenv("REPRO_LLC_BYTES", raising=False)
    monkeypatch.setattr(R, "measured_cache_bytes", lambda: 48 << 20)
    R.measured_roofline.cache_clear()
    with caplog.at_level(logging.INFO, logger="repro.api"):
        bw, gf, cache = machine_roofline(None)
    assert np.isfinite(bw) and bw > 0
    assert np.isfinite(gf) and gf > 0
    assert cache == 48 << 20  # the measured LLC fed the model
    assert any("measured roofline" in r.getMessage()
               for r in caplog.records)
    assert machine_roofline(None) == (bw, gf, cache)  # stable re-read
    info = R.measured_roofline.cache_info()
    assert info.currsize == 1 and info.hits >= 1  # measured exactly once


def test_measured_roofline_failure_not_cached(monkeypatch):
    """Regression (PR-7 bugfix): a transient calibration failure used to
    be lru_cached as the (0.0, 0.0) sentinel, permanently disabling
    measured roofs for the process.  The failure path must NOT be cached
    — the next call retries and a later success IS cached."""
    import repro.api.roofline as R

    R.measured_roofline.cache_clear()
    calls = {"n": 0}
    real_steady = R._steady_min

    def flaky_steady(fn, *a, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected transient calibration failure")
        return real_steady(fn, repeats=1, warmup=0)

    monkeypatch.setattr(R, "_steady_min", flaky_steady)
    try:
        assert R.measured_roofline() == (0.0, 0.0)  # sentinel reported...
        assert R.measured_roofline.cache_info().currsize == 0  # ...UNCACHED
        bw, gf = R.measured_roofline()  # retried -> real measurement
        assert bw > 0 and gf > 0
        assert R.measured_roofline() == (bw, gf)
        info = R.measured_roofline.cache_info()
        assert info.currsize == 1 and info.hits >= 1  # success cached
    finally:
        R.measured_roofline.cache_clear()  # drop the 1-repeat numbers


def test_auto_decision_table_deterministic_without_measurement():
    """CI acceptance: under REPRO_ROOFLINE_MEASURE=0 the auto-strategy
    decision table reproduces the PR-4 classifications from the
    per-platform default roofs — the matrix legs stay deterministic."""
    import os

    from repro.api.build import _auto_strategy

    assert os.environ.get("REPRO_ROOFLINE_MEASURE") == "0"  # conftest
    spec = ReductionSpec(source="unused", strategy="auto")
    # the paper benchmark's roof-bound resident shapes (PR-4 table)
    for dtype in (jnp.float32, jnp.complex64):
        choice, block_p, _k = _auto_strategy(spec, (4096, 16384), dtype)
        assert choice == "block_greedy"
        assert block_p == 8
    # small, cache-resident shape: stepwise resident greedy
    choice, block_p, _k = _auto_strategy(spec, (200, 120), jnp.float32)
    assert choice == "greedy"
    assert block_p == 1
    # explicit block_p is respected, not overridden
    spec_p = ReductionSpec(source="unused", strategy="auto", block_p=3)
    choice, block_p, _k = _auto_strategy(spec_p, (4096, 16384), jnp.float32)
    assert choice == "block_greedy"
    assert block_p == 3


# ------------------------- panel ortho / adaptive block_p (PR 5) ----


def test_front_door_panel_ortho_flag_reaches_driver():
    """panel_ortho=False must route the blocked build through the
    p-sequential ortho path — bit-identical to calling the driver with
    panel=False directly (and distinct plumbing from the default)."""
    S = _S(np.complex64)
    basis = build_basis(source=S, strategy="block_greedy", tau=TAU,
                        block_p=4, panel_ortho=False)
    ref = _rb_greedy_block_impl(S, tau=TAU, p=4, panel=False)
    k = int(ref.k)
    _assert_bitwise(basis, ref.Q[:, :k], ref.pivots[:k], ref.errs[:k], k)


def test_adaptive_block_records_p_trajectory():
    """adaptive_block=True: the live panel width is bounded by the spec's
    block_p, the trajectory lands in the provenance (JSON-serializable),
    and the build still reaches tau."""
    import json

    from repro.core.errors import proj_error_max

    S = _S(np.complex64)
    basis = build_basis(source=S, strategy="block_greedy", tau=TAU,
                        block_p=8, adaptive_block=True)
    traj = basis.provenance["p_trajectory"]
    assert isinstance(traj, list) and traj
    json.dumps(traj)  # provenance must stay JSON-serializable
    assert all(1 <= entry["p"] <= 8 for entry in traj)
    assert traj[0]["p"] == 8  # starts at the spec ceiling
    # the rejection signal actually fired on this family: the width moved
    assert any(entry["p"] < 8 for entry in traj)
    assert float(proj_error_max(S, basis.Q)) < TAU
    # non-adaptive builds carry no trajectory
    plain = build_basis(source=S, strategy="block_greedy", tau=TAU,
                        block_p=8)
    assert "p_trajectory" not in plain.provenance


def test_distributed_block_p_routes_to_blocked_driver():
    """block_p > 1 on a mesh runs the blocked distributed sweep; a
    1-device mesh must reproduce the resident blocked driver."""
    from repro.compat import make_auto_mesh
    from repro.core.block_greedy import _rb_greedy_block_impl

    S = _S(np.complex64)
    basis = build_basis(source=S, strategy="distributed", tau=TAU,
                        mesh=make_auto_mesh((1,), ("cols",)), block_p=2)
    ref = _rb_greedy_block_impl(S, tau=TAU, p=2)
    k = int(ref.k)
    assert basis.k == k
    assert np.array_equal(basis.pivots, np.asarray(ref.pivots[:k]))


# --------------------------------------------------- source coercion ----


def test_same_source_works_across_strategies(tmp_path):
    """Satellite: a .npy path (or provider) is a valid source for EVERY
    strategy, not only the streamed one."""
    from repro.data import ArrayProvider, write_snapshot_npy

    S_host = make_smooth_matrix(dtype=np.complex64)
    path = write_snapshot_npy(tmp_path / "S.npy", S_host)
    S = jnp.asarray(S_host)

    for strategy in ("greedy", "mgs", "pod", "block_greedy", "streamed"):
        from_path = build_basis(source=path, strategy=strategy, tau=TAU)
        from_prov = build_basis(source=ArrayProvider(S), strategy=strategy,
                                tau=TAU)
        from_array = build_basis(source=S, strategy=strategy, tau=TAU)
        for other in (from_prov, from_array):
            assert from_path.k == other.k, strategy
            assert np.array_equal(np.asarray(from_path.Q),
                                  np.asarray(other.Q)), strategy


def test_numpy_source_stays_host_resident_until_tiled():
    """ArrayProvider must not device-place a host matrix at wrap time:
    "auto" probes shape/dtype through as_provider BEFORE deciding, and a
    too-big-for-device source must still be able to pick "streamed"."""
    from repro.data import ArrayProvider, as_provider

    S_host = make_smooth_matrix(dtype=np.complex64)
    prov = as_provider(S_host)
    assert isinstance(prov, ArrayProvider)
    assert isinstance(prov._S, np.ndarray)  # no eager device transfer
    t = prov.tile(0, 7)
    assert isinstance(t, jax.Array)
    assert np.array_equal(np.asarray(t), S_host[:, :7])

    # a raw numpy source streams tile-by-tile and matches the jax source
    a = build_basis(source=S_host, strategy="streamed", tau=TAU, tile_m=40)
    b = build_basis(source=jnp.asarray(S_host), strategy="streamed",
                    tau=TAU, tile_m=40)
    assert np.array_equal(np.asarray(a.Q), np.asarray(b.Q))


def test_legacy_drivers_accept_coerced_sources(tmp_path):
    """rb_greedy / mgs / pod accept what as_provider accepts, directly."""
    from repro.data import write_snapshot_npy

    S_host = make_smooth_matrix(dtype=np.complex64)
    path = write_snapshot_npy(tmp_path / "S.npy", S_host)
    S = jnp.asarray(S_host)

    ref = rb_greedy(S, tau=TAU)
    got = rb_greedy(path, tau=TAU)
    assert np.array_equal(np.asarray(got.Q), np.asarray(ref.Q))

    assert np.array_equal(
        np.asarray(pod(path, tau=TAU).basis),
        np.asarray(pod(S, tau=TAU).basis),
    )
    m_ref = _mgs_pivoted_qr_impl(S, tau=TAU)
    m_got = _mgs_pivoted_qr_impl(path, tau=TAU)
    assert np.array_equal(np.asarray(m_got.Q), np.asarray(m_ref.Q))


# -------------------------------------------------- spec ergonomics ----


def test_spec_and_kwargs_equivalent():
    S = _S()
    spec = ReductionSpec(source=S, strategy="greedy", tau=TAU)
    a = build_basis(spec)
    b = build_basis(source=S, strategy="greedy", tau=TAU)
    c = build_basis(spec, tau=TAU)  # kwargs override path
    assert np.array_equal(np.asarray(a.Q), np.asarray(b.Q))
    assert np.array_equal(np.asarray(a.Q), np.asarray(c.Q))


def test_spec_validation():
    with pytest.raises(ValueError, match="strategy"):
        ReductionSpec(source=np.ones((2, 2)), strategy="nope")
    with pytest.raises(ValueError, match="source"):
        ReductionSpec()
    with pytest.raises(TypeError):
        build_basis(np.ones((2, 2)))  # a bare matrix is not a spec


def test_provenance_fields():
    S = _S()
    basis = build_basis(source=S, strategy="greedy", tau=TAU)
    p = basis.provenance
    assert p["shape"] == [S.shape[0], S.shape[1]]
    assert p["dtype"] == "complex64"
    assert p["tau"] == TAU
    assert p["backend"] in ("xla", "xla_ref", "pallas")
    assert p["wall_time_s"] > 0
    assert p["spec"]["source"]["shape"] == [S.shape[0], S.shape[1]]


# ------------------------------------------------- artifact methods ----


def test_project_reconstruct_and_errors():
    S = _S(np.complex64)
    basis = build_basis(source=S, strategy="greedy", tau=1e-5)
    c = basis.project(S[:, 0])
    assert c.shape == (basis.k,)
    r = basis.reconstruct(S[:, 0])
    assert float(jnp.linalg.norm(r - S[:, 0])) < 1e-3
    errs = basis.per_column_errors(S)
    assert errs.shape == (S.shape[1],)
    assert float(jnp.max(errs)) < 1e-4


# ------------------------------------------------------- save / load ----


@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
@pytest.mark.parametrize("strategy", ["greedy", "streamed"])
def test_save_load_round_trip(tmp_path, dtype, strategy):
    """Satellite: bit-identical Q/R/pivots/errs and working eim()/
    roq_weights() after reload, across dtypes and resident/streamed."""
    S = _S(dtype)
    basis = build_basis(source=S, strategy=strategy, tau=TAU, tile_m=40)
    out = tmp_path / f"{strategy}_{np.dtype(dtype).name}"
    basis.save(out)
    loaded = ReducedBasis.load(out)

    assert loaded.k == basis.k
    assert loaded.Q.dtype == basis.Q.dtype
    assert np.array_equal(np.asarray(loaded.Q), np.asarray(basis.Q))
    assert np.array_equal(loaded.pivots, basis.pivots)
    assert np.array_equal(loaded.errs, basis.errs)
    assert np.array_equal(np.asarray(loaded.R), np.asarray(basis.R))
    assert loaded.provenance["strategy"] == strategy
    assert loaded.provenance["dtype"] == np.dtype(dtype).name

    # the reloaded artifact is immediately servable
    ei = loaded.eim()
    assert ei.nodes.shape == (loaded.k,)
    f0 = S[:, int(loaded.pivots[0])]
    interp = ei.B @ f0[ei.nodes]
    assert float(jnp.linalg.norm(interp - f0)) < 1e-2
    w = jnp.ones((S.shape[0],), jnp.float32)
    omega = loaded.roq_weights(S[:, 0], w)
    assert omega.shape == (loaded.k,)
    # ROQ exactness on a basis-span vector: <d, q_0> via full quadrature
    # equals the ROQ sum at the EIM nodes
    q0 = loaded.Q[:, 0]
    full_ip = jnp.sum(w.astype(q0.dtype) * jnp.conj(S[:, 0]) * q0)
    roq_ip = jnp.sum(omega * q0[ei.nodes])
    assert abs(complex(full_ip - roq_ip)) < 5e-3 * max(
        abs(complex(full_ip)), 1.0)


def test_save_load_without_R(tmp_path):
    S = _S()
    basis = build_basis(source=S, strategy="streamed", tau=TAU, tile_m=40,
                        keep_R=False)
    assert basis.R is None
    basis.save(tmp_path)
    loaded = ReducedBasis.load(tmp_path)
    assert loaded.R is None
    assert np.array_equal(np.asarray(loaded.Q), np.asarray(basis.Q))


def test_resave_into_same_directory_loads_newest(tmp_path):
    """save() numbers past existing steps, so a reused directory always
    reloads the artifact written last (no stale-step shadowing)."""
    S = _S()
    build_basis(source=S, strategy="greedy", tau=1e-2).save(tmp_path)
    newer = build_basis(source=S, strategy="greedy", tau=1e-4)
    newer.save(tmp_path)
    loaded = ReducedBasis.load(tmp_path)
    assert loaded.k == newer.k
    assert np.array_equal(np.asarray(loaded.Q), np.asarray(newer.Q))


def test_load_rejects_future_version(tmp_path):
    S = _S()
    build_basis(source=S, strategy="greedy", tau=TAU).save(tmp_path)
    import numpy as _np

    step = tmp_path / "step_00000000"
    arr = _np.load(step / "artifact_version.npy")
    _np.save(step / "artifact_version.npy", arr + 99)
    # keep the manifest CRC consistent with the bumped leaf
    import json
    import zlib

    man = json.loads((step / "manifest.json").read_text())
    new = _np.load(step / "artifact_version.npy")
    for leaf in man["leaves"]:
        if leaf["name"] == "artifact_version":
            leaf["crc32"] = zlib.crc32(new.tobytes())
    (step / "manifest.json").write_text(json.dumps(man))
    with pytest.raises(ValueError, match="artifact version"):
        ReducedBasis.load(tmp_path)


# ------------------------------------------------ deprecation hygiene ----


def test_legacy_mgs_wrapper_warns_and_matches():
    from repro.core import mgs_pivoted_qr

    S = _S(np.complex128)
    with pytest.warns(DeprecationWarning, match="build_basis"):
        legacy = mgs_pivoted_qr(S, tau=TAU)
    front = build_basis(source=S, strategy="mgs", tau=TAU)
    assert front.k == int(legacy.k)
    assert np.array_equal(front.pivots, np.asarray(legacy.pivots))
    assert np.array_equal(np.asarray(front.Q), np.asarray(legacy.Q))


def test_legacy_block_wrapper_warns_and_matches():
    from repro.core.block_greedy import rb_greedy_block

    S = _S(np.complex64)
    with pytest.warns(DeprecationWarning, match="build_basis"):
        legacy = rb_greedy_block(S, tau=TAU, p=2)
    front = build_basis(source=S, strategy="block_greedy", tau=TAU,
                        block_p=2)
    k = int(legacy.k)
    assert front.k == k
    assert np.array_equal(front.pivots, np.asarray(legacy.pivots[:k]))
    assert np.array_equal(np.asarray(front.Q),
                          np.asarray(legacy.Q[:, :k]))


def test_parity_oracle_and_fast_drivers_do_not_warn():
    """rb_greedy_stepwise is the exempt parity oracle; rb_greedy /
    rb_greedy_streamed / pod are strategy engines, not deprecated."""
    from repro.core import rb_greedy_stepwise

    S = _S(np.complex64)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        rb_greedy_stepwise(S, tau=TAU)
        rb_greedy(S, tau=TAU)
        rb_greedy_streamed(S, tau=TAU, tile_m=40)
        pod(S, tau=TAU)
        build_basis(source=S, tau=TAU)


def test_strategies_tuple_is_exhaustive():
    from repro.api.build import _BUILDERS

    # "auto" resolves to a builder; "batched" delegates to
    # build_basis_set (multi-basis artifact) before builder dispatch.
    assert set(STRATEGIES) == set(_BUILDERS) | {"auto", "batched"}
