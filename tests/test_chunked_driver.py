"""Chunked device-resident rb_greedy == the seed per-step driver.

The chunked driver runs C iterations inside one jitted lax.while_loop and
syncs only (n_done, stop_code) per chunk; these tests assert it matches
:func:`rb_greedy_stepwise` pivot-for-pivot including the rank-guard drop,
the tau-drop and the refresh path, across chunk sizes and dtypes.
"""

import subprocess
import sys
import os

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import dtype_tol, make_smooth_matrix
from repro.core import rb_greedy, rb_greedy_stepwise


def _assert_same(a, b):
    ka, kb = int(a.k), int(b.k)
    assert ka == kb
    assert np.array_equal(np.asarray(a.pivots), np.asarray(b.pivots))
    # dtype-scaled (eps * sqrt(N)) comparison, not hard-coded ULP
    # constants: both drivers run the same kernels but float reduction
    # order may differ across XLA versions / fusion decisions.
    tol = dtype_tol(np.asarray(a.Q).dtype, n=a.Q.shape[0], factor=100.0)
    errscale = float(np.max(np.asarray(a.errs))) + 1e-300
    np.testing.assert_allclose(np.asarray(a.errs), np.asarray(b.errs),
                               rtol=tol, atol=tol * errscale)
    np.testing.assert_allclose(np.asarray(a.Q), np.asarray(b.Q),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(a.rnorms), np.asarray(b.rnorms),
                               rtol=tol, atol=tol * errscale)


@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
@pytest.mark.parametrize("chunk", [1, 3, 16, 64])
@pytest.mark.parametrize("tau", [1e-4, 1e-8])
def test_matches_stepwise(dtype, chunk, tau):
    S = jnp.asarray(make_smooth_matrix(dtype=dtype))
    _assert_same(rb_greedy_stepwise(S, tau=tau),
                 rb_greedy(S, tau=tau, chunk=chunk))


@pytest.mark.parametrize("chunk", [1, 5, 16])
def test_tau_drop_edge(chunk):
    """tau hit mid-chunk: the below-tau basis is dropped exactly like the
    seed driver (k, zeroed Q column/R row, pivot = -1)."""
    S = jnp.asarray(make_smooth_matrix(dtype=np.float64))
    a = rb_greedy_stepwise(S, tau=1e-6)
    b = rb_greedy(S, tau=1e-6, chunk=chunk)
    _assert_same(a, b)
    k = int(b.k)
    assert int(b.pivots[k]) == -1  # dropped slot marker
    assert float(jnp.linalg.norm(b.Q[:, k])) == 0.0


@pytest.mark.parametrize("chunk", [1, 4, 32])
def test_rank_guard_edge(chunk):
    """Exactly-low-rank snapshots: the junk pivot is dropped, not added."""
    rng = np.random.default_rng(0)
    A = rng.standard_normal((50, 8)) @ rng.standard_normal((8, 30))
    S = jnp.asarray(A)
    a = rb_greedy_stepwise(S, tau=1e-18)
    b = rb_greedy(S, tau=1e-18, chunk=chunk)
    _assert_same(a, b)
    assert int(b.k) <= 9  # stopped at numerical rank, no junk directions


@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
@pytest.mark.parametrize("chunk", [2, 16])
def test_refresh_path(dtype, chunk):
    """Deep tolerance exercises the refresh stop-code round trip."""
    S = jnp.asarray(make_smooth_matrix(dtype=dtype))
    a = rb_greedy_stepwise(S, tau=1e-12)
    b = rb_greedy(S, tau=1e-12, chunk=chunk)
    _assert_same(a, b)
    from repro.core.errors import proj_error_max
    assert float(proj_error_max(S, b.Q[:, :int(b.k)])) < 1e-11


def test_refresh_never_matches(chunk=7):
    S = jnp.asarray(make_smooth_matrix(dtype=np.float64))
    _assert_same(rb_greedy_stepwise(S, tau=1e-8, refresh="never"),
                 rb_greedy(S, tau=1e-8, chunk=chunk, refresh="never"))


def test_callback_per_chunk():
    S = jnp.asarray(make_smooth_matrix(dtype=np.float64))
    ref = rb_greedy_stepwise(S, tau=1e-8)
    k = int(ref.k)

    seen = []
    rb_greedy(S, tau=1e-8, chunk=4, callback=lambda s: seen.append(int(s.k)))
    # once per chunk, strictly increasing, history arrays complete at each
    assert seen == sorted(seen)
    assert len(seen) <= -(-(k + 1) // 4) + 2
    # chunk=1 restores the seed per-iteration cadence
    seen1 = []
    rb_greedy(S, tau=1e-8, chunk=1, callback=lambda s: seen1.append(int(s.k)))
    assert seen1 == list(range(1, seen1[-1] + 1))
    assert len(seen1) == k + 1  # k accepted + the dropped below-tau step


def test_callback_history_is_complete():
    """The per-chunk state carries the full per-step history (errs,
    pivots, rnorms) — what the seed driver exposed per iteration."""
    S = jnp.asarray(make_smooth_matrix(dtype=np.float64))
    hist = {}

    def cb(state):
        k = int(state.k)
        hist[k] = (np.asarray(state.errs[:k]).copy(),
                   np.asarray(state.pivots[:k]).copy())

    res = rb_greedy(S, tau=1e-8, chunk=8, callback=cb)
    k = int(res.k)
    last = hist[max(hist)]
    ref = rb_greedy_stepwise(S, tau=1e-8)
    np.testing.assert_allclose(last[0][:k], np.asarray(ref.errs[:k]))
    assert np.array_equal(last[1][:k], np.asarray(ref.pivots[:k]))


def test_invalid_chunk_rejected():
    S = jnp.asarray(make_smooth_matrix(dtype=np.float64))
    with pytest.raises(ValueError, match="chunk"):
        rb_greedy(S, tau=1e-4, chunk=0)


_DIST_SCRIPT = r"""
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, numpy as np, json
from jax.sharding import Mesh
from repro.core import distributed, rb_greedy_stepwise
from repro.core.distributed import distributed_greedy

x = np.linspace(0, 1, 200)
nu = np.linspace(0.5, 2.0, 120)
S = np.stack([np.sin(2*np.pi*v*x)*np.exp(-v*x) for v in nu], axis=1)
S = S * np.exp(1j*np.outer(x, nu))

ser = rb_greedy_stepwise(jnp.asarray(S), tau=1e-5)
k = int(ser.k)
# exact residual^2 of every column after j serial bases, in NumPy
C = np.asarray(ser.Q[:, :k]).conj().T @ S
norms = np.sum(np.abs(S) ** 2, axis=0)
res2 = norms - np.concatenate([np.zeros((1, S.shape[1])),
                               np.cumsum(np.abs(C) ** 2, axis=0)[:-1]])
mesh = Mesh(np.asarray(jax.devices()), ("cols",))


def run(chunk):
    d = distributed_greedy(jnp.asarray(S), tau=1e-5, max_k=min(*S.shape),
                           mesh=mesh, chunk=chunk)
    kd = int(d.k)
    return {"pivots": np.asarray(d.pivots[:kd]).tolist(),
            "errs": np.asarray(d.errs[:kd]).tolist()}


class Patched:
    def __init__(self, module, **names):
        self._module, self._names = module, names

    def __getattr__(self, name):
        return self._names.get(name, getattr(self._module, name))


out = {"n_devices": len(jax.devices()),
       "serial": {"pivots": np.asarray(ser.pivots[:k]).tolist(),
                  "errs": np.asarray(ser.errs[:k]).tolist()},
       "res2": res2.tolist(), "scale2": float(norms.max()),
       "eps": float(np.finfo(np.float64).eps)}
for chunk in (1, 8):
    out[f"chunk{chunk}"] = run(chunk)
# a planted fault: every argmax of the driver takes the runner-up
distributed.jnp = Patched(jnp, argmax=lambda x, *a, **kw:
                          jax.lax.top_k(x, 2)[1][1])
distributed._make_dist_greedy_chunk.cache_clear()
jax.clear_caches()
out["wrong_pivot"] = run(8)
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def dist_chunk_result():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _DIST_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    import json
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


# err_j^2 is |s|^2 - acc (Eq. 6.3): a difference of two numbers of the
# size of |s|^2_max, so any two summation orders of the same sweep leave
# it a few eps * |s|^2_max apart whatever err_j is, and err_j itself
# eps * |s|^2_max / err_j apart (2.5e-10 at err 5.6e-5 here: the serial
# and the sharded reductions sum in different orders).  Pivots are the
# argmax of those residuals, so they may swap where two residuals^2 lie
# within that much of each other, and the two builds then part ways.
ERR2_TOL_EPS = 100.0


def _departures(r, dist):
    """Where ``dist`` departs from the serial build beyond what floating
    point allows: a different pivot that is not a near-tie, or an err^2
    off by more than the tolerance.  Empty when they agree."""
    ser, res2 = r["serial"], np.asarray(r["res2"])
    tol2 = ERR2_TOL_EPS * r["eps"] * r["scale2"]
    out = []
    if len(dist["pivots"]) != len(ser["pivots"]):
        out.append(("k", len(ser["pivots"]), len(dist["pivots"])))
    for j, (a, b) in enumerate(zip(dist["pivots"], ser["pivots"])):
        if a != b:
            gap = abs(res2[j, a] - res2[j, b])
            if gap > tol2:
                out.append(("pivot", j, a, b, gap))
            break   # past a near-tie the two builds differ by design
        gap2 = abs(dist["errs"][j] ** 2 - ser["errs"][j] ** 2)
        if gap2 > tol2:
            out.append(("err2", j, gap2, tol2))
    return out


@pytest.mark.parametrize("chunk", [1, 8])
def test_distributed_chunked_matches_serial(dist_chunk_result, chunk):
    assert dist_chunk_result["n_devices"] == 4
    assert _departures(dist_chunk_result,
                       dist_chunk_result[f"chunk{chunk}"]) == []


def test_distributed_parity_rejects_a_wrong_pivot(dist_chunk_result):
    bad = _departures(dist_chunk_result, dist_chunk_result["wrong_pivot"])
    assert bad and bad[0][0] == "pivot"
