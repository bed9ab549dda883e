"""GW waveform model sanity + the paper's n-width-decay premise."""

import jax.numpy as jnp
import numpy as np

from repro.gw import (
    build_snapshot_matrix, chirp_grid, frequency_grid, taylorf2,
)
from repro.gw.grids import mass_grid, random_mass_samples


def test_waveform_normalized_and_finite():
    f = jnp.asarray(frequency_grid(20.0, 512.0, 500))
    h = taylorf2(f, 10.0, 8.0, dtype=jnp.complex128)
    assert np.isfinite(np.asarray(h)).all()
    assert float(jnp.linalg.norm(h)) == 1.0 or abs(
        float(jnp.linalg.norm(h)) - 1.0) < 1e-8


def test_amplitude_powerlaw():
    f = jnp.asarray(frequency_grid(20.0, 512.0, 500))
    h = taylorf2(f, 10.0, 8.0, normalize=False, dtype=jnp.complex128)
    amp = np.abs(np.asarray(h))
    slope = np.polyfit(np.log(np.asarray(f)), np.log(amp), 1)[0]
    assert abs(slope + 7.0 / 6.0) < 1e-6


def test_phase_smoothness_in_parameters():
    """Waveforms converge as the parameter delta shrinks (smoothness in the
    sense the greedy theory needs); absolute deltas are large even for
    small mass changes (many phase cycles), so test CONVERGENCE."""
    f = jnp.asarray(frequency_grid(20.0, 256.0, 400))
    h0 = taylorf2(f, 10.0, 8.0, dtype=jnp.complex128)
    diffs = []
    for d in (1e-2, 1e-3, 1e-4, 1e-5):
        h = taylorf2(f, 10.0 + d, 8.0, dtype=jnp.complex128)
        diffs.append(float(jnp.linalg.norm(h - h0)))
    assert all(a > b for a, b in zip(diffs, diffs[1:]))
    assert diffs[-1] < 1e-2


def test_nwidth_exponential_decay():
    """The paper's premise: smooth families have fast-decaying n-width, so
    the singular values of S decay (near-)exponentially."""
    # a narrow parameter range: the regime where the n-width premise bites
    f = frequency_grid(20.0, 256.0, 400)
    m1, m2 = chirp_grid(mc_min=9.0, mc_max=10.0, n_mc=24, n_eta=6)
    S = build_snapshot_matrix(f, m1, m2, dtype=jnp.complex128)
    sig = np.linalg.svd(np.asarray(S), compute_uv=False)
    sig = sig / sig[0]
    assert sig[60] < 1e-6
    ks = np.arange(5, 40)
    slope = np.polyfit(ks, np.log(np.maximum(sig[5:40], 1e-300)), 1)[0]
    assert slope < -0.1


def test_grids():
    m1, m2 = mass_grid(5.0, 30.0, 10)
    assert (m1 >= m2).all()
    m1, m2 = random_mass_samples(50)
    assert (m1 >= m2).all()
    m1, m2 = chirp_grid(n_mc=8, n_eta=4)
    eta = m1 * m2 / (m1 + m2) ** 2
    assert (eta <= 0.25 + 1e-12).all()


def test_out_of_sample_validation():
    """greedycpp-style validation: basis built on a grid represents
    out-of-sample waveforms to similar accuracy."""
    from repro.core import rb_greedy
    from repro.core.errors import per_column_errors

    f = frequency_grid(20.0, 256.0, 400)
    m1, m2 = chirp_grid(n_mc=24, n_eta=8)
    S = build_snapshot_matrix(f, m1, m2, dtype=jnp.complex128)
    res = rb_greedy(S, tau=1e-6)
    k = int(res.k)

    mv1, mv2 = random_mass_samples(64, 7.0, 25.0, seed=5)
    # keep validation inside the training chirp-mass hull
    V = build_snapshot_matrix(f, mv1, mv2, dtype=jnp.complex128)
    errs = per_column_errors(V, res.Q[:, :k])
    assert float(jnp.median(errs)) < 1e-3


_SHARDED_SCRIPT = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.compat import make_auto_mesh
from repro.gw import build_snapshot_matrix, chirp_grid, frequency_grid
from repro.gw.waveform import taylorf2

f = frequency_grid(20.0, 512.0, 600)
m1, m2 = chirp_grid(n_mc=64, n_eta=16)
mesh = make_auto_mesh((4,), ("cols",))
S = build_snapshot_matrix(f, m1, m2, dtype=jnp.complex64,
                          sharding=NamedSharding(mesh, P(None, ("cols",))))
# the previous construction: columns generated on the host CPU backend in
# 256-column chunks and concatenated there
gen = jax.jit(jax.vmap(lambda a, b: taylorf2(jnp.asarray(f), a, b)))
ref = np.concatenate([np.asarray(gen(m1[lo:lo + 256], m2[lo:lo + 256])).T
                      for lo in range(0, len(m1), 256)], axis=1)
shards = [{"device": s.device.id, "cols": [s.index[1].start, s.index[1].stop],
           "shape": list(s.data.shape),
           "rel_err": float(np.max(np.abs(np.asarray(s.data)
                                          - ref[:, s.index[1]]))
                            / np.max(np.abs(ref)))}
          for s in S.addressable_shards]
print(json.dumps({"shape": list(S.shape), "shards": shards}))
"""


def test_sharded_snapshots_generated_per_device():
    """Each of 4 devices generates and holds only its own M/4 columns, and
    they equal the host-built matrix.

    Tolerance: both sides evaluate the same model on the same backend;
    only the partitioning of the column axis differs, which can at most
    reorder each column's normalisation sum over N rows, i.e.
    ~sqrt(N) ulps of complex64 relative to the largest entry.
    """
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    N, M = out["shape"]
    assert (N, M) == (600, 1024)
    tol = 8 * np.finfo(np.float32).eps * np.sqrt(N)
    shards = sorted(out["shards"], key=lambda s: s["cols"][0])
    assert sorted(s["device"] for s in shards) == [0, 1, 2, 3]
    for d, s in enumerate(shards):
        assert s["cols"] == [d * M // 4, (d + 1) * M // 4]
        assert s["shape"] == [N, M // 4]
        assert s["rel_err"] <= tol, s
