"""The program's named host spans (``repro.spans``), read back from a real
profile: a build and an engine are run under ``jax.profiler.trace`` at a
small size, and the spans on the profile's host plane are checked for
their nesting, their counts and their names."""

import glob
import json
import math
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.api import build_basis
from repro.serving import ROQEngine
from repro.spans import NAMES, SPANS

N, M, K, CHUNK = 64, 512, 8, 4
N_REQUESTS = 20


def _matrix(seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((N, M))
            + 1j * rng.standard_normal((N, M))).astype(np.complex64)


def _record(log_dir, fn):
    """Run ``fn`` under the profiler; the program's spans on its host
    plane, as ``(name, start_ns, end_ns, line)``, ordered by start."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        out = fn()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, i))
    spans.sort(key=lambda s: s[1])
    return spans, out


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(inner, outer):
    return (inner[3] == outer[3] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


def _within(spans, name, outer):
    return [s for s in _named(spans, name) if _inside(s, outer)]


@pytest.fixture(scope="module")
def build_profiles(tmp_path_factory):
    S = _matrix()
    out = {}
    for strategy, block_p in (("greedy", 1), ("block_greedy", 2)):
        def run():
            return build_basis(source=S, strategy=strategy, block_p=block_p,
                               max_k=K, chunk=CHUNK, tau=1e-12,
                               backend="xla")
        build_basis(source=S, strategy=strategy, block_p=block_p, max_k=K,
                    chunk=CHUNK, tau=1e-12, backend="xla")   # compile
        out[strategy] = _record(tmp_path_factory.mktemp(strategy), run)
    return out


# A stepwise and a blocked distributed build on four CPU devices, run in a
# child (the device count is fixed when JAX starts), under the profiler.
# The family's residuals fall below the refresh trigger within max_k, so
# both drivers refresh, and the blocked one compacts its hole columns.
_DIST_SCRIPT = r"""
import glob, json, os, sys
import jax, numpy as np
from jax.sharding import Mesh
from repro.api import build_basis

x = np.linspace(0, 1, 64)
nu = np.linspace(0.5, 2.0, 128)
S = (np.stack([np.sin(2 * np.pi * v * x) * np.exp(-v * x) for v in nu], 1)
     * np.exp(1j * np.outer(x, nu))).astype(np.complex64)
mesh = Mesh(np.asarray(jax.devices()), ("cols",))


def run():
    return [build_basis(source=S, strategy="distributed", mesh=mesh,
                        block_p=p, max_k=32, chunk=4, tau=1e-12,
                        backend="xla") for p in (1, 2)]


run()   # compile
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
with jax.profiler.trace(sys.argv[1], profiler_options=opts):
    run()
(path,) = glob.glob(os.path.join(sys.argv[1], "**", "*.xplane.pb"),
                    recursive=True)
spans = []
for plane in jax.profiler.ProfileData.from_file(path).planes:
    if plane.name == "/host:CPU":
        for i, line in enumerate(plane.lines):
            spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns, i)
                      for e in line.events if e.name.startswith("repro.")]
print("SPANS " + json.dumps(sorted(spans, key=lambda s: s[1])))
"""


@pytest.fixture(scope="module")
def dist_profile(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    proc = subprocess.run(
        [sys.executable, "-c", _DIST_SCRIPT,
         str(tmp_path_factory.mktemp("dist"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("SPANS ")][-1]
    return [tuple(s) for s in json.loads(line[len("SPANS "):])]


@pytest.fixture(scope="module")
def serve_profile(tmp_path_factory):
    basis = build_basis(source=_matrix(1), strategy="greedy", max_k=K,
                        tau=1e-12, backend="xla")
    rng = np.random.default_rng(2)
    reqs = (rng.standard_normal((N_REQUESTS, K))
            + 1j * rng.standard_normal((N_REQUESTS, K))).astype(np.complex64)
    engine = ROQEngine({"b": basis}, max_batch=8, max_wait_ms=1.0)
    engine.warm("b")

    def run():
        futs = [engine.submit("b", f) for f in reqs]
        for f in futs:
            f.result(timeout=30)
        engine.close(drain=True)   # the worker's last spans end in here
        return engine.stats()

    return _record(tmp_path_factory.mktemp("serve"), run)


@pytest.mark.parametrize("strategy", ["greedy", "block_greedy"])
def test_build_spans_nest(build_profiles, strategy):
    spans, basis = build_profiles[strategy]
    assert basis.k == K
    (build,) = _named(spans, "repro.build")
    (driver,) = _within(spans, "repro.driver", build)
    chunks = _within(spans, "repro.driver.chunk", driver)
    assert chunks and len(chunks) == len(_named(spans, "repro.driver.chunk"))
    (to_host,) = _within(spans, "repro.build.to_host", build)
    # the copy to the host follows the driver, outside it
    assert to_host[1] >= driver[2]


@pytest.mark.parametrize("blocked", [False, True])
def test_distributed_driver_names_its_init_refresh_and_compaction(
        dist_profile, blocked):
    builds = _named(dist_profile, "repro.build")
    assert len(builds) == 2
    (driver,) = _within(dist_profile, "repro.driver", builds[blocked])
    (init,) = _within(dist_profile, "repro.driver.init", driver)
    chunks = _within(dist_profile, "repro.driver.chunk", driver)
    assert chunks and init[2] <= chunks[0][1]
    (refresh,) = _within(dist_profile, "repro.driver.refresh", driver)
    assert any(_inside(refresh, c) for c in chunks)
    compact = _within(dist_profile, "repro.driver.compact", driver)
    assert len(compact) == blocked
    if blocked:
        assert compact[0][1] >= chunks[-1][2]


def test_stepwise_build_has_one_chunk_span_per_chunk(build_profiles):
    spans, _ = build_profiles["greedy"]
    assert len(_named(spans, "repro.driver.chunk")) == math.ceil(K / CHUNK)


def test_engine_spans_count_requests_and_batches(serve_profile):
    spans, stats = serve_profile
    assert len(_named(spans, "repro.serve.submit")) == N_REQUESTS
    flushes = _named(spans, "repro.serve.flush")
    assert len(flushes) == stats["counters"]["batches"] > 0
    assert _named(spans, "repro.serve.wait")


def test_each_flush_holds_route_stack_eval_and_resolve(serve_profile):
    spans, _ = serve_profile
    for flush in _named(spans, "repro.serve.flush"):
        for name in ("repro.serve.route", "repro.serve.stack",
                     "repro.serve.resolve"):
            assert len(_within(spans, name, flush)) == 1, name
        (ev,) = _within(spans, "repro.serve.eval", flush)
        assert len(_within(spans, "repro.serve.to_host", ev)) == 1


def test_queue_wait_is_reported(serve_profile):
    _, stats = serve_profile
    wait = stats["queue_wait_ms"]
    assert wait["n"] == N_REQUESTS
    assert 0.0 <= wait["p50"] <= wait["p95"]
    assert "throughput_rps" not in stats


def test_every_span_seen_is_listed_and_every_listed_span_is_seen(
        build_profiles, serve_profile, dist_profile):
    seen = {s[0] for s in serve_profile[0] + dist_profile}
    for spans, _ in build_profiles.values():
        seen |= {s[0] for s in spans}
    assert seen == NAMES
    assert len(NAMES) == len(SPANS)
