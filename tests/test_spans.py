"""The program's named host spans (``repro.spans``), read back from a real
profile: a build and an engine are run under ``jax.profiler.trace`` at a
small size, and the spans on the profile's host plane are checked for
their nesting, their counts and their names."""

import glob
import math
import os

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
        build_profiles, serve_profile):
    seen = {s[0] for s in serve_profile[0]}
    for spans, _ in build_profiles.values():
        seen |= {s[0] for s in spans}
    assert seen == NAMES
    assert len(NAMES) == len(SPANS)
