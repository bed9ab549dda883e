#!/usr/bin/env python3
"""Smoke run of the reduce-then-serve path on a TPU.

One process drives the production launchers' own functions at the paper's
widths: N=10,000 complex64 rows, max_k=100, tau=1e-12, with the column
count cut to one chip (``configs/gw_greedy.py::one_chip``, M=32,768).

  python chip_smoke.py              # one chip (the default)
  python chip_smoke.py --chips 4    # distributed build over 4 chips
  JAX_PLATFORMS=cpu python chip_smoke.py --small   # CPU rehearsal

One chip: ``launch/reduce.py::real_run`` builds, saves and exports the
greedy basis (Pallas backend), then each phase prints one line and checks
it: build, backend (the compiled sweep is a Mosaic kernel, not interpret
mode), orthogonality, a float64 residual check on sampled columns, pivot
parity against an ``xla_ref`` build of the same matrix, and serving through
``launch/serve.py::serve_basis``.  ``--chips 4`` runs only the distributed
build over a 4-device column mesh and the resident build it is compared
with.  Any failed check exits non-zero.  The last line is a JSON verdict,
printed only on a TPU: ``--small`` on another platform runs the same
phases at a toy size and still exits non-zero.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
OUT = os.path.join(ROOT, "artifacts", "chip_smoke")

U32 = 2.0 ** -24  # float32 unit roundoff

# Iterated Gram-Schmidt (kappa=2) keeps |Q^H Q - I| at the rounding of one
# N-term f32 inner product, ~u*sqrt(N), independent of k; 8x that allows
# for the passes.  A bf16 dot (u=2^-9) would sit ~1e4x higher.
ORTHO_FACTOR = 8.0
# Sampled residuals must not exceed the build's reported error at k beyond
# the rounding of Eq. 6.3: err^2 = |s|^2 - sum|c|^2 accumulates one f32
# rounding of |s|^2 per basis, so err^2 may read up to ~k*u*|s|^2 low.
RESID_FACTOR = 4.0
# Two f32 builds of one S agree on each tracked residual^2 to ~(j+1)*u*|s|^2
# at step j (one rounding per accumulated basis, plus that of |s|^2); a
# pivot choice between columns closer than 4x that is a tie either build
# may break, and their err^2 must agree within it.
TIE_FACTOR = 4.0
# Serving evaluates B @ f at the k nodes in f32: each output sums k
# products (|error| <= k*u*sum|B_ij||f_j|) of a request rounded once
# (<= u*|f|), so |I[f] - f| <= ~(k + 1)*u*Lambda*max|f| with Lambda the
# Lebesgue constant max_i sum_j |B_ij|; 4x for the complex product's four
# real GEMMs.
SERVE_FACTOR = 4.0
SERVE_REQUESTS = 256
SERVE_MAX_BATCH = 64
RESID_SAMPLES = 512


def check(phase: str, ok: bool, detail: str) -> None:
    print(f"{phase}: {'PASS' if ok else 'FAIL'} {detail}", flush=True)
    if not ok:
        raise SystemExit(f"{phase} check failed: {detail}")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (from its own
    monitoring events), so build wall time can be reported apart."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration


def timed_build(clock, label, **kw):
    """One launcher build; prints its phase line and returns the basis."""
    import jax

    from repro.launch.reduce import real_run

    c0, t0 = clock.total, time.perf_counter()
    basis = real_run(None, os.path.join(OUT, label), **kw)
    wall = time.perf_counter() - t0
    compile_s = clock.total - c0
    stats = jax.devices()[0].memory_stats() or {}
    prov = basis.provenance
    print(f"build[{label}]: k={basis.k} stop={prov.get('stop')} "
          f"final_err={float(basis.errs[-1])!r} "
          f"backend={prov.get('backend')} build_s={prov['wall_time_s']!r} "
          f"wall_s={wall!r} compile_s={compile_s!r} "
          f"run_s={wall - compile_s!r} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}",
          flush=True)
    return basis


def sampled_columns(S, seed: int = 0):
    """Host copies of RESID_SAMPLES seeded random columns of S, plus every
    column's |s|^2 as the greedy drivers compute it (f32, to float64)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.greedy import greedy_init

    M = S.shape[1]
    idx = np.sort(np.random.default_rng(seed).choice(
        M, size=min(RESID_SAMPLES, M), replace=False))
    cols = np.asarray(S[:, jnp.asarray(idx)]).astype(np.complex128)
    norms_sq = np.asarray(greedy_init(S, 1).norms_sq, np.float64)
    return idx, cols, norms_sq


def check_orthogonality(basis):
    import numpy as np

    Q = np.asarray(basis.Q).astype(np.complex128)
    defect = float(np.max(np.abs(Q.conj().T @ Q - np.eye(Q.shape[1]))))
    tol = float(ORTHO_FACTOR * U32 * np.sqrt(Q.shape[0]))
    check("orthogonality", defect <= tol,
          f"max|Q^H Q - I|={defect!r} (float64 host) tol={tol!r}")


def check_residuals(basis, cols, norms_sq):
    import numpy as np

    Q = np.asarray(basis.Q).astype(np.complex128)
    res = np.linalg.norm(cols - Q @ (Q.conj().T @ cols), axis=0)
    err_k = float(basis.errs[-1])
    scale_sq = float(np.max(norms_sq))
    allowed = float(np.sqrt(err_k ** 2
                            + RESID_FACTOR * basis.k * U32 * scale_sq))
    check("residual", float(np.max(res)) <= allowed,
          f"max float64 residual of {cols.shape[1]} sampled columns="
          f"{float(np.max(res))!r} median={float(np.median(res))!r} "
          f"vs err at k={basis.k}: {err_k!r} allowed={allowed!r}")


def check_parity(phase, a, b, norms_sq, names):
    """Pivots of builds a and b agree until their first difference, and
    that difference is a tie (the two chosen columns' float64 residuals,
    from a's R rows, closer than the f32 tolerance); errs agree over the
    common prefix."""
    import numpy as np

    pa, pb = np.asarray(a.pivots), np.asarray(b.pivots)
    n = min(len(pa), len(pb))
    diff = np.flatnonzero(pa[:n] != pb[:n])
    d = int(diff[0]) if diff.size else n
    scale_sq = float(np.max(norms_sq))
    tie_tol = lambda j: TIE_FACTOR * (j + 1) * U32 * scale_sq  # noqa: E731
    detail = f"{names[0]} vs {names[1]}: pivots agree for {d} steps " \
             f"(k={a.k} / {b.k})"
    if d < n:
        R = np.asarray(a.R)[:d].astype(np.complex128)
        res_sq = norms_sq[[pa[d], pb[d]]] - np.sum(
            np.abs(R[:, [pa[d], pb[d]]]) ** 2, axis=0)
        gap = float(abs(res_sq[0] - res_sq[1]))
        detail += (f"; step {d} picks {pa[d]} vs {pb[d]}, residual^2 gap "
                   f"{gap!r} vs tie tol {tie_tol(d)!r}")
        tie = gap <= tie_tol(d)
    else:
        tie = a.k == b.k
    e2a = np.asarray(a.errs[:d], np.float64) ** 2
    e2b = np.asarray(b.errs[:d], np.float64) ** 2
    j = np.arange(d)
    worst = float(np.max(np.abs(e2a - e2b) / tie_tol(j))) if d else 0.0
    detail += f"; max |err_a^2 - err_b^2| / tol = {worst!r}"
    check(phase, tie and worst <= 1.0, detail)


def check_serving(basis_dir, k):
    import numpy as np

    from repro.api import ReducedBasis
    from repro.launch.serve import serve_basis

    basis = ReducedBasis.load(basis_dir)
    Q = np.asarray(basis.Q).astype(np.complex128)
    nodes = np.asarray(basis.eim().nodes)
    B = Q @ np.linalg.inv(Q[nodes, :])
    lebesgue = float(np.max(np.sum(np.abs(B), axis=1)))
    stats = serve_basis(basis_dir, max_batch=SERVE_MAX_BATCH,
                        requests=SERVE_REQUESTS, seed=0)
    deaths = stats["counters"]["worker_deaths"]
    tol = SERVE_FACTOR * (k + 1) * U32 * lebesgue * stats["max_ref"]
    lat = stats["latency_ms"] or {}
    check("serving",
          stats["served"] == SERVE_REQUESTS
          and stats["counters"]["completed"] == SERVE_REQUESTS
          and deaths == 0 and stats["max_err"] <= tol,
          f"served={stats['served']}/{SERVE_REQUESTS} "
          f"completed={stats['counters']['completed']} "
          f"worker_deaths={deaths} max_err={stats['max_err']!r} "
          f"(float64 host reference, max|ref|={stats['max_ref']!r}, "
          f"Lebesgue={lebesgue!r}) tol={tol!r} "
          f"p50_ms={lat.get('p50')!r} p99_ms={lat.get('p99')!r}")


def check_backend(basis, on_tpu):
    import jax
    import jax.numpy as jnp

    from repro.core.backend import pivot_update
    from repro.kernels.common import default_interpret

    resolved = basis.provenance["backend"]
    N, M = basis.provenance["shape"]
    sds = jax.ShapeDtypeStruct
    compiled = jax.jit(functools.partial(pivot_update, backend=resolved)).lower(
        sds((N,), jnp.complex64), sds((N, M), jnp.complex64),
        sds((M,), jnp.float32), sds((M,), jnp.float32)).compile()
    mosaic = "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    detail = (f"resolved={resolved} interpret={default_interpret()} "
              f"sweep_has_tpu_custom_call={mosaic} sweep_temp_bytes="
              f"{getattr(mem, 'temp_size_in_bytes', 'n/a')}")
    if on_tpu:
        check("backend", resolved == "pallas" and mosaic, detail)
    else:
        print(f"backend: REHEARSAL {detail}", flush=True)


def one_chip(small, on_tpu, clock):
    from repro.data.providers import WaveformProvider
    from repro.launch.reduce import gw_problem

    # Off the TPU the rehearsal forces the same Pallas kernels (interpret
    # mode); on it the launcher's default resolves to them.
    backend = None if on_tpu else "pallas"
    basis = timed_build(clock, "pallas", small=small, strategy="greedy",
                        backend=backend)
    check_backend(basis, on_tpu)
    check_orthogonality(basis)
    # the launcher's own construction again: the same program on the same
    # device regenerates the matrix the build saw, bit for bit
    _, f, m1, m2 = gw_problem(small)
    S = WaveformProvider(f, m1, m2).materialize()
    _, cols, norms_sq = sampled_columns(S)
    del S
    check_residuals(basis, cols, norms_sq)
    ref = timed_build(clock, "xla_ref", small=small, strategy="greedy",
                      backend="xla_ref")
    check_parity("parity", basis, ref, norms_sq, ("pallas", "xla_ref"))
    check_serving(os.path.join(OUT, "pallas", "basis"), basis.k)


def four_chips(small, on_tpu, clock):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.gw import build_snapshot_matrix
    from repro.launch.reduce import column_sharding, gw_problem

    n_dev = len(jax.devices())
    check("devices", n_dev == 4, f"{n_dev} devices")
    backend = None if on_tpu else "pallas"
    dist = timed_build(clock, "distributed", small=small,
                       strategy="distributed", backend=backend)
    # the launcher's layout of S, rebuilt the way real_run builds it
    _, f, m1, m2 = gw_problem(small)
    S = build_snapshot_matrix(f, m1, m2, dtype=jnp.complex64,
                              sharding=column_sharding())
    N, M = S.shape
    shards = sorted(S.addressable_shards, key=lambda s: s.index[1].start)
    layout = [(s.device.id, s.index[1].start, s.index[1].stop,
               s.data.nbytes) for s in shards]
    want = N * (M // n_dev) * np.dtype(np.complex64).itemsize
    check("shards",
          sorted(d for d, *_ in layout) == list(range(n_dev))
          and all(lo == i * M // n_dev and hi == (i + 1) * M // n_dev
                  and nb == want for i, (_, lo, hi, nb) in enumerate(layout)),
          "device:cols bytes " + ", ".join(
              f"{d}:{lo}-{hi} {nb}" for d, lo, hi, nb in layout)
          + f" (each M/{n_dev}={M // n_dev} columns, {want} B)")
    _, _, norms_sq = sampled_columns(S)
    del S
    resident = timed_build(clock, "resident", small=small, strategy="greedy",
                           backend=backend)
    check_parity("parity", dist, resident, norms_sq,
                 ("distributed", "resident"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--small", action="store_true",
                    help="toy size (configs/gw_greedy.py reduced()) for a "
                         "CPU rehearsal; never reports ok")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: distributed build over a 4-chip mesh and the "
                         "resident build it is compared with, nothing else")
    args = ap.parse_args(argv)

    import jax

    from repro.compat import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.small:
        print(f"chip_smoke: no TPU (JAX sees {dev.platform}); "
              f"use --small for a rehearsal", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    run = four_chips if args.chips == 4 else one_chip
    run(args.small, on_tpu, clock)
    if not on_tpu:
        print(f"chip_smoke: rehearsal on {dev.platform} passed its checks; "
              f"no verdict off a TPU", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
