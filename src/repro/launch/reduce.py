import os

if os.environ.get("REPRO_DRYRUN"):  # must precede any jax import
    # compile-only on 512 host devices: never take an attached chip
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Distributed greedy-reduction launcher (the paper's production job).

Two modes:

  real   — build the snapshot matrix column-sharded over the current mesh
           (each device generates its own parameter slice, greedycpp-style),
           run distributed RB-greedy with periodic checkpointing, export
           basis/pivots/EI nodes.  The full-size problem is the paper's
           N=10,000 complex64 rows at one chip's column count
           (configs/gw_greedy.py ``one_chip``); ``--small`` is a toy.

  dryrun — REPRO_DRYRUN=1: lower + compile one distributed-greedy step at
           the Blue Waters flagship shape (10,000 x 3,276,800 complex64,
           ~0.5 TB) on the 256- or 512-device production mesh, and report
           memory/cost/collective analysis.  No data is allocated
           (ShapeDtypeStruct stand-ins).

Usage:
  python -m repro.launch.reduce --tau 1e-6 --out basis/      # real (small)
  REPRO_DRYRUN=1 python -m repro.launch.reduce --mesh multi  # flagship
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import enable_compile_cache, make_auto_mesh
from repro.configs.gw_greedy import CONFIG as GW_CONFIG, one_chip, reduced
from repro.core.distributed import (
    DistGreedyState,
    make_dist_greedy_step,
    state_shardings,
)
from repro.launch import roofline as R
from repro.launch.mesh import make_production_mesh


def dryrun(mesh_kind: str, out_dir: str):
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    wl = GW_CONFIG
    n_dev = mesh.size
    # pad columns to divide the device count (the real launcher does the
    # same: greedycpp distributes N/P column blocks)
    M = ((wl.n_cols + n_dev - 1) // n_dev) * n_dev
    N = wl.n_rows
    dt = jnp.complex64

    cols = P(None, tuple(mesh.axis_names))
    s_sds = jax.ShapeDtypeStruct((N, M), dt, sharding=NamedSharding(mesh, cols))
    sh = state_shardings(mesh)
    rdt = jnp.float32

    def sds(shape, dtype, s):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=s)

    state = DistGreedyState(
        Q=sds((N, wl.max_k), dt, sh.Q),
        R=sds((wl.max_k, M), dt, sh.R),
        norms_sq=sds((M,), rdt, sh.norms_sq),
        acc=sds((M,), rdt, sh.acc),
        pivots=sds((wl.max_k,), jnp.int32, sh.pivots),
        errs=sds((wl.max_k,), rdt, sh.errs),
        k=sds((), jnp.int32, sh.k),
    )

    step = make_dist_greedy_step(mesh)
    t0 = time.time()
    lowered = step.lower(s_sds, state)
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mem_rec = {
        a: int(getattr(mem, a))
        for a in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes")
        if mem is not None and hasattr(mem, a)
    }
    terms = R.cost_terms(compiled)
    sec = R.roofline_seconds(terms)
    # useful flops of one iteration: c = q^H S -> 8*N*M/P complex flops
    useful = 8.0 * N * (M / n_dev)
    rec = {
        "workload": wl.name,
        "mesh": mesh_kind,
        "devices": n_dev,
        "shape": [N, M],
        "dtype": str(dt.__name__ if hasattr(dt, "__name__") else dt),
        "compile_s": t_compile,
        "memory": mem_rec,
        "per_device_cost": {k: v for k, v in terms.items()
                            if k != "collective_detail"},
        "collective_detail": terms["collective_detail"],
        "roofline": sec,
        "useful_flops_per_device": useful,
        "useful_flop_ratio": useful / max(terms["flops"], 1.0),
    }
    print(json.dumps(rec, indent=1, default=str))
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(
            out_dir, f"gw_greedy__{mesh_kind}.json"), "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def gw_problem(small: bool):
    """The launcher's workload and its grids: ``(workload, f, m1, m2)``."""
    from repro.gw import chirp_grid, frequency_grid

    wl = reduced() if small else one_chip()
    f = frequency_grid(20.0, 512.0, wl.n_rows)
    m1, m2 = chirp_grid(n_mc=wl.n_cols // 16, n_eta=16)
    return wl, f, m1, m2


def column_sharding() -> NamedSharding:
    """S's layout for the distributed build: columns over every device."""
    mesh = make_auto_mesh((len(jax.devices()),), ("cols",))
    return NamedSharding(mesh, P(None, ("cols",)))


def real_run(tau: float, out: str, small: bool, chunk: int = 16,
             backend: str | None = None, strategy: str = "distributed",
             workdir: str | None = None, resume: bool = False,
             tile_m: int = 4096):
    """Build, save and export one basis; returns the ReducedBasis."""
    from repro.api import ReductionSpec, build_basis
    from repro.checkpoint import save_checkpoint
    from repro.data.providers import WaveformProvider
    from repro.gw import build_snapshot_matrix

    wl, f, m1, m2 = gw_problem(small)

    common = dict(
        tau=wl.tau, max_k=wl.max_k, chunk=chunk, backend=backend,
        workdir=workdir, resume=resume,
    )
    if strategy == "distributed":
        sharding = column_sharding()
        mesh = sharding.mesh
        S = build_snapshot_matrix(f, m1, m2, dtype=jnp.complex64,
                                  sharding=sharding)
        print("S column shards: " + ", ".join(
            f"{s.device.id}:{s.index[1].start}-{s.index[1].stop} "
            f"({s.data.nbytes} B)" for s in S.addressable_shards))
        if workdir is None:
            # Legacy standalone checkpointing; with a workdir the build
            # lifecycle owns its own <workdir>/build/ checkpoints.
            os.makedirs(out, exist_ok=True)
            ckpt_dir = os.path.join(out, "ckpt")
            # The chunked driver invokes the callback once per chunk (k
            # advances by up to `chunk` between calls), so checkpoint on
            # an interval threshold rather than an exact k % 25 == 0 hit.
            last_ckpt = [0]

            def cb(state):
                k = int(state.k)
                if k - last_ckpt[0] >= 25:
                    save_checkpoint(state, ckpt_dir, k)
                    last_ckpt[0] = k

            common["callback"] = cb
        spec = ReductionSpec(source=S, strategy="distributed", mesh=mesh,
                             **common)
    else:
        # Every other strategy reads the snapshot columns through a
        # provider: "streamed" never materializes the matrix (tiles are
        # generated on the fly, greedycpp's generate-your-slice strategy);
        # resident strategies materialize it once on device.
        prov = WaveformProvider(f, m1, m2, dtype=jnp.complex64)
        spec = ReductionSpec(
            source=prov, strategy=strategy, tile_m=tile_m,
            checkpoint_every_tiles=1 if workdir is not None else 0,
            **common)

    t0 = time.time()
    basis = build_basis(spec)
    k = basis.k
    print(f"greedy k={k} in {time.time()-t0:.1f}s; "
          f"final err={float(basis.errs[max(k-1, 0)]):.3e}; "
          f"stop={basis.provenance.get('stop')}")
    os.makedirs(out, exist_ok=True)
    # the durable artifact (Q/R/pivots/errs + provenance; serve with
    # `python -m repro.launch.serve --basis <dir>`): with a workdir the
    # build already finalized it there; otherwise save under out/ ...
    if workdir is None:
        basis.save(os.path.join(out, "basis"))
    # ... plus the legacy flat exports
    np.save(os.path.join(out, "basis.npy"), np.asarray(basis.Q))
    np.save(os.path.join(out, "pivots.npy"), np.asarray(basis.pivots))
    ei = basis.eim()
    np.save(os.path.join(out, "ei_nodes.npy"), np.asarray(ei.nodes))
    print(f"exported ReducedBasis artifact + {k} EI nodes to {out}")
    return basis


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--tau", type=float, default=1e-6)
    ap.add_argument("--out", default="artifacts/reduce")
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--chunk", type=int, default=16,
                    help="greedy iterations per device-resident chunk "
                         "(1 = seed per-iteration cadence)")
    ap.add_argument("--backend",
                    choices=["auto", "xla", "pallas", "xla_ref"],
                    default=None,
                    help="hot-loop primitive backend (default: auto — "
                         "Pallas kernels on TPU, jnp/XLA elsewhere; "
                         "xla_ref = seed reference ops baseline)")
    ap.add_argument("--strategy",
                    choices=["distributed", "streamed", "greedy",
                             "block_greedy", "auto"],
                    default="distributed",
                    help="reduction strategy (streamed generates waveform "
                         "tiles on the fly and never materializes S)")
    ap.add_argument("--workdir", default=None,
                    help="build-lifecycle directory: checkpoints in "
                         "<workdir>/build/, finalized artifact in "
                         "<workdir>; resumable and supervisor-safe")
    ap.add_argument("--resume", action="store_true",
                    help="resume from --workdir checkpoints (or return "
                         "the already-finalized artifact)")
    ap.add_argument("--tile-m", type=int, default=4096,
                    help="streamed tile width in columns")
    args = ap.parse_args()
    enable_compile_cache()
    if os.environ.get("REPRO_DRYRUN"):
        dryrun(args.mesh, args.out)
    else:
        real_run(args.tau, args.out, args.small, chunk=args.chunk,
                 backend=args.backend, strategy=args.strategy,
                 workdir=args.workdir, resume=args.resume,
                 tile_m=args.tile_m)


if __name__ == "__main__":
    main()
