"""Build cells: back-to-back builds of one basis from a snapshot matrix S
made in set-up, through the program's front door ``build_basis``.

The configuration file gives the matrix (rows, columns, dtype, the
waveform family's parameter ranges) and the build's ``max_k`` and
``tau``; the traffic file gives the strategy, ``block_p`` and ``chunk``.
The seed draws the M parameter points, so no two seeds give the same S.
"""

from __future__ import annotations

import time

import numpy as np


def snapshot_params(config: dict, seed: int):
    """Frequencies and component masses of the M snapshot columns: chirp
    mass and symmetric mass ratio uniform over the configuration's ranges
    (those of ``repro.gw.chirp_grid``), drawn from the seed."""
    rng = np.random.default_rng(seed)
    M = config["n_cols"]
    mc = rng.uniform(*config["mc_range"], M)
    eta = np.minimum(rng.uniform(*config["eta_range"], M), 0.25 - 1e-9)
    total = mc / eta ** 0.6
    disc = np.sqrt(np.maximum(1.0 - 4.0 * eta, 0.0))
    f = np.linspace(config["f_min_hz"], config["f_max_hz"], config["n_rows"])
    return f, 0.5 * total * (1.0 + disc), 0.5 * total * (1.0 - disc)


def column_mesh(devices):
    """A 1-D mesh over the cell's chips; S is column-sharded on it."""
    import jax

    return jax.make_mesh((len(devices),), ("cols",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=devices)


def snapshots(config: dict, seed: int, devices):
    """S on the device(s), generated by the program's waveform model in
    one jitted call (each chip makes its own columns)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.gw import build_snapshot_matrix

    f, m1, m2 = snapshot_params(config, seed)
    sharding = None
    if len(devices) > 1:
        mesh = column_mesh(devices)
        sharding = NamedSharding(mesh, P(None, tuple(mesh.axis_names)))
    S = build_snapshot_matrix(f, m1, m2, dtype=jnp.dtype(config["dtype"]),
                              sharding=sharding)
    S.block_until_ready()
    return S


def build_spec(config: dict, traffic: dict, S, devices):
    from repro.api import ReductionSpec

    mesh = column_mesh(devices) if traffic["strategy"] == "distributed" \
        else None
    return ReductionSpec(
        source=S, strategy=traffic["strategy"], block_p=traffic["block_p"],
        chunk=traffic["chunk"], max_k=config["max_k"], tau=config["tau"],
        mesh=mesh)


class BuildCell:
    """Set-up makes S and runs one build, which compiles every program a
    build runs; ``window`` then times builds back to back."""

    def __init__(self, config, traffic, seed, devices):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.S = snapshots(config, seed, devices)
        self.spec = build_spec(config, traffic, self.S, devices)
        self.build()

    def build(self):
        import jax

        from repro.api import build_basis

        with jax.profiler.TraceAnnotation("api.build"):
            basis = build_basis(self.spec)
            jax.block_until_ready(basis.Q)
        return basis

    def sound(self, basis) -> bool:
        """The exact part of the comparison, made on every build: the
        configuration's tolerance is never reached, so Algorithm 3 runs
        to max_k and stops for no other reason."""
        return (basis.k == self.config["max_k"]
                and basis.provenance.get("stop") == "STOP_NONE")

    def window(self, seconds: float) -> dict:
        """Builds back to back until ``seconds`` have passed; the build in
        flight then is finished and counted.  One build, drawn from the
        seed, is kept for the comparison."""
        rng = np.random.default_rng(self.seed)
        n = failed = 0
        kept = None
        t0 = time.perf_counter()
        while True:
            basis = self.build()
            n += 1
            failed += not self.sound(basis)
            if rng.random() * n < 1.0:   # reservoir of one
                kept = basis
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
        self.kept, self.unsound = kept, failed
        return {"attempted": n, "failed": failed,
                "metrics": {"build_s": (t1 - t0) / n},
                "counters": {"builds": n}}

    def compare(self) -> dict:
        """The numbers compared with the plain reference, for the kept
        build; S stays (it is the input), the program's state is gone."""
        from bench.reference.greedy import certificate

        b = self.kept
        numbers = certificate(self.S, b.Q, b.R, b.pivots, b.errs,
                              p=self.traffic["block_p"])
        numbers["unsound_builds"] = self.unsound
        return numbers
