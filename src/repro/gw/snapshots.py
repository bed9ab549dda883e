"""Snapshot-matrix construction (the greedycpp model interface).

greedycpp's strategy (Sec. 6.1.1): "The parameter values that define S are
distributed among the different MPI processes, and each process is
responsible for forming a 'slice' of S over a subset of columns."  The JAX
analogue: parameters are sharded on the column mesh axis and each device
vmaps the model over its local parameter slice — no host round-trip, no file
I/O.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.gw.waveform import taylorf2_batch


def build_snapshot_matrix(
    f: np.ndarray,
    m1s: np.ndarray,
    m2s: np.ndarray,
    dtype=jnp.complex64,
    sharding: NamedSharding | None = None,
) -> jax.Array:
    """Build S (N, M) on the device; optionally column-sharded.

    ``sharding`` must shard the column (second) axis.  The parameter
    vectors are then placed with the matching 1-D sharding and the model
    is jitted with ``out_shardings=sharding``, so each device evaluates
    the waveforms of its own column range: the full matrix never exists
    on one device or on the host (the paper's "may be too large to load
    into memory" setting).
    """
    f = jnp.asarray(f)

    def gen(m1, m2):
        return taylorf2_batch(f, m1, m2, dtype=dtype)

    if sharding is None:
        return jax.jit(gen)(jnp.asarray(m1s), jnp.asarray(m2s))
    params = NamedSharding(sharding.mesh, P(sharding.spec[1]))
    m1, m2 = (jax.device_put(np.asarray(m), params) for m in (m1s, m2s))
    return jax.jit(gen, out_shardings=sharding)(m1, m2)
