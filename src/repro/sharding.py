"""Logical-axis sharding rules and mesh-aware constraints.

Logical axes used throughout the model zoo:

  "dp"   — batch / data-parallel        -> mesh ("pod", "data") or ("data",)
  "fsdp" — ZeRO-3 parameter sharding    -> same mesh axes as "dp"
  "tp"   — tensor parallel (heads/ffn/vocab/experts) -> mesh ("model",)
  "sp"   — sequence parallel (residual stream) -> mesh ("model",)

Models call :func:`constrain` with logical names; when no mesh is active the
call is a no-op, so the same code runs in single-device smoke tests and in
the 512-chip dry-run.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def _axes_for(mesh: Mesh, logical: str):
    names = mesh.axis_names
    if logical in ("dp", "fsdp"):
        axes = tuple(a for a in ("pod", "data") if a in names)
        return axes if axes else None
    if logical in ("tp", "sp"):
        return "model" if "model" in names else None
    if logical == "cols":  # distributed-greedy column axis: all axes
        return tuple(names)
    raise ValueError(f"unknown logical axis {logical!r}")


def resolve(mesh: Mesh, *logical: Optional[str]) -> P:
    """PartitionSpec for a tuple of per-dim logical axis names (None = rep)."""
    out = []
    for ax in logical:
        if ax is None:
            out.append(None)
        else:
            out.append(_axes_for(mesh, ax))
    return P(*out)


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Activate a mesh for :func:`constrain` (and nested jit sharding)."""
    prev = getattr(_state, "mesh", None)
    _state.mesh = mesh
    try:
        yield
    finally:
        _state.mesh = prev


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axis names; no-op without mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = resolve(mesh, *logical)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh: Mesh, *logical: Optional[str]) -> NamedSharding:
    return NamedSharding(mesh, resolve(mesh, *logical))


def tree_shardings(mesh: Mesh, logical_tree: Any) -> Any:
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, resolve(mesh, *spec)),
        logical_tree,
        is_leaf=lambda s: isinstance(s, tuple)
        and all(x is None or isinstance(x, str) for x in s),
    )


# ---------------------------------------------------- manual TP micro-kernels
def _dp_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def seq_allgather(x: jax.Array) -> jax.Array:
    """Gather a sequence-sharded activation to full length, explicitly in
    its own (bf16) dtype.

    GSPMD sometimes gathers the f32 pre-cast intermediate of rms_norm
    (convert-hoisting), doubling AG bytes; doing the gather manually via
    shard_map pins both the dtype and the collective (all-gather over
    "model").  x: (B, S, d) sharded (dp, model, None) -> (B, S, d)
    replicated over model.  No-op without an active mesh.
    """
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return x
    dp = _dp_axes(mesh)

    def local(xl):
        return jax.lax.all_gather(xl, "model", axis=1, tiled=True)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=P(dp, "model", None),
        out_specs=P(dp, None, None),
        check_vma=False,
    )(x)


def tp_rs_matmul(h: jax.Array, w: jax.Array) -> jax.Array:
    """y = h @ w with a MANUAL bf16 reduce-scatter over the model axis.

    h: (B, S, f) sharded (dp, None, model); w: (f, d) sharded (model, fsdp).
    Each shard computes its partial product and the partial sums are merged
    with ``psum_scatter`` over "model" onto the sequence dimension — the
    Megatron-LM bf16 RS, which GSPMD's convert-hoisted f32 all-reduce misses
    (EXPERIMENTS.md §Perf it1/it4).  Returns (B, S, d) sharded
    (dp, model, None).  No-op matmul without an active mesh.
    """
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return h @ w
    dp = _dp_axes(mesh)

    def local(hl, wl):
        part = (hl @ wl).astype(h.dtype)  # bf16 partial sums (Megatron)
        return jax.lax.psum_scatter(
            part, "model", scatter_dimension=1, tiled=True
        )

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None, "model"), P("model", None)),
        out_specs=P(dp, "model", None),
        check_vma=False,
    )(h, w)


def tp_ag_matmuls(x: jax.Array, *ws: jax.Array):
    """Fused (sequence all-gather + n projections) in one manual region.

    x: (B, S, d) sharded (dp, model, None); each w: (d, f) sharded
    (fsdp, model).  Returns one (B, S, f) output per w, sharded
    (dp, None, model).  Fusing the gather with the matmuls matters for the
    BACKWARD pass: the input-cotangent partial sums feed the transpose of
    the manual all-gather (a bf16 psum_scatter) directly, instead of being
    merged by GSPMD's f32 all-reduce before reaching it.  Plain matmuls
    without an active mesh.
    """
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return tuple(x @ w for w in ws)
    dp = _dp_axes(mesh)

    def local(xl, *wls):
        xg = jax.lax.all_gather(xl, "model", axis=1, tiled=True)
        return tuple(xg @ wl for wl in wls)

    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, "model", None),) + tuple(
            P(None, "model") for _ in ws),
        out_specs=tuple(P(dp, None, "model") for _ in ws),
        check_vma=False,
    )(x, *ws)
    return out
