"""A run with the timed path broken underneath must come out not correct.

Each test drives a whole run of a cell through the harness, on the CPU at
the small size of ``small.py`` (the look for a chip is skipped), with one
fault planted in the program where the timed path produces its answer,
and checks that ``correct`` is false.  The sound run beside them shows
that the limits pass the unbroken program at this size.

  JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.tests.small import make_root

SEED = 2 ** 31 + 4242
BUILD_CELLS = ("build-greedy-1chip", "build-blocked-1chip")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


def run(root, workload, seconds=1.0):
    jax.clear_caches()   # the broken function must be traced anew
    try:
        return harness.run_cell(workload, SEED, seconds, False,
                                time.perf_counter(), require_tpu=False,
                                root=root)
    finally:
        jax.clear_caches()


def broken_sweeps(monkeypatch, fault):
    """Plant ``fault(c, acc_in, acc_out) -> (c, acc)`` in both sweeps."""
    from repro.core import backend

    pivot_update, block_sweep = backend.pivot_update, backend.block_sweep

    def stepwise(q, S, acc, norms_sq, backend=None):
        c, acc_out, mx, arg = pivot_update(q, S, acc, norms_sq,
                                           backend=backend)
        c, acc_out = fault(c, acc, acc_out)
        return c, acc_out, mx, arg

    def blocked(Qnew, S, acc, backend=None):
        C, acc_out = block_sweep(Qnew, S, acc, backend=backend)
        return fault(C, acc, acc_out)

    monkeypatch.setattr(backend, "pivot_update", stepwise)
    monkeypatch.setattr(backend, "block_sweep", blocked)


def state_unchanged(c, acc, acc_out):
    """The sweep hands back the residuals it was given."""
    return c, acc


def half_the_columns(c, acc, acc_out):
    """Only the first half of the columns is swept; the rest keep their
    old residuals and get no row of R."""
    half = c.shape[-1] // 2
    keep = jnp.arange(c.shape[-1]) < half
    return jnp.where(keep, c, 0), jnp.where(keep, acc_out, acc)


def altered_answer(c, acc, acc_out):
    """One entry of every row of R is off by a part in a thousand."""
    return c.at[..., 0].multiply(1.001), acc_out


class Patched:
    """A module with some of its names replaced, for the code that looks
    them up through it."""

    def __init__(self, module, **names):
        self._module, self._names = module, names

    def __getattr__(self, name):
        return self._names.get(name, getattr(self._module, name))


def wrong_pivot(monkeypatch):
    """Every pivot search passes over the largest residual: the stepwise
    argmax returns the runner-up, the blocked top-p the p after it."""
    from repro.core import block_greedy, greedy

    def runner_up(x, *args, **kwargs):
        return jax.lax.top_k(x, 2)[1][1]

    def next_p(x, k):
        vals, idx = jax.lax.top_k(x, k + 1)
        return vals[1:], idx[1:]

    monkeypatch.setattr(greedy, "jnp", Patched(jnp, argmax=runner_up))
    monkeypatch.setattr(block_greedy, "jax", Patched(
        jax, lax=Patched(jax.lax, top_k=next_p)))


def unnormalised_gs(monkeypatch):
    """Gram-Schmidt hands back the projected vector without dividing it
    by its norm."""
    from repro.core import block_greedy, greedy

    ortho, panel = greedy.imgs_orthogonalize, block_greedy.\
        panel_imgs_orthogonalize

    def stepwise(*args, **kwargs):
        q, coeffs, rnorm, n = ortho(*args, **kwargs)
        return q * rnorm.astype(q.dtype), coeffs, rnorm, n

    def blocked(*args, **kwargs):
        Q, oks, rnorms, n = panel(*args, **kwargs)
        return Q * rnorms[None, :].astype(Q.dtype), oks, rnorms, n

    monkeypatch.setattr(greedy, "imgs_orthogonalize", stepwise)
    monkeypatch.setattr(block_greedy, "panel_imgs_orthogonalize", blocked)


@pytest.mark.parametrize("workload", BUILD_CELLS + ("serve-roq-k80",))
def test_sound_run_is_correct(root, workload):
    assert run(root, workload)["correct"]


@pytest.mark.parametrize("fault", [state_unchanged, half_the_columns,
                                   altered_answer])
@pytest.mark.parametrize("workload", BUILD_CELLS)
def test_broken_build_is_not_correct(root, workload, fault, monkeypatch):
    broken_sweeps(monkeypatch, fault)
    assert not run(root, workload)["correct"]


@pytest.mark.parametrize("fault", [wrong_pivot, unnormalised_gs])
@pytest.mark.parametrize("workload", BUILD_CELLS)
def test_broken_driver_is_not_correct(root, workload, fault, monkeypatch):
    fault(monkeypatch)
    result = run(root, workload)
    assert not result["correct"], result["checks"]


def stale(evaluate):
    """Every batch gets the answers of the batch before it."""
    last = []

    def fault(planes, Fp):
        out = evaluate(planes, Fp)
        prev = last[-1] if last and last[-1].shape == out.shape else None
        last[:] = [out]
        return np.zeros_like(out) if prev is None else prev
    return fault


def half_the_batch(evaluate):
    """Only the first half of each batch is evaluated."""
    def fault(planes, Fp):
        out = evaluate(planes, Fp)
        out[:, (out.shape[1] + 1) // 2:] = 0
        return out
    return fault


def altered_serve(evaluate):
    """One sample of every answer is off by a part in a thousand."""
    def fault(planes, Fp):
        out = evaluate(planes, Fp)
        out[0, :] *= 1.001
        return out
    return fault


@pytest.mark.parametrize("fault", [stale, half_the_batch, altered_serve])
def test_broken_serving_is_not_correct(root, fault, monkeypatch):
    from repro.serving import roq

    monkeypatch.setattr(roq, "_eval_planes", fault(roq._eval_planes))
    result = run(root, "serve-roq-k80")
    assert not result["correct"], result["checks"]


def bf16(x):
    """``x`` rounded to bfloat16 and back, each plane of a complex array
    on its own."""
    x = np.asarray(x)
    if np.iscomplexobj(x):
        return (bf16(x.real) + 1j * bf16(x.imag)).astype(x.dtype)
    return x.astype(jnp.bfloat16).astype(x.dtype)


def basis_in_bf16(monkeypatch):
    """The router keeps its copy of the basis in bfloat16."""
    from repro.serving import router

    load = router.BasisRouter._load

    def fault(self, basis_id):
        src = self._sources[basis_id]
        if not isinstance(src, str):
            self._sources[basis_id] = dataclasses.replace(
                src, Q=jnp.asarray(bf16(src.Q)))
        return load(self, basis_id)

    monkeypatch.setattr(router.BasisRouter, "_load", fault)


def planes_in_bf16(monkeypatch):
    """The interpolant cache commits its planes in bfloat16."""
    from repro.serving import roq

    commit = roq._commit_planes
    monkeypatch.setattr(roq, "_commit_planes", lambda B: tuple(
        jnp.asarray(bf16(p)) for p in commit(B)))


@pytest.mark.parametrize("fault", [basis_in_bf16, planes_in_bf16])
def test_served_copy_in_bf16_is_not_correct(root, fault, monkeypatch):
    fault(monkeypatch)
    result = run(root, "serve-roq-k80")
    assert not result["correct"], result["checks"]
