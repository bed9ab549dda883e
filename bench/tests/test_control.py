"""The control comes out as not correct: the plain reference put in the
program's place, one precision below the configuration's full float32
(three bfloat16 passes; one for the serving cell, see PERF.md), fails
the cell's own limits.  So do the readings that set the upper ends of
the limits three passes do not separate: the reference at one bfloat16
pass, and with each sweep's pivots taken one rank too low.

On the chip, at each cell's own size and on three seeds, this is what
``bench/calibrate.py`` reads (its ``control``, ``bf16_1pass`` and
``wrong_pivot`` readings, in PERF.md).
Here it runs on the CPU at a size a test run holds: the bfloat16 passes
are written out (``bench.reference.greedy.real_dot``), so the CPU computes
the same precision the chip does.

  JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import numpy as np
import pytest

from bench import common
from bench.kinds.build import snapshots
from bench.reference.greedy import certificate, plain_greedy
from bench.reference.serving import control_answers, serve_error

SEEDS = (2 ** 31 + 11, 2 ** 31 + 12, 2 ** 31 + 13)
# The columns of a full-size cell; rows and k are cut so the CPU holds it.
SIZE = {"n_rows": 2048, "n_cols": 4096, "max_k": 32}


def limits(cell):
    return common.load_json(common.BENCH, "cells", cell + ".json")


def config(name):
    return dict(common.load_json(common.BENCH, "configs", name + ".json"),
                **SIZE)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("passes,skip,fails", [
    (3, 0, "r_gap"),            # the control
    (1, 0, "ortho"),
    (None, 1, "pivot_gap"),     # the runner-up taken as each pivot
])
@pytest.mark.parametrize("cell,cfg,p", [
    ("build-greedy-1chip", "gw-bbh-n10k-c64", 1),
    ("build-blocked-1chip", "gw-bbh-n10k-c64", 8),
])
def test_build_control_fails(cell, cfg, p, passes, skip, fails, seed):
    import jax

    cf = config(cfg)
    S = snapshots(cf, seed, jax.devices()[:1])
    numbers = certificate(S, *plain_greedy(S, cf["max_k"], p, passes, skip),
                          p=p)
    numbers["unsound_builds"] = 0
    checks = common.judge(numbers, limits(cell))
    assert checks[fails]["value"] > checks[fails]["limit"], checks


def deim(Q):
    """Empirical interpolation nodes of the columns of Q (DEIM)."""
    nodes = [int(np.argmax(np.abs(Q[:, 0])))]
    for i in range(1, Q.shape[1]):
        c = np.linalg.solve(Q[nodes, :i], Q[nodes, i])
        nodes.append(int(np.argmax(np.abs(Q[:, i] - Q[:, :i] @ c))))
    return np.asarray(nodes)


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_control_fails(seed):
    rng = np.random.default_rng(seed)
    N, k, n = SIZE["n_rows"], 100, 256
    Q64, _ = np.linalg.qr(rng.standard_normal((N, k))
                          + 1j * rng.standard_normal((N, k)))
    nodes = deim(Q64)
    coef = rng.standard_normal((k, n)) + 1j * rng.standard_normal((k, n))
    F = (Q64[nodes] @ coef).T.astype(np.complex64)
    limit = limits("serve-roq-k80")["serve_err"]["limit"]
    # the serving cell's control is one bf16 pass: three read within 1.3x
    # of the program on the chip (PERF.md)
    assert serve_error(Q64, coef, control_answers(Q64, nodes, F, 1)) > limit
    assert serve_error(Q64, coef, control_answers(Q64, nodes, F, None)) \
        <= limit
