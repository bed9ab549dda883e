"""R reaches the host from ``build_basis`` exactly as ``np.asarray`` of the
driver's own R would bring it: the same dtype, shape, values and flags.

A complex device R crosses as interleaved real data and is viewed back as
complex on the host (``api/build.py::_r_to_host``); a real device R is
copied as it is; the streamed driver's host R passes through.  Each case
captures the driver's result inside ``build_basis`` and compares the
basis's R with ``np.asarray(res.R[:k])`` of that same result.  The
column-sharded case runs on four CPU devices in a child process, and also
reads the HLO of the interleave on the sharded R: it keeps the column
sharding and gathers nothing onto one device.
"""

import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.api import build_basis
from tests.conftest import make_smooth_matrix

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, M, K = 64, 96, 12

# strategy -> (module, driver name that build_basis calls, spec kwargs)
CASES = {
    "greedy-c64": ("repro.core.greedy", "rb_greedy", np.complex64,
                   dict(strategy="greedy")),
    "block_greedy-c64": ("repro.core.block_greedy", "_rb_greedy_block_impl",
                         np.complex64, dict(strategy="block_greedy",
                                            block_p=4)),
    "greedy-f32": ("repro.core.greedy", "rb_greedy", np.float32,
                   dict(strategy="greedy")),
    "streamed-c64": ("repro.core.streaming", "rb_greedy_streamed",
                     np.complex64, dict(strategy="streamed", tile_m=32)),
    "streamed-no-R": ("repro.core.streaming", "rb_greedy_streamed",
                      np.complex64, dict(strategy="streamed", tile_m=32,
                                         keep_R=False)),
}


def _source(dtype):
    S = make_smooth_matrix(N, M, np.complex128)
    S = S * np.exp(1j * np.linspace(0, 3, M))[None, :]
    if not np.issubdtype(dtype, np.complexfloating):
        S = S.real
    return S.astype(dtype)


def _flags(a):
    return (a.dtype, a.shape, a.flags.c_contiguous, a.flags.writeable,
            a.flags.aligned)


@pytest.mark.parametrize("case", list(CASES))
def test_basis_R_is_the_drivers_R(monkeypatch, case):
    module, name, dtype, kwargs = CASES[case]
    mod = importlib.import_module(module)
    driver, seen = getattr(mod, name), []

    def capture(*a, **kw):
        seen.append(driver(*a, **kw))
        return seen[-1]

    monkeypatch.setattr(mod, name, capture)
    basis = build_basis(source=_source(dtype), tau=1e-12, max_k=K,
                        backend="xla", **kwargs)
    (res,) = seen
    k = int(res.k)
    assert basis.k == k > 0
    if res.R is None:
        assert basis.R is None
        return
    want = np.asarray(res.R[:k])
    assert isinstance(basis.R, np.ndarray)
    assert _flags(basis.R) == _flags(want)
    assert want.dtype == dtype
    assert np.array_equal(basis.R.view(np.uint8), want.view(np.uint8))


_SCRIPT = r"""
import json
import jax, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.api import build_basis
from repro.api import build as api_build
from repro.compat import make_auto_mesh
from repro.core import distributed
from tests.conftest import make_smooth_matrix

S = make_smooth_matrix(64, 128, np.complex128)
S = (S * np.exp(1j * np.linspace(0, 3, 128))[None, :]).astype(np.complex64)
mesh = make_auto_mesh((4,), ("cols",))
driver, seen = distributed.distributed_greedy, []
def capture(*a, **kw):
    seen.append(driver(*a, **kw))
    return seen[-1]
distributed.distributed_greedy = capture
basis = build_basis(source=S, strategy="distributed", mesh=mesh, tau=1e-12,
                    max_k=12, backend="xla")
(res,) = seen
k = int(res.k)
want = np.asarray(res.R[:k])
lowered = api_build._interleaved_rows.lower(res.R, k)
hlo = lowered.compile().as_text()
out = api_build._interleaved_rows(res.R, k)
print("RESULT " + json.dumps({
    "devices": len(jax.devices()), "k": k, "basis_k": basis.k,
    "R_spec": str(res.R.sharding.spec),
    "out_spec": str(out.sharding.spec),
    "out_shards": len(out.addressable_shards),
    "out_shard_shape": list(out.addressable_shards[0].data.shape),
    "equal": bool(np.array_equal(basis.R.view(np.uint8), want.view(np.uint8))),
    "same": [str(basis.R.dtype), list(basis.R.shape)] ==
            [str(want.dtype), list(want.shape)],
    "gathers": sum(op in hlo for op in ("all-gather", "all-reduce",
                                         "all-to-all", "collective-permute")),
}), flush=True)
"""


@pytest.fixture(scope="module")
def distributed_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    (line,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def test_distributed_basis_R_is_the_drivers_R(distributed_run):
    r = distributed_run
    assert r["devices"] == 4 and r["basis_k"] == r["k"] > 0
    assert r["equal"] and r["same"]


def test_distributed_interleave_keeps_the_column_sharding(distributed_run):
    r = distributed_run
    assert "cols" in r["R_spec"] and r["out_spec"] == r["R_spec"]
    assert r["out_shards"] == 4
    assert r["out_shard_shape"] == [r["k"], 2 * 128 // 4]
    assert r["gathers"] == 0
