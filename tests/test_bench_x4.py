"""The four-chip benchmark cell ``build-greedy-x4`` run whole through the
benchmark's harness on four CPU devices, at the small size of
``bench/tests/small.py`` (256 rows, 2,048 columns a device, k=24): the
sound run is correct, and a run with either collective of the
column-sharded build broken is not.

The faults are planted in the child, in the names ``core/distributed.py``
looks up while it traces its chunk:

- ``psum_local``: the pivot column's owner-masked ``psum`` hands back the
  device's own contribution, so the devices that do not own the pivot
  orthogonalize a zero column;
- ``gather_local``: the ``all_gather`` of the (value, index) pairs hands
  back the device's own pair, so the global MAXLOC becomes a local one.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAULTS = ("sound", "psum_local", "gather_local")

_SCRIPT = r"""
import json, sys, time
import jax
from bench import harness
from bench.tests.small import make_root
from repro.core import distributed


class Patched:
    def __init__(self, module, **names):
        self._module, self._names = module, names

    def __getattr__(self, name):
        return self._names.get(name, getattr(self._module, name))


LAX = {"sound": {},
       "psum_local": {"psum": lambda x, axes: x},
       "gather_local": {"all_gather": lambda x, axes, tiled=False: x[None]}}
root = make_root(sys.argv[1])
for fault in sys.argv[2:]:
    distributed.jax = Patched(jax, lax=Patched(jax.lax, **LAX[fault]))
    distributed._make_dist_greedy_chunk.cache_clear()
    jax.clear_caches()   # the broken chunk must be traced anew
    res = harness.run_cell("build-greedy-x4", 2 ** 31 + 4243, 1.0, False,
                           time.perf_counter(), require_tpu=False, root=root)
    print("RESULT " + json.dumps({"fault": fault, "devices": len(jax.devices()),
                                  "correct": res["correct"],
                                  "checks": res["checks"]}), flush=True)
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT,
         str(tmp_path_factory.mktemp("checkout")), *FAULTS],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = [json.loads(ln[len("RESULT "):]) for ln in proc.stdout.splitlines()
           if ln.startswith("RESULT ")]
    return {r["fault"]: r for r in out}


@pytest.mark.parametrize("fault", FAULTS)
def test_x4_cell_is_correct_only_when_sound(runs, fault):
    r = runs[fault]
    assert r["devices"] == 4
    assert r["correct"] == (fault == "sound"), r["checks"]
