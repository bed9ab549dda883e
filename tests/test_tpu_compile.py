"""The main-path Pallas kernels compile for a TPU v5e at production widths.

Interpret-mode tests (tests/test_kernels.py) check the kernels' numbers but
not what the TPU compiler accepts: block shapes that break the (8, 128)
tiling rule, or more VMEM than a kernel may use, pass there and fail on the
chip.  Here each kernel entry point is lowered with ``interpret=False`` for
one chip of a *described* ``v5e:2x2`` topology and compiled by the installed
TPU compiler; nothing runs, so no chip is needed.

Widths are the paper's N=10,000 rows padded to the sweeps' row tile
(10,240) and M=65,536 columns, twice the one-chip build's
(:func:`repro.configs.gw_greedy.one_chip`).  The stepwise sweep also
compiles at the unpadded N=10,000, as its wrapper now calls it: its ragged
last row tile is masked in the kernel, and no S-sized pad is left in the
wrapper's program.  The GS kernels see k padded to one lane tile (128), and
the complex build's real embedding (2N x 2k).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and under pytest-xdist every
worker imports this file.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.block_sweep.kernel import (
    block_sweep_complex,
    block_sweep_real,
)
from repro.kernels.greedy_update import ops as greedy_update_ops
from repro.kernels.greedy_update.kernel import (
    greedy_update_complex,
    greedy_update_real,
)
from repro.kernels.imgs_panel.kernel import imgs_panel_real
from repro.kernels.imgs_project.kernel import imgs_project_real

N = 10_000       # the paper's rows
N_PAD = 10_240   # N padded to a multiple of the sweep's row tile
M = 65_536
M_CHIP = 49_152  # the one-chip build's columns
K_PAD = 128      # k <= 100 padded to one lane tile
P_PANEL = 8      # block_p=8, one f32 sublane tile


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_config():
    """Compile as the program runs on the chip: 32-bit JAX (the test suite
    turns x64 on, which makes Pallas index maps int64), and no persistent
    compilation cache (a compile for a described chip is written to it but
    cannot be read back without the chip)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = {name: getattr(jax.config, name)
             for name in ("jax_enable_x64", "jax_enable_compilation_cache")}
    for name in saved:
        jax.config.update(name, False)
    cc.reset_cache()
    yield
    for name, value in saved.items():
        jax.config.update(name, value)
    cc.reset_cache()


def _compile(fn, sharding, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_greedy_update_real_compiles(one_chip, chip_config):
    _compile(greedy_update_real, one_chip,
             (1, N_PAD), (N_PAD, M), (1, M), (1, M))


def test_greedy_update_complex_compiles(one_chip, chip_config):
    compiled = _compile(greedy_update_complex, one_chip,
                        (1, N_PAD), (1, N_PAD), (N_PAD, M), (N_PAD, M),
                        (1, M), (1, M))
    # the kernel streams the planes: device memory is the arguments plus
    # the (1, M) outputs, with no S-sized temporary
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4 * N_PAD * M // 8


@pytest.mark.parametrize("kernel,n_planes", [(greedy_update_real, 1),
                                             (greedy_update_complex, 2)],
                         ids=["real", "complex"])
def test_greedy_update_compiles_unpadded(one_chip, chip_config, kernel,
                                         n_planes):
    # q stays padded to the row tile; S and the (1, M) vectors do not
    shapes = ([(1, N_PAD)] * n_planes + [(N, M)] * n_planes
              + [(1, M), (1, M)])
    compiled = _compile(kernel, one_chip, *shapes)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * N * M // 8


@pytest.mark.parametrize("dtype,n_split", [(jnp.float32, 0),
                                           (jnp.complex64, 2)],
                         ids=["real", "complex"])
def test_greedy_update_wrapper_has_no_s_sized_pad(one_chip, chip_config,
                                                 dtype, n_split):
    """The wrapper hands the kernel S at (10,000, 49,152): no pad of S,
    and no S-sized temporary beyond the re/im split of a complex S."""
    def arg(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    fn = jax.jit(functools.partial(greedy_update_ops.greedy_update,
                                   interpret=False))
    compiled = fn.lower(arg((N,), dtype), arg((N, M_CHIP), dtype),
                        arg((M_CHIP,)), arg((M_CHIP,))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    pads = re.findall(r"= \w+\[(\d+),(\d+)\]\S* pad\(", text)
    assert pads, "the padded q should show as a pad"
    assert not [p for p in pads if int(p[0]) >= N], pads
    plane = 4 * N * M_CHIP
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes - n_split * plane < plane


@pytest.mark.parametrize("kernel,n_planes", [(block_sweep_real, 1),
                                             (block_sweep_complex, 2)],
                         ids=["real", "complex"])
def test_block_sweep_compiles(one_chip, chip_config, kernel, n_planes):
    shapes = ([(P_PANEL, N_PAD)] * n_planes + [(N_PAD, M)] * n_planes
              + [(1, M)])
    _compile(kernel, one_chip, *shapes)


@pytest.mark.parametrize("n,k", [(N_PAD, K_PAD), (2 * N_PAD, 2 * K_PAD)],
                         ids=["real", "complex_embedding"])
def test_imgs_project_compiles(one_chip, chip_config, n, k):
    _compile(imgs_project_real, one_chip, (1, n), (n, k),
             nt=1024, kt=k)


@pytest.mark.parametrize("n,k", [(N_PAD, K_PAD), (2 * N_PAD, 2 * K_PAD)],
                         ids=["real", "complex_embedding"])
def test_imgs_panel_compiles(one_chip, chip_config, n, k):
    _compile(imgs_panel_real, one_chip, (P_PANEL, n), (n, k),
             nt=1024, kt=k)
