"""The paper's own production workload (Sec. 6.1.4, Blue Waters).

Column-pivoted QR via RB-greedy on a dense complex snapshot matrix:
N = 10,000 rows x M = 3,276,800 columns (~0.5 TB at complex64), k = 100
basis vectors — the largest matrix the paper reports (32,768 cores).
This config drives the distributed-greedy dry-run + roofline cell;
:func:`one_chip` is the same problem cut to one chip's columns.
"""

import dataclasses


@dataclasses.dataclass(frozen=True)
class GreedyWorkload:
    name: str = "gw-greedy-bluewaters"
    n_rows: int = 10_000
    n_cols: int = 3_276_800
    dtype: str = "complex64"
    max_k: int = 100
    tau: float = 1e-12


CONFIG = GreedyWorkload()


def reduced():
    return GreedyWorkload(
        name="gw-greedy-small", n_rows=256, n_cols=2048, max_k=40, tau=1e-5
    )


def one_chip():
    """The paper's widths on one TPU v5e chip (16 GB): N=10,000 complex64
    rows, max_k=100, tau=1e-12, with the column count cut to M=32,768
    (S is 2.6 GB).  The resident build holds S three times (S, XLA's
    re/im planes, and the sweep's row-padded planes, ~8 GB), so
    M=65,536 needs ~16 GB and does not fit."""
    return GreedyWorkload(name="gw-greedy-one-chip", n_cols=32_768)
