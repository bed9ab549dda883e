#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip:

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, metrics and configurations are listed in ``BENCHMARK.json`` at the
root of the checkout.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones read from
the device trace of the window), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with the plain
reference beside its limit, which are also the last lines of standard
error.  Exits non-zero, printing no result, where JAX finds no TPU or
fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

if __name__ == "__main__":
    from bench.harness import main

    sys.exit(main(sys.argv[1:], T_START))
