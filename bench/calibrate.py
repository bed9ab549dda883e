#!/usr/bin/env python3
"""Readings that the limits in ``bench/cells/<cell>.json`` are set from.

  python3 bench/calibrate.py --workload <cell> --seeds <n> [--seconds <s>]

For each seed, in one process: the cell's own set-up, a short window at
the cell's own load (one build, or ``--seconds`` of traffic), and the
comparison of what the window produced with the plain reference (the
program's readings); then, on the first ``--control-seeds`` seeds, the
reference put in the program's place at one precision below the
configuration's (three bfloat16 passes: the control, which has to fail),
at one pass, and with a planted wrong pivot (build cells: each sweep
takes the runner-up).  Prints one JSON line
per seed, and last the largest program reading and the smallest reading
of each other side, number by number.  Needs the chip; the benchmark's
own runs never run this.
"""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import json  # noqa: E402

# name -> (bfloat16 passes or None for full float32, pivots skipped)
PRECISIONS = {"control": (3, 0), "bf16_1pass": (1, 0),
              "wrong_pivot": (None, 1)}


def build_readings(cell, precisions) -> dict:
    import numpy as np

    from bench.reference.greedy import certificate, plain_greedy

    out = {"program": cell.compare()}
    S_host = np.asarray(cell.S)   # copied from the device once
    p = cell.traffic["block_p"]
    for name, (passes, skip) in precisions.items():
        res = plain_greedy(cell.S, cell.config["max_k"], p, passes, skip)
        out[name] = certificate(S_host, *res, p=p)
    return out


def serve_readings(cell, precisions) -> dict:
    from bench.reference.serving import control_answers, serve_error

    out = {"program": cell.compare()}
    idx = sorted(cell.results)
    F = cell.at_nodes[cell.col[idx]]
    exact_coef = cell.coef[:, cell.col[idx]]
    for name, (passes, skip) in precisions.items():
        if skip:
            continue
        ans = control_answers(cell.Q64, cell.nodes, F, passes)
        out[name] = {"serve_err": serve_error(cell.Q64, exact_coef, ans)}
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 101)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="seeds on which the reference also runs in the "
                         "program's place (the first ones)")
    ap.add_argument("--precisions", nargs="+", default=list(PRECISIONS),
                    choices=list(PRECISIONS))
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse off the chip (small files from --root)")
    ap.add_argument("--root", default=ROOT)
    args = ap.parse_args(argv)

    from bench import common, harness

    common.use_compile_cache()
    _, cell_entry, config, traffic, _ = common.cell_files(args.workload,
                                                          args.root)
    devices = common.device_info(cell_entry["chips"], not args.cpu)
    cls = harness.cell_class(traffic["kind"])
    reader = build_readings if traffic["kind"] == "build" \
        else serve_readings
    worst = {}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        cell = cls(config, traffic, seed, devices)
        t1 = time.perf_counter()
        cell.window(args.seconds)
        t2 = time.perf_counter()
        precisions = {p: PRECISIONS[p] for p in args.precisions} \
            if i < args.control_seeds else {}
        readings = reader(cell, precisions)
        t3 = time.perf_counter()
        del cell
        print(json.dumps({"seed": seed, "setup_s": t1 - t0,
                          "window_s": t2 - t1, "compare_s": t3 - t2,
                          **readings}), flush=True)
        for side, nums in readings.items():
            for name, v in nums.items():
                agg = max if side == "program" else min
                key = (side, name)
                worst[key] = v if key not in worst else agg(worst[key], v)
    summary = {"workload": args.workload, "seeds": args.seeds}
    for side in ["program"] + args.precisions:
        key = side + ("_max" if side == "program" else "_min")
        summary[key] = {n: v for (s, n), v in worst.items() if s == side}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
