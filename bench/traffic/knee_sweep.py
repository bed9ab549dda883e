#!/usr/bin/env python3
"""One-off sweep for the highest request rate the serving cell sustains.

  python3 bench/traffic/knee_sweep.py --workload serve-roq-k80 \
      --rates 1000 1500 2000 ... --seconds 20

One process sets the cell up once and offers each rate in turn, from the
lowest, through the cell's own generator and engine.  A rate is
sustained when at least 99% of its requests are answered by the window's
close and the backlog does not grow: the median latency of the last
quarter of the window stays within 1.25x that of the first quarter.  The
knee is the highest rate below the first one that is not sustained, so a
rate that passes above a failure does not count; the sweep stops after
two failures in a row.  The cell's mix then offers 0.8 of the knee; the
sweep's output is recorded in PERF.md.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import gc  # noqa: E402
import json  # noqa: E402

import numpy as np  # noqa: E402


def sustained(cell, seconds: float) -> dict:
    due, done = cell.due, cell.done
    t0 = float(np.min(due))
    lat = done - due
    ok = ~np.isnan(lat)
    rel = due - t0
    first = ok & (rel < 0.25 * seconds)
    last = ok & (rel >= 0.75 * seconds)
    trend = float(np.median(lat[last]) / np.median(lat[first]))
    answered = float(np.mean(ok & (done <= t0 + seconds)))
    p = np.percentile(lat[ok], [50, 95]) * 1e3 if ok.any() else [np.nan] * 2
    return {"answered_by_close": answered, "latency_trend": trend,
            "p50_ms": float(p[0]), "p95_ms": float(p[1]),
            "sustained": bool(answered >= 0.99 and trend <= 1.25)}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="serve-roq-k80")
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 77)
    args = ap.parse_args(argv)

    from bench import common
    from bench.kinds.serve import ServeCell

    common.use_compile_cache()
    _, cell_entry, config, traffic, _ = common.cell_files(args.workload)
    devices = common.device_info(cell_entry["chips"])
    cell = ServeCell(config, traffic, args.seed, devices)
    gc.collect()
    gc.freeze()   # as the harness does after set-up
    best, failed, misses = None, False, 0
    for rate in sorted(args.rates):
        cell.traffic = dict(traffic, rate_rps=rate)
        rec = cell.window(args.seconds)
        row = {"rate_rps": rate, **rec["metrics"], **sustained(cell,
                                                               args.seconds)}
        print(json.dumps(row), flush=True)
        misses = 0 if row["sustained"] else misses + 1
        failed = failed or not row["sustained"]
        if not failed:
            best = rate
        if misses == 2:
            break
    cell.engine.close(drain=True)
    print(json.dumps({"knee_rps": best,
                      "offer_rps": None if best is None else 0.8 * best}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
