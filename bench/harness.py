"""Run one cell once: set-up, the measured window, the comparison with the
plain reference, and the result line.

Everything that belongs to one cell is data found by name: the cell's
entry in ``BENCHMARK.json`` names its configuration file and traffic
mix, ``bench/cells/<cell>.json`` holds the limits of its comparison, the
mix's ``kind`` picks the cell class in ``bench/kinds/``, and each per-layer
metric is read by ``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import gc
import glob
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

from bench import common

KINDS = {"build": "bench.kinds.build:BuildCell",
         "serve": "bench.kinds.serve:ServeCell"}


def cell_class(kind: str):
    mod, cls = KINDS[kind].split(":")
    return getattr(importlib.import_module(mod), cls)


def load_reader(name: str):
    """``bench/metrics/<name>.py`` (a metric's name may hold dots)."""
    path = os.path.join(common.BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Context:
    """What a per-layer reader reads: the reduced trace of the window, the
    run's counters, and the cell's configuration, traffic and chips."""

    def __init__(self, trace, counters, config, traffic, chips, peaks):
        self.trace, self.counters = trace, counters
        self.config, self.traffic = config, traffic
        self.chips, self.peaks = chips, peaks


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             root: str = common.ROOT) -> dict:
    bench, cell, config, traffic, limits = common.cell_files(workload, root)
    devices = common.device_info(cell["chips"], require_tpu)
    dev = devices[0]
    clock = common.CompileClock()
    runner = cell_class(traffic["kind"])(config, traffic, seed, devices)
    # Set-up leaves JAX's and the reference's objects behind; frozen, the
    # collector's full passes in the window walk only what the window
    # makes, and do not stall the load generator and the engine by 100 ms.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    compiles_before = clock.compiles

    import jax

    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # no event per Python call
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        rec = runner.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    in_window = clock.compiles - compiles_before
    print(f"setup_s {setup_s!r} (compile {clock.total!r} s); "
          f"{in_window} programs compiled in the window", file=sys.stderr,
          flush=True)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": common.memory_peak_bytes(devices)}

    metrics = {}
    breakdown = None
    if trace:
        from bench import trace as tr
        from bench.peaks import peaks

        paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        reduced = tr.reduce_file(paths[0], window_span="bench.window")
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s
        breakdown = {"device_ops": reduced.top_ops(10),
                     "idle_gaps": reduced.idle_gaps(10)}
        ctx = Context(reduced, rec["counters"], config, traffic,
                      len(devices), peaks(dev.device_kind) if require_tpu
                      else None)
        for m in common.metrics_for(bench, workload, trace=True):
            reader = load_reader(m["name"])
            value = reader.read(ctx)
            if value is None:
                print(f"metric {m['name']}: nothing in the trace matched "
                      f"{getattr(reader, 'MATCH', '')!r}; left out",
                      file=sys.stderr, flush=True)
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(rec["metrics"], setup_s=setup_s)
        for m in common.metrics_for(bench, workload, trace=False):
            # "<base>.<variant>" (a bound of its own for some cells) is
            # the cell's <base> reading
            name = m["name"]
            value = values.get(name, values.get(name.split(".")[0]))
            metrics[name] = {"value": value, "unit": m["unit"]}

    numbers = runner.compare()
    checks = common.judge(numbers, limits)
    correct = common.passed(checks)
    for name in sorted(set(numbers) - set(checks)):
        print(f"info {name}: {numbers[name]!r}", file=sys.stderr)
    if rec["failed"]:
        print(f"{rec['failed']} of {rec['attempted']} attempts failed",
              file=sys.stderr)
    common.print_checks(checks)
    result = {"correct": bool(correct), "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="bench/run.py", description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    common.use_compile_cache()
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start)
    print(json.dumps(result), flush=True)
    return 0
