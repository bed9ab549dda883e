"""Shared pieces of the harness: files, the device, the compile cache,
the clocks and the result line."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
# A fixed path inside the checkout: the directory is part of the cache's
# key, so only a path that never moves lets a later run find the programs
# the first one compiled.  It is git-ignored.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def use_compile_cache() -> None:
    """Point JAX's persistent compilation cache (and the program's, which
    reads the same variable) at ``CACHE_DIR``.  Call before JAX starts."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # cache every program, however quick to compile, so the second run of
    # a cell compiles nothing at all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_files(workload: str, root: str = ROOT):
    """``(cell, config, traffic, limits)`` of one ``BENCHMARK.json``
    workload, each read from the file its name points to."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(root, cfg_entry["file"])
    traffic = load_json(root, "bench", "traffic", cell["traffic"] + ".json")
    limits = load_json(root, "bench", "cells", workload + ".json")
    return bench, cell, config, traffic, limits


def metrics_for(bench: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports: its end-to-end metrics, or
    with ``trace`` its per-layer ones."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in bench[key]
            if workload in m.get("workloads", [workload])]


def device_info(chips: int, require_tpu: bool = True):
    """The devices of this run; exits non-zero, with no result, where
    there is no TPU or fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          file=sys.stderr, flush=True)
    if require_tpu and dev.platform != "tpu":
        print(f"no TPU: JAX sees {dev.platform}; the benchmark measures "
              f"only on the chip", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"the cell needs {chips} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips]


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest chip, as the runtime reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and how many
    programs it compiled (from its own monitoring events), so that a
    compile inside the measured window shows.  Copied from
    ``chip_smoke.py``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.total = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration
        if event == self.EVENTS[2]:
            self.compiles += 1


def percentiles(samples, qs=(50.0, 95.0, 99.0)) -> dict:
    """Percentiles by sorted linear interpolation (numpy's default
    method).  Copied from ``repro.timing.percentiles`` so that the
    yardstick does not move with the program."""
    xs = sorted(float(x) for x in samples)
    if not xs:
        raise ValueError("percentiles() of empty sample set")
    out = {}
    n = len(xs)
    for q in qs:
        fq = float(q)
        if not 0.0 <= fq <= 100.0:
            raise ValueError(f"percentile rank {q!r} outside [0, 100]")
        pos = (fq / 100.0) * (n - 1)
        lo = int(pos)
        hi = min(lo + 1, n - 1)
        frac = pos - lo
        out[q] = xs[lo] + (xs[hi] - xs[lo]) * frac
    return out


def judge(numbers: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}`` for every number that has a limit;
    a number passes when it is at most its limit."""
    return {name: {"value": numbers[name], "limit": limits[name]["limit"]}
            for name in limits}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def print_checks(checks: dict) -> None:
    """Each number compared, beside its limit: the last lines on stderr."""
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)
