"""A checkout-shaped directory holding the benchmark's own files with the
cells cut to a size the CPU runs in seconds, for the tests."""

from __future__ import annotations

import json
import os
import shutil

from bench import common

SMALL = {"n_rows": 256, "n_cols": 2048, "max_k": 24}


def make_root(tmp, rate_rps: float = 400.0) -> str:
    """Copy ``BENCHMARK.json`` and ``bench/{configs,traffic,cells}`` under
    ``tmp`` with every configuration cut to ``SMALL`` (that many columns per
    chip) and every serving mix's rate set low."""
    root = os.fspath(tmp)
    for sub in ("configs", "traffic", "cells"):
        shutil.copytree(os.path.join(common.BENCH, sub),
                        os.path.join(root, "bench", sub))
    shutil.copy(os.path.join(common.ROOT, "BENCHMARK.json"), root)
    cfg_dir = os.path.join(root, "bench", "configs")
    for name in os.listdir(cfg_dir):
        path = os.path.join(cfg_dir, name)
        with open(path) as f:
            cfg = json.load(f)
        cfg.update(SMALL)
        cfg["n_cols"] = SMALL["n_cols"] * cfg.get("chips", 1)
        with open(path, "w") as f:
            json.dump(cfg, f)
    mix_dir = os.path.join(root, "bench", "traffic")
    for name in os.listdir(mix_dir):
        if not name.endswith(".json"):
            continue
        path = os.path.join(mix_dir, name)
        with open(path) as f:
            traffic = json.load(f)
        if traffic["kind"] == "serve":
            traffic["rate_rps"] = rate_rps
            with open(path, "w") as f:
                json.dump(traffic, f)
    return root
