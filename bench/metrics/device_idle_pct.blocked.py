"""``device_idle_pct.build`` in the blocked build cell, where it moves ``build_s.blocked``:
the same reading (``bench/metrics/device_idle_pct.build.py``)."""

from bench.harness import load_reader

read = load_reader("device_idle_pct.build").read
