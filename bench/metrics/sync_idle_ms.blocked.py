"""``sync_idle_ms`` in the blocked build cell, where it moves
``build_s.blocked``: the same reading (``bench/metrics/sync_idle_ms.py``)."""

from bench.harness import load_reader

read = load_reader("sync_idle_ms").read
