"""``host_copy_ms`` in the blocked build cell, where it moves
``build_s.blocked``: the same reading (``bench/metrics/host_copy_ms.py``)."""

from bench.harness import load_reader

read = load_reader("host_copy_ms").read
