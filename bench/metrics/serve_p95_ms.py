"""95th percentile client-side latency of the serving cell, over every
request due in the window, each timed from its scheduled send time; a
request that failed or was refused counts as waiting longer than any
answered one.  Taken on the host's clock by the load generator
(``bench/kinds/serve.py``).  A per-layer metric beside the median
``serve_p50_ms``: the program's own stalls of ~120 ms (PERF.md) set its
spread between runs, too wide for an end-to-end bound."""


def read(ctx):
    return ctx.counters.get("p95_ms")
