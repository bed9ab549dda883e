"""Mean milliseconds a request waits in serving.roq from the start of its
``repro.serve.submit`` span to the start of the first
``repro.serve.flush`` span that begins after the submit ends.

Exact for one basis with batches under ``max_batch``, where the worker
flushes every pending request at once: then a request's flush is the
first to begin after it was enqueued.  A request enqueued between the
worker's drain of the queue and the flush that follows is counted one
batch early, to that flush, and its wait reads short."""

import numpy as np

from bench import spans

MATCH = ("repro.serve.submit", "repro.serve.flush")


def read(ctx):
    w = spans.queue_waits_s(ctx.trace, *MATCH)
    if w is None:
        return None
    return 1e3 * float(np.mean(w))
