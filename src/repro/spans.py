"""Named host spans at the boundaries of the build and serving layers.

Every span is a ``jax.profiler.TraceAnnotation``: it costs one native call
(about a microsecond) unless a profiler is recording, and when one is, it
lands on the profile's host plane on the same clock as the device's
operations.  So a profile taken around a build or an engine, e.g. with
``jax.profiler.trace(dir)``, names what the host was doing during every
gap in the device's work.  There is no switch: the profiler is the switch.

``SPANS`` lists every name the program writes, with what each covers;
readers of a profile take names from here.
"""

from __future__ import annotations

import functools

import jax

SPANS = (
    # build path
    ("repro.build", "build_basis, whole call; metadata: strategy"),
    ("repro.driver", "one greedy driver call: init, its first host sync "
                     "and the chunk loop"),
    ("repro.driver.chunk", "one pass of a driver's chunk loop: dispatch, "
                           "the host syncs of k and the stop code, stop "
                           "handling and refresh; metadata: k"),
    ("repro.driver.init", "a distributed driver's set-up: S placed on "
                          "the mesh, the state made, the first host sync "
                          "(the largest column norm), the thresholds "
                          "placed"),
    ("repro.driver.refresh", "a distributed driver's exact recomputation "
                             "of the residuals and its host sync"),
    ("repro.driver.compact", "a distributed blocked build's hole columns "
                             "dropped (block_greedy._compact_result): eager, "
                             "syncing on the data-dependent count"),
    ("repro.build.to_host", "copy of the pivots, errs and R to the host "
                            "after a greedy build; a complex R crosses "
                            "as real data, f32 for c64"),
    # serving path
    ("repro.serve.submit", "ROQEngine.submit: breakers, admission, "
                           "enqueue; metadata: req (an ordinal)"),
    ("repro.serve.wait", "the engine's worker waiting for requests or "
                         "for the oldest one's max_wait"),
    ("repro.serve.flush", "one batch flush; metadata: batch, size, "
                          "bucket"),
    ("repro.serve.route", "router lookup of the basis and its dtype"),
    ("repro.serve.stack", "stacking the batch's requests into one matrix"),
    ("repro.serve.eval", "InterpolantCache.evaluate: padding to the "
                         "bucket, input copies, the interpolant's "
                         "dispatch"),
    ("repro.serve.to_host", "copy of the interpolant's result to the "
                            "host, including the wait for the device"),
    ("repro.serve.resolve", "setting the batch's futures; their done "
                            "callbacks run here"),
)
NAMES = frozenset(name for name, _ in SPANS)


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A context manager that records ``name`` (one of ``SPANS``), with
    ``meta`` as its metadata, in a profile being recorded."""
    return jax.profiler.TraceAnnotation(name, **meta)


def traced(name: str):
    """Decorator: every call of the function runs inside ``span(name)``."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return wrapped
    return deco
