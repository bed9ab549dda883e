"""Readings of the program's own host spans in the reduced trace of a
window (``bench/trace.py``): their summed, covered and longest time, the
device's idle time inside them, and each request's wait from its submit
to the start of the flush that serves it.

The program writes these spans with ``jax.profiler.TraceAnnotation``
(its list is ``repro.spans.SPANS``); they sit on the profile's host plane
on the same clock as the device's operations, clipped to the window like
every other host span.  Names are matched letter for letter.  Each
function returns ``None`` where the trace holds no span of the name, as a
program without the span gives.
"""

from __future__ import annotations

import numpy as np

from bench.trace import _union


def intervals(trace, name: str):
    """``(start, end)`` arrays (ns) of the host spans named ``name``."""
    h = trace.host
    sel = [i for i, n in enumerate(h.names) if n == name]
    return h.start[sel], h.end[sel]


def total_s(trace, name: str):
    """Seconds summed over the spans named ``name``."""
    s, e = intervals(trace, name)
    return float(np.sum(e - s)) * 1e-9 if len(s) else None


def longest_s(trace, name: str):
    """Seconds of the longest span named ``name``."""
    s, e = intervals(trace, name)
    return float(np.max(e - s)) * 1e-9 if len(s) else None


def covered_s(trace, name: str):
    """Seconds in which some span named ``name`` is open (their union)."""
    s, e = intervals(trace, name)
    if not len(s):
        return None
    us, ue = _union(s, e)
    return float(np.sum(ue - us)) * 1e-9


def _busy_until(bs, be, t):
    """Device-busy ns before each time in ``t``, for sorted disjoint busy
    intervals ``[bs, be)``."""
    if not len(bs):
        return np.zeros_like(t)
    cum = np.concatenate([[0.0], np.cumsum(be - bs)])
    i = np.searchsorted(bs, t, side="right")   # intervals begun by t
    j = np.maximum(i - 1, 0)
    part = np.minimum(t - bs[j], be[j] - bs[j])
    return np.where(i > 0, cum[j] + part, 0.0)


def idle_inside_s(trace, name: str):
    """Seconds inside the spans named ``name`` (their union) in which no
    operation ran on the first device."""
    s, e = intervals(trace, name)
    if not len(s) or not trace.n_devices:
        return None
    us, ue = _union(s, e)
    ops = trace.ops[0]
    bs, be = _union(ops.start, ops.end)
    busy = _busy_until(bs, be, ue) - _busy_until(bs, be, us)
    return float(np.sum((ue - us) - busy)) * 1e-9


def queue_waits_s(trace, submit: str, flush: str):
    """Each request's wait (s): from the start of its ``submit`` span to
    the start of the first ``flush`` span that begins after the submit
    ends.  Requests with no such flush in the window are left out."""
    ss, se = intervals(trace, submit)
    fs, _ = intervals(trace, flush)
    if not len(ss) or not len(fs):
        return None
    fs = np.sort(fs)
    i = np.searchsorted(fs, se, side="left")
    ok = i < len(fs)
    if not ok.any():
        return None
    return (fs[i[ok]] - ss[ok]) * 1e-9
