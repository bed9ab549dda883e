"""Seeded open-loop burst traffic: the one generator every serving mix uses.

A mix file (``bench/traffic/<mix>.json``) gives the offered rate in
requests per second and the range of burst sizes.  Bursts arrive as a
Poisson process, and a burst's requests are all due at its time; a burst
size of 1 is a plain Poisson stream of single requests.

Every seed gets the same work: the multiset of gaps and burst sizes comes
from the mix's own fixed ``schedule_seed``, scaled so the bursts span the
window exactly, and the run's seed only shuffles their order (and, in the
caller, what each request carries).  So runs on different seeds differ in
order and content, not in load.

The pacing loop is the one ``benchmarks/serving_load.py::
_paced_multibasis`` uses (a running deadline, sleep for the remainder,
never skip a burst); requests are timed from their scheduled time, so a
stalled sender shows as latency, and the sender's own lateness is
returned apart so that a starved generator is not read as a slow server.
"""

from __future__ import annotations

import time

import numpy as np


def schedule(mix: dict, seconds: float, seed: int):
    """``(times, sizes)``: each burst's due time in seconds from the
    window's start (first at 0, all below ``seconds``) and its size."""
    lo, hi = int(mix["burst_min"]), int(mix["burst_max"])
    mean_burst = 0.5 * (lo + hi)
    n = max(1, int(round(mix["rate_rps"] * seconds / mean_burst)))
    base = np.random.default_rng(int(mix["schedule_seed"]))
    gaps = base.exponential(1.0, n)
    sizes = base.integers(lo, hi + 1, n)
    order = np.random.default_rng(seed)
    gaps = gaps[order.permutation(n)]
    sizes = sizes[order.permutation(n)]
    times = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    times *= seconds / float(np.sum(gaps))
    return times, sizes


def send(times, sizes, submit):
    """Offer the bursts on schedule.  ``submit(i, t_due)`` sends request
    ``i`` (numbered across bursts) due at ``t_due`` on the
    ``time.perf_counter`` clock.  Returns ``(t0, lateness)``: the window's
    start and, per burst, how late its first request went out (s)."""
    lateness = np.empty(len(times))
    t0 = time.perf_counter()
    i = 0
    for b, (t, size) in enumerate(zip(times, sizes)):
        due = t0 + float(t)
        lag = due - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        lateness[b] = time.perf_counter() - due
        for _ in range(int(size)):
            submit(i, due)
            i += 1
    return t0, lateness
