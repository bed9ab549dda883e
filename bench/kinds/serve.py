"""Serving cells: open-loop EIM interpolation requests through
``ROQEngine.submit``, the program's online stage.

The configuration file gives the served basis (a greedy basis of the
build configuration's waveform family, built in set-up from the seed)
and the engine's settings; the traffic file gives the offered rate and
the burst sizes.  A request is a vector in span(Q) known at the basis's
EIM nodes; the reply is its N-sample interpolant, which in exact
arithmetic is the vector itself.  Q is the basis as the build returned
it, copied to the host before the engine takes it, so a copy the engine
keeps at a lower precision, or corrupts, shows in the comparison.
"""

from __future__ import annotations

import functools
import sys
import time

import numpy as np

from bench.common import percentiles
from bench.kinds.build import snapshots
from bench.traffic import open_loop

BASIS_ID = "gw"
# An answer is waited for this long past the window's close before it
# counts as never coming.
DRAIN_S = 60.0


class ServeCell:
    def __init__(self, config, traffic, seed, devices):
        import jax

        from repro.api import ReductionSpec, build_basis
        from repro.serving import ROQEngine

        self.config, self.traffic, self.seed = config, traffic, seed
        S = snapshots(config, seed, devices)
        basis = build_basis(ReductionSpec(
            source=S, strategy="greedy", max_k=config["max_k"],
            tau=config["tau"], chunk=config["chunk"]))
        jax.block_until_ready(basis.Q)
        del S
        self.Q64 = np.asarray(basis.Q).astype(np.complex128)
        dtype = np.asarray(basis.Q).dtype
        self.engine = ROQEngine(
            {BASIS_ID: basis}, max_batch=config["max_batch"],
            max_wait_ms=config["max_wait_ms"],
            queue_depth=config["queue_depth"])
        # every bucket the batcher can fill compiles here, not in the window
        self.engine.warm(BASIS_ID)
        # the nodes are the interpolant's interface: a client samples its
        # waveform there
        _, eim = self.engine.router.get(BASIS_ID)
        self.nodes = np.asarray(eim.nodes)
        rng = np.random.default_rng(seed)
        k, pool = self.Q64.shape[1], traffic["pool"]
        self.coef = (rng.standard_normal((k, pool))
                     + 1j * rng.standard_normal((k, pool)))
        self.at_nodes = np.ascontiguousarray(
            (self.Q64[self.nodes] @ self.coef).T.astype(dtype))
        self.rng = rng

    def window(self, seconds: float) -> dict:
        from repro.serving import QueueFullError

        times, sizes = open_loop.schedule(self.traffic, seconds, self.seed)
        n = int(np.sum(sizes))
        col = self.rng.integers(self.at_nodes.shape[0], size=n)
        sample = set(self.rng.choice(
            n, size=min(n, self.traffic["sample"]), replace=False).tolist())
        due = np.zeros(n)
        done = np.full(n, np.nan)
        self.results = {}
        errors = []
        refused = 0

        def finished(i, fut):
            done[i] = time.perf_counter()
            if fut.exception() is not None:
                errors.append((i, fut.exception()))
            elif i in sample:
                self.results[i] = fut.result()

        def submit(i, t_due):
            nonlocal refused
            due[i] = t_due
            try:
                fut = self.engine.submit(BASIS_ID, self.at_nodes[col[i]])
            except QueueFullError:
                refused += 1
                return
            fut.add_done_callback(functools.partial(finished, i))

        t0, lateness = open_loop.send(times, sizes, submit)
        t_close = t0 + seconds
        deadline = time.perf_counter() + DRAIN_S
        while (np.isnan(done).sum() > refused
               and time.perf_counter() < deadline):
            time.sleep(0.01)
        t_end = time.perf_counter()
        lost = int(np.isnan(done).sum()) - refused
        late = percentiles(lateness * 1e3, (50.0, 99.0))
        print(f"loadgen: {len(times)} bursts, {n} requests over {seconds} s; "
              f"sender lateness p50 {late[50.0]!r} ms, p99 {late[99.0]!r} "
              f"ms, max {float(np.max(lateness)) * 1e3!r} ms",
              file=sys.stderr, flush=True)
        ok = ~np.isnan(done)
        for i, _ in errors:
            ok[i] = False
        # a request that failed, was refused or never came misses every
        # limit: it counts as waiting longer than any answered one did
        lat = np.where(ok, done - due, t_end - float(np.min(due)))
        p = percentiles(lat * 1e3, (50.0, 95.0))
        in_window = int(np.sum(ok & (done <= t_close)))
        self.col, self.lost, self.errors = col, lost, errors
        self.due, self.done = due, done
        return {"attempted": n, "failed": n - int(ok.sum()),
                "metrics": {"serve_p50_ms": p[50.0],
                            "serve_rps": in_window / seconds},
                "counters": {"requests": n, "refused": refused,
                             "p95_ms": p[95.0],
                             "batches": self.engine.stats()["counters"][
                                 "batches"]}}

    def compare(self) -> dict:
        """Every sampled answer against its exact value, Q coef, once the
        engine has stopped."""
        from bench.reference.serving import serve_error

        self.engine.close(drain=True)
        idx = sorted(self.results)
        out = np.stack([self.results[i] for i in idx], axis=1) if idx \
            else np.zeros((self.Q64.shape[0], 0))
        numbers = {"serve_err": serve_error(
            self.Q64, self.coef[:, self.col[idx]], out)}
        numbers["lost"] = self.lost
        numbers["errored"] = len(self.errors)
        return numbers
