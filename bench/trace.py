"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy and idle time, per-op device time, and the longest idle
gaps labelled by what the host was doing.

Read with ``jax.profiler.ProfileData`` alone.  A device is a plane named
``/device:...``; its operations are the events of its ``XLA Ops`` line
(on a TPU each is named by its HLO text, ``%name.N = shape op(...)``, and
is reduced here to ``name.N``; a ``while`` or ``conditional`` spans the
ops inside it) and its programs those of its ``XLA Modules`` line.  A
Mosaic kernel's op is named after the jitted function that calls
``pallas_call`` (``greedy_update_complex.14``).  Host spans are the
events of the ``/host:CPU`` plane: the benchmark's own
``TraceAnnotation``s and the runtime's.  The measured window is the host
span the harness names (``bench.window``), and everything is clipped to
it; on a TPU v5e the device clock read about 0.7 ms behind the host's,
which moves the window's edges by that much.
"""

from __future__ import annotations

import dataclasses

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# ops that only contain other ops: left out of the breakdown's top ops
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Timeline:
    """Events of one line: names, start and end (ns), clipped to the window."""

    names: list
    start: np.ndarray
    end: np.ndarray

    def total(self, pred) -> float:
        sel = [i for i, n in enumerate(self.names) if pred(n)]
        return float(np.sum(self.end[sel] - self.start[sel])) * 1e-9

    def count(self, pred) -> int:
        return sum(1 for n in self.names if pred(n))


def op_name(text: str) -> str:
    """``name.N`` of an op whose event carries its HLO text."""
    return text.split(" = ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    """``name`` of ``name.N``."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def _line(events, lo, hi, named=None) -> Timeline:
    names, start, end = [], [], []
    for e in events:
        s = e.start_ns
        t = s + e.duration_ns
        if t <= lo or s >= hi:
            continue
        names.append(named(e.name) if named else e.name)
        start.append(max(s, lo))
        end.append(min(t, hi))
    return Timeline(names, np.asarray(start, np.float64),
                    np.asarray(end, np.float64))


def _union(start, end):
    """Disjoint sorted intervals covering the union of [start, end)."""
    if len(start) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    ends = np.append(reach[idx[1:] - 1], reach[-1])
    return s[idx], ends


@dataclasses.dataclass
class Reduced:
    window: tuple            # (start_ns, end_ns) of the measured window
    ops: list                # one Timeline of XLA ops per device
    modules: list            # one Timeline of XLA programs per device
    host: Timeline           # every host span

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def n_devices(self) -> int:
        return len(self.ops)

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        tot = 0.0
        for tl in self.ops:
            s, e = _union(tl.start, tl.end)
            tot += float(np.sum(e - s)) * 1e-9
        return tot / max(self.n_devices, 1)

    def op_time(self, pred) -> float:
        """Device seconds of the ops whose name satisfies ``pred``,
        averaged over devices."""
        return sum(tl.total(pred) for tl in self.ops) / max(
            self.n_devices, 1)

    def op_count(self, pred) -> int:
        return sum(tl.count(pred) for tl in self.ops)

    def module_time(self, pred) -> float:
        return sum(tl.total(pred) for tl in self.modules) / max(
            self.n_devices, 1)

    def module_count(self, pred) -> int:
        """Executions of matching programs, on the first device."""
        return self.modules[0].count(pred) if self.modules else 0

    def top_ops(self, n: int) -> list:
        """The ``n`` ops (by name without its ``.N``) that took most device
        time (seconds, averaged over devices), containers left out."""
        agg = {}
        for tl in self.ops:
            for name, s, e in zip(tl.names, tl.start, tl.end):
                name = base_name(name)
                if name in CONTAINERS:
                    continue
                agg[name] = agg.get(name, 0.0) + (e - s) * 1e-9
        k = max(self.n_devices, 1)
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
        return [[name, t / k] for name, t in top]

    def idle_gaps(self, n: int) -> list:
        """The ``n`` longest idle gaps of the first device, each named by
        the innermost host span that covers its middle."""
        if not self.ops:
            return []
        s, e = _union(self.ops[0].start, self.ops[0].end)
        lo, hi = self.window
        gap_s = np.concatenate([[lo], e])
        gap_e = np.concatenate([s, [hi]])
        length = gap_e - gap_s
        out = []
        for i in np.argsort(-length)[:n]:
            if length[i] <= 0:
                break
            mid = 0.5 * (gap_s[i] + gap_e[i])
            out.append([self.host_label(mid), float(length[i]) * 1e-9])
        return out

    def host_label(self, t_ns: float) -> str:
        h = self.host
        cover = np.flatnonzero((h.start <= t_ns) & (h.end > t_ns))
        if cover.size == 0:
            return "(no host span)"
        inner = cover[np.argmin(h.end[cover] - h.start[cover])]
        return h.names[inner]


def reduce_file(path: str, window_span: str = "bench.window") -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    host_events = []
    for plane in planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                host_events.extend(line.events)
    marks = [e for e in host_events if e.name == window_span]
    if not marks:
        raise ValueError(f"no host span {window_span!r} in {path}")
    lo = marks[0].start_ns
    hi = lo + marks[0].duration_ns
    ops, modules = [], []
    for plane in sorted(planes, key=lambda p: p.name):
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        ops.append(_line(lines[OPS_LINE].events, lo, hi, op_name))
        mod = lines.get(MODULES_LINE)
        modules.append(_line(mod.events if mod else [], lo, hi))
    host = _line(host_events, lo, hi)
    return Reduced((lo, hi), ops, modules, host)
