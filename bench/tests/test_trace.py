"""The reduction from a profiler trace to busy time, op time and idle gaps.

The arithmetic is checked on a hand-made timeline; the reading of a real
``.xplane.pb`` on a small trace recorded on one TPU v5e and kept in
``bench/tests/data/`` (three calls of the stepwise sweep kernel at
256 x 1,024 in a ``bench.window`` span, each in a ``bench.step`` span,
10 ms apart; the device clock reads about 0.7 ms behind the host's, so
the first call falls just before the window).
"""

from __future__ import annotations

import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def reduced_by_hand():
    # window 0..100 ns; ops overlap at 10..40 and leave 40..60 and 90..100
    # idle (and 0..10)
    ops = [ev("fusion.1", 10, 20), ev("greedy_update_complex.2", 20, 20),
           ev("all-reduce.3", 60, 30), ev("fusion.1", 95, 20),
           ev("while.4", 10, 30)]
    host = [ev("bench.window", 0, 100), ev("api.build", 0, 99.5),
            ev("sync", 38, 25)]
    return trace.Reduced(
        (0, 100), [trace._line(ops, 0, 100)],
        [trace._line([ev("jit_step", 10, 80)], 0, 100)],
        trace._line(host, 0, 100))


def test_busy_is_the_union_of_ops():
    r = reduced_by_hand()
    # busy 10..40, 60..90, 95..100 = 65 ns
    assert r.busy_s() == pytest.approx(65e-9)
    assert r.window_s == pytest.approx(100e-9)


def test_op_time_sums_matching_events_clipped_to_the_window():
    r = reduced_by_hand()
    assert r.op_time(lambda n: "fusion" in n) == pytest.approx(25e-9)
    assert r.op_time(lambda n: "all-reduce" in n) == pytest.approx(30e-9)
    assert r.op_count(lambda n: "greedy_update" in n) == 1
    assert r.module_count(lambda n: "jit_step" in n) == 1
    assert r.op_time(lambda n: "nothing" in n) == 0.0


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    gaps = reduced_by_hand().idle_gaps(10)
    assert [g[1] for g in gaps] == pytest.approx([20e-9, 10e-9, 5e-9])
    assert [g[0] for g in gaps] == ["sync", "api.build", "api.build"]


def test_top_ops_by_device_time_without_containers():
    top = reduced_by_hand().top_ops(3)
    assert top == [["all-reduce", pytest.approx(30e-9)],
                   ["fusion", pytest.approx(25e-9)],
                   ["greedy_update_complex", pytest.approx(20e-9)]]


def test_op_names_from_hlo_text():
    text = ("%greedy_update_complex.14 = (f32[1,32768]{1,0}) custom-call("
            "f32[1,10240]{1,0} %copy-done.5), custom_call_target=\"tpu\"")
    assert trace.op_name(text) == "greedy_update_complex.14"
    assert trace.base_name("greedy_update_complex.14") == \
        "greedy_update_complex"
    assert trace.base_name("pad") == "pad"


def test_union_of_nested_and_disjoint_intervals():
    s, e = trace._union(np.array([5.0, 0.0, 1.0, 20.0]),
                        np.array([8.0, 10.0, 2.0, 30.0]))
    assert list(s) == [0.0, 20.0] and list(e) == [10.0, 30.0]


def test_recorded_tpu_trace():
    r = trace.reduce_file(os.path.join(DATA, "small.xplane.pb"),
                          window_span="bench.window")
    assert r.n_devices == 1
    assert 0 < r.busy_s() < 1e-3 < r.window_s
    kernel = lambda n: n.startswith("greedy_update_complex")  # noqa: E731
    assert r.op_count(kernel) == 2
    assert 0 < r.op_time(kernel) < r.busy_s()
    assert r.module_count(lambda n: "jit_greedy_update" in n) == 2
    assert "greedy_update_complex" in [name for name, _ in r.top_ops(3)]
    # the 10 ms sleeps between the steps are the longest idle gaps
    assert r.idle_gaps(1)[0][1] > 5e-3
