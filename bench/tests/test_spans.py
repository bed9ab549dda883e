"""The readers of the program's own host spans (``bench/spans.py`` and the
seven metrics that use it), checked on hand-made timelines in the style
of ``test_trace.py``: idle time inside spans, the assignment of submits
to flushes, and nothing read where a span is missing."""

from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from bench import spans, trace
from bench.harness import Context, load_reader

READERS = ("host_copy_ms", "host_copy_ms.blocked", "sync_idle_ms",
           "sync_idle_ms.blocked", "queue_wait_ms", "flush_busy_pct",
           "to_host_max_ms")
MS = 1e-6   # ms per ns


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def reduced(host, ops=(), window=(0, 1000)):
    lo, hi = window
    host = [ev("bench.window", lo, hi - lo)] + list(host)
    return trace.Reduced(
        window, [trace._line(list(ops), lo, hi)] if ops is not None else [],
        [trace._line([], lo, hi)], trace._line(host, lo, hi))


def ctx(r, **counters):
    return Context(r, counters, {}, {}, 1, None)


def build_timeline():
    # two builds; in each, a driver span with the device busy for part of
    # it, then the copy to the host with the device idle
    host = [ev("repro.build", 0, 400), ev("repro.driver", 0, 300),
            ev("repro.driver.chunk", 50, 100),
            ev("repro.driver.chunk", 150, 150),
            ev("repro.build.to_host", 300, 90),
            ev("repro.build", 500, 400), ev("repro.driver", 500, 300),
            ev("repro.build.to_host", 800, 100)]
    # busy 20..120 and 100..200 (overlapping: 20..200), 250..350 (runs out
    # of the first driver), 520..780
    ops = [ev("fusion.1", 20, 100), ev("fusion.2", 100, 100),
           ev("pad.3", 250, 100), ev("greedy_update_complex.4", 520, 260)]
    return reduced(host, ops)


def test_idle_inside_spans_is_span_time_less_the_busy_union():
    r = build_timeline()
    # first driver 0..300: busy 20..200 and 250..300, idle 70; second
    # 500..800: busy 520..780, idle 40
    assert spans.idle_inside_s(r, "repro.driver") == pytest.approx(110e-9)
    assert load_reader("sync_idle_ms").read(ctx(r, builds=2)) == \
        pytest.approx(55 * MS)
    assert load_reader("sync_idle_ms.blocked").read(ctx(r, builds=2)) == \
        pytest.approx(55 * MS)


def test_idle_inside_nested_spans_counts_once():
    r = reduced([ev("repro.driver", 0, 100), ev("repro.driver", 10, 50)],
                [ev("fusion.1", 40, 20)])
    assert spans.idle_inside_s(r, "repro.driver") == pytest.approx(80e-9)
    assert spans.covered_s(r, "repro.driver") == pytest.approx(100e-9)


def test_idle_inside_spans_with_no_ops_is_the_span_time():
    r = reduced([ev("repro.driver", 100, 50)], [])
    assert spans.idle_inside_s(r, "repro.driver") == pytest.approx(50e-9)


def test_host_copy_is_the_summed_copy_per_build():
    r = build_timeline()
    assert spans.total_s(r, "repro.build.to_host") == pytest.approx(190e-9)
    for name in ("host_copy_ms", "host_copy_ms.blocked"):
        assert load_reader(name).read(ctx(r, builds=2)) == \
            pytest.approx(95 * MS)


def serve_timeline():
    # submits at 0, 10, 20 (1 ns each) are flushed at 100; the submit at
    # 105 ends after that flush began, so it waits for the flush at 300;
    # the submit at 950 has no flush in the window
    host = [ev("repro.serve.submit", t, 1) for t in (0, 10, 20, 105, 950)]
    host += [ev("repro.serve.wait", 0, 100),
             ev("repro.serve.flush", 100, 50),
             ev("repro.serve.eval", 110, 30),
             ev("repro.serve.to_host", 120, 20),
             ev("repro.serve.flush", 300, 100),
             ev("repro.serve.eval", 310, 80),
             ev("repro.serve.to_host", 320, 70)]
    return reduced(host, [ev("fusion.1", 115, 5), ev("fusion.1", 315, 5)])


def test_submits_go_to_the_first_flush_that_begins_after_them():
    r = serve_timeline()
    w = spans.queue_waits_s(r, "repro.serve.submit", "repro.serve.flush")
    assert list(w * 1e9) == pytest.approx([100, 90, 80, 195])
    assert load_reader("queue_wait_ms").read(ctx(r, requests=5)) == \
        pytest.approx(116.25 * MS)


def test_flush_busy_share_and_longest_copy():
    r = serve_timeline()
    assert load_reader("flush_busy_pct").read(ctx(r)) == pytest.approx(15.0)
    assert load_reader("to_host_max_ms").read(ctx(r)) == \
        pytest.approx(70 * MS)


def test_spans_are_clipped_to_the_window():
    r = reduced([ev("repro.serve.flush", 900, 200)], window=(0, 1000))
    assert load_reader("flush_busy_pct").read(ctx(r)) == pytest.approx(10.0)


@pytest.mark.parametrize("name", READERS)
def test_nothing_is_read_where_the_span_is_missing(name):
    # a program without the spans: only the benchmark's own and the
    # runtime's are on the host plane
    r = reduced([ev("api.build", 0, 500), ev("X64FromTuple", 400, 90)],
                [ev("fusion.1", 20, 100)])
    assert load_reader(name).read(ctx(r, builds=1, requests=3)) is None


def test_nothing_is_read_without_builds_or_flushes():
    r = build_timeline()
    assert load_reader("host_copy_ms").read(ctx(r, builds=0)) is None
    r = reduced([ev("repro.serve.submit", 10, 1)])
    assert load_reader("queue_wait_ms").read(ctx(r)) is None
    r = reduced([ev("repro.serve.submit", 500, 1),
                 ev("repro.serve.flush", 100, 10)])
    assert load_reader("queue_wait_ms").read(ctx(r)) is None


def test_idle_inside_spans_without_a_device_is_not_read():
    r = reduced([ev("repro.driver", 0, 100)], ops=None)
    assert load_reader("sync_idle_ms").read(ctx(r, builds=1)) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_match_span_names_the_program_writes(name):
    from repro.spans import NAMES

    reader = load_reader(name.split(".")[0])
    assert set(reader.MATCH) <= NAMES
