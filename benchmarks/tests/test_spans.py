"""The span arithmetic on two canned scrapes: a window's number is the
difference of sums over the difference of counts, never a page's mean."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.readers import monitor, spans  # noqa: E402

BEFORE = """# HELP tbtpu_span_seconds Traced span latency by event.
tbtpu_span_seconds{event="op.service.execute",quantile="0.5"} 0.9
tbtpu_span_seconds_sum{event="op.service.execute"} 100.0
tbtpu_span_seconds_count{event="op.service.execute"} 200
tbtpu_span_seconds_sum{event="op.queue.wal"} 1.0
tbtpu_span_seconds_count{event="op.queue.wal"} 200
tbtpu_span_seconds_sum{event="op.service.wal"} 2.0
tbtpu_span_seconds_count{event="op.service.wal"} 200
tbtpu_span_seconds_sum{event="pipeline.commit.inflight_depth"} 3e-07
tbtpu_span_seconds_count{event="pipeline.commit.inflight_depth"} 200
tbtpu_events_total{event="sm.route.fast_batches"} 200
tbtpu_events_total{event="pipeline.commit.inflight.d1"} 150
tbtpu_events_total{event="pipeline.commit.inflight.d4"} 50
"""
AFTER = """tbtpu_span_seconds_sum{event="op.service.execute"} 100.5
tbtpu_span_seconds_count{event="op.service.execute"} 300
tbtpu_span_seconds_sum{event="op.queue.wal"} 1.1
tbtpu_span_seconds_count{event="op.queue.wal"} 300
tbtpu_span_seconds_sum{event="op.service.wal"} 2.3
tbtpu_span_seconds_count{event="op.service.wal"} 300
tbtpu_span_seconds_sum{event="pipeline.commit.inflight_depth"} 5.5e-07
tbtpu_span_seconds_count{event="pipeline.commit.inflight_depth"} 300
tbtpu_span_seconds_sum{event="pipeline.store.stall"} 0.2
tbtpu_span_seconds_count{event="pipeline.store.stall"} 4
tbtpu_events_total{event="sm.route.fast_batches"} 300
tbtpu_events_total{event="pipeline.commit.inflight.d1"} 175
tbtpu_events_total{event="pipeline.commit.inflight.d4"} 125
"""


def ctx():
    return {"scrape_before": spans.parse(BEFORE), "scrape_after": spans.parse(AFTER)}


def close(a, b):
    return abs(a - b) < 1e-9 * max(1.0, abs(b))


def test_window_numbers_are_differences():
    c = ctx()
    # the page's own mean would be 100.5 / 300 s = 335 ms; the window's is 5 ms
    assert close(spans.read({"events": ["op.service.execute"], "scale": 1000.0}, c), 5.0)
    assert close(spans.read({"events": ["op.queue.wal", "op.service.wal"], "scale": 1000.0}, c), 4.0)
    assert close(spans.read({"events": ["pipeline.commit.inflight_depth"], "scale": 1e9}, c), 2.5)
    assert close(spans.delta(c, "tbtpu_events_total", "sm.route.fast_batches"), 100.0)
    depth = {"weights": {f"pipeline.commit.inflight.d{d}": d for d in (1, 2, 3, 4)}}
    assert close(spans.read(depth, c), (25 * 1 + 75 * 4) / 100)


def test_a_span_first_seen_in_the_window_and_one_never_seen():
    c = ctx()
    stall = {"events": ["pipeline.store.stall"], "per": "op.service.execute", "scale": 1000.0}
    assert close(spans.read(stall, c), 2.0)  # 0.2 s over the 100 batches committed
    never = {"events": ["pipeline.never"], "per": "op.service.execute"}
    assert spans.read(never, c) is None  # nothing to read: nothing returned, not 0
    assert spans.read({**never, "absent_is_zero": True}, c) == 0.0
    assert spans.read(stall, {}) is None  # an untraced run has no scrapes


def test_compile_counter_is_a_difference_too():
    c = {"monitor_before": {"compiles": 41}, "monitor_after": {"compiles": 43}}
    assert monitor.read({"field": "compiles"}, c) == 2.0
    assert monitor.read({"field": "compiles"}, {}) is None
