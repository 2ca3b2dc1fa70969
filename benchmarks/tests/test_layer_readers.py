"""The readers PR 25 added (`counters`, `seconds`) on canned scrapes, and
every `per_layer` entry of BENCHMARK.json against its file and reader.

A window's number is a difference between two pages; an absent span or
counter, a denominator that did not move and a share with no window all
read as NOTHING (None), never as 0: 0 is a measurement.
"""

import importlib
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.readers import counters, seconds, spans  # noqa: E402

BEFORE = """tbtpu_span_seconds_sum{event="pipeline.commit.idle"} 100.0
tbtpu_span_seconds_count{event="pipeline.commit.idle"} 50
tbtpu_span_seconds_sum{event="pipeline.store.idle"} 90.0
tbtpu_span_seconds_count{event="pipeline.store.idle"} 40
tbtpu_span_seconds_sum{event="device.compile"} 300.0
tbtpu_span_seconds_count{event="device.compile"} 27
tbtpu_span_seconds_sum{event="device.unfed"} 10.0
tbtpu_span_seconds_count{event="device.unfed"} 99
tbtpu_span_seconds_sum{event="op.service.execute"} 7.0
tbtpu_span_seconds_count{event="op.service.execute"} 400
tbtpu_span_seconds_sum{event="device.read_balances"} 2.0
tbtpu_span_seconds_count{event="device.read_balances"} 410
tbtpu_events_total{event="device.h2d_bytes"} 1000000
tbtpu_events_total{event="vsr.commits"} 400
tbtpu_events_total{event="sm.route.exact_batches"} 400
"""
AFTER = """tbtpu_span_seconds_sum{event="pipeline.commit.idle"} 120.0
tbtpu_span_seconds_count{event="pipeline.commit.idle"} 90
tbtpu_span_seconds_sum{event="pipeline.store.stall"} 6.0
tbtpu_span_seconds_count{event="pipeline.store.stall"} 30
tbtpu_span_seconds_sum{event="pipeline.store.idle"} 92.0
tbtpu_span_seconds_count{event="pipeline.store.idle"} 44
tbtpu_span_seconds_sum{event="device.compile"} 301.5
tbtpu_span_seconds_count{event="device.compile"} 29
tbtpu_span_seconds_sum{event="device.unfed"} 14.0
tbtpu_span_seconds_count{event="device.unfed"} 140
tbtpu_span_seconds_sum{event="sm.ct.stage"} 0.9
tbtpu_span_seconds_count{event="sm.ct.stage"} 600
tbtpu_span_seconds_sum{event="op.service.execute"} 47.0
tbtpu_span_seconds_count{event="op.service.execute"} 700
tbtpu_span_seconds_sum{event="device.read_balances"} 3.5
tbtpu_span_seconds_count{event="device.read_balances"} 710
tbtpu_events_total{event="device.h2d_bytes"} 301000000
tbtpu_events_total{event="vsr.commits"} 700
tbtpu_events_total{event="sm.exact.sweeps"} 1200
tbtpu_events_total{event="sm.route.exact_batches"} 700
tbtpu_events_total{event="sm.route.bail_batches"} 0
"""


def ctx(window=40.0):
    out = {"scrape_before": spans.parse(BEFORE), "scrape_after": spans.parse(AFTER)}
    if window is not None:
        out["window"] = {"t0": 0.0, "seconds": window, "answered_before": 420}
    return out


def spec(name):
    with open(os.path.join(REPO, "benchmarks", "layer_metrics", name + ".json")) as f:
        return json.load(f)


def close(a, b):
    return a is not None and abs(a - b) < 1e-9 * max(1.0, abs(b))


def test_the_metric_files_read_differences():
    c = ctx()
    # 20 s idle + 6 s of stall first seen in the window + no barrier at all, of 40 s
    assert close(seconds.read(spec("commit_thread_busy_pct"), c), 100 * (1 - 26 / 40))
    assert close(seconds.read(spec("store_thread_busy_pct"), c), 95.0)
    assert close(seconds.read(spec("device_unfed_pct"), c), 10.0)
    assert close(seconds.read(spec("compile_s_in_window"), c), 1.5)  # not 301.5, not 1.5 / 40
    assert close(counters.read(spec("h2d_bytes_per_batch"), c), 1_000_000.0)
    # a counter first bumped inside the window counts from 0
    assert close(counters.read(spec("exact_sweeps_per_batch"), c), 4.0)
    # a span first seen in the window, per batch committed in the window
    assert close(spans.read(spec("commit_stage_ms_per_batch"), c), 3.0)
    assert spans.read(spec("commit_barrier_ms_per_batch"), c) == 0.0  # absent_is_zero
    # 1.5 s in 300 calls of the window (the checkpoint's whole-table read one of them): not 3.5 / 710
    assert close(spans.read(spec("read_balances_ms_per_read"), c), 5.0)


def test_a_window_without_a_read_reports_no_read_time():
    c = ctx()
    c["scrape_after"]["tbtpu_span_seconds_count"]["device.read_balances"] = 410.0
    assert spans.read(spec("read_balances_ms_per_read"), c) is None  # the set-up's reads: not 0 ms
    for page in ("scrape_before", "scrape_after"):
        del c[page]["tbtpu_span_seconds_sum"]["device.read_balances"]
    assert spans.read(spec("read_balances_ms_per_read"), c) is None


@pytest.mark.parametrize("name,reader", [
    ("commit_sync_ms_per_batch", spans),       # the parent commit has no such span
    ("commit_prefetch_ms_per_batch", spans),
    ("d2h_bytes_per_batch", counters),         # no such counter on either page
])
def test_what_the_program_does_not_record_reads_as_nothing(name, reader):
    assert reader.read(spec(name), ctx()) is None


def test_an_absent_wait_that_is_not_marked_optional_is_nothing():
    c = ctx()
    for page in ("scrape_before", "scrape_after"):
        del c[page]["tbtpu_span_seconds_sum"]["pipeline.store.idle"]
    assert seconds.read(spec("store_thread_busy_pct"), c) is None  # not "100% busy"
    for page in ("scrape_before", "scrape_after"):
        del c[page]["tbtpu_span_seconds_sum"]["device.compile"]
    assert seconds.read(spec("compile_s_in_window"), c) is None  # no listener: not "0 s"


@pytest.mark.parametrize("window", [None, 0.0])
def test_a_share_without_a_window_is_nothing_and_a_sum_needs_none(window):
    c = ctx(window)
    for name in ("commit_thread_busy_pct", "store_thread_busy_pct", "device_unfed_pct"):
        assert seconds.read(spec(name), c) is None
    assert close(seconds.read(spec("compile_s_in_window"), c), 1.5)


def test_a_denominator_that_did_not_move_is_nothing():
    c = ctx()
    c["scrape_after"]["tbtpu_events_total"]["vsr.commits"] = 400.0
    assert counters.read(spec("h2d_bytes_per_batch"), c) is None
    assert counters.read({"count": ["sm.route.bail_batches"], "per": "vsr.commits"}, ctx()) == 0.0


@pytest.mark.parametrize("reader", [counters, seconds])
def test_an_untraced_run_has_no_scrapes(reader):
    assert reader.read({"count": ["a"], "per": "b", "events": ["a"]}, {}) is None


def test_every_per_layer_entry_finds_its_file_and_its_reader():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"] for w in manifest["workloads"]}
    for entry in manifest["per_layer"]:
        s = spec(entry["name"])  # the file is there under the metric's name
        reader = importlib.import_module("benchmarks.readers." + s["reader"])
        assert callable(reader.read), entry["name"]
        assert reader.read(s, {}) is None  # a run with nothing to read raises nothing
        assert set(entry.get("workloads", cells)) <= cells and s["what"]
    # and no file is an orphan: each is an entry's, or a pending cell's (tests/pending/)
    pending = os.path.join(REPO, "benchmarks", "tests", "pending")
    for name in os.listdir(pending):
        with open(os.path.join(pending, name)) as f:
            manifest["per_layer"] += json.load(f)["per_layer"]
    files = {f[:-5] for f in os.listdir(os.path.join(REPO, "benchmarks", "layer_metrics"))}
    assert files == {e["name"] for e in manifest["per_layer"]}
