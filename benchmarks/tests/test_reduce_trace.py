"""The trace reduction: on hand-made planes (the arithmetic), and on the
small trace recorded on the chip by record_trace.py (the real format)."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import reduce_trace  # noqa: E402
from benchmarks.readers import trace as trace_reader  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000  # ns


def planes():
    ops = [("fusion.1", 10 * MS, 2 * MS), ("fusion.2", 11 * MS, 3 * MS),  # overlap: 10..14
           ("copy", 20 * MS, 1 * MS), ("fusion.1", 30 * MS, 2 * MS)]
    modules = [("jit_kernel_a(123)", 10 * MS, 4 * MS), ("jit_kernel_b(7)", 20 * MS, 1 * MS),
               ("jit_kernel_a(123)", 30 * MS, 2 * MS)]
    host = [("wait", 0, 40 * MS)]  # the trace spans 0..40 ms on the host
    return [("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", modules)]),
            ("/host:CPU", [("main", host)])]


def test_busy_is_a_union_and_the_window_spans_every_plane():
    r = reduce_trace.reduce_planes(planes(), "/device:TPU")
    assert r["window_s"] == pytest.approx(0.040)
    assert r["busy_s"] == pytest.approx(0.007)  # 4 + 1 + 2 ms, the overlap once
    assert r["modules"]["jit_kernel_a"] == {"seconds": pytest.approx(0.006), "calls": 2}
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.004)]
    gaps = dict(r["idle_gaps"])
    assert gaps["before jit_kernel_b"] == pytest.approx(0.006)
    assert gaps["before jit_kernel_a"] == pytest.approx(0.009)


def test_no_device_plane_no_numbers():
    assert reduce_trace.reduce_planes(planes(), "/device:GPU") == {}
    assert trace_reader.read({"arithmetic": "idle"}, {"trace": {}}) is None


def test_roofline_and_idle_from_the_reduction():
    r = reduce_trace.reduce_planes(planes(), "/device:TPU")
    ctx = {"trace": r, "config": {"batch": 8190}, "traffic": {},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert trace_reader.read({"arithmetic": "idle"}, ctx) == pytest.approx(82.5)
    spec = {"arithmetic": "roofline", "module": "^jit_kernel_a",
            "needed_work": "create_transfers_fast"}
    needed = 8190 * (48 + 4) + 2 * 8190 * 64 * 2  # bytes, from the shapes alone
    assert trace_reader.read(spec, ctx) == pytest.approx(
        100 * (needed / 819e9) / 0.003)
    assert trace_reader.read({**spec, "module": "^jit_absent"}, ctx) is None  # never 0


@pytest.mark.skipif(not os.path.isdir(os.path.join(DATA, "small_trace")),
                    reason="no recorded trace beside the test")
def test_the_recorded_trace_reduces_to_what_was_read_off_it_by_hand():
    with open(os.path.join(DATA, "small_trace.expected.json")) as f:
        want = json.load(f)
    got = reduce_trace.reduce_dir(os.path.join(DATA, "small_trace"))
    assert got["devices"] == want["devices"]
    assert got["window_s"] == pytest.approx(want["window_s"])
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    for name, m in want["modules"].items():
        assert got["modules"][name]["calls"] == m["calls"]
        assert got["modules"][name]["seconds"] == pytest.approx(m["seconds"])
