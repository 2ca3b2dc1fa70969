"""Device-plane observability (ISSUE 18): per-kernel cost/roofline
attribution, the owner-tagged device memory ledger, transfer-bandwidth
accounting, and the determinism guarantee that none of it steers a
replicated byte.

Layers under test:
  - tracer.py             device memory ledger (owner gauges, high-water,
                          prefix retirement), dispatch/finish windows +
                          in-flight depth, xfer-bandwidth histograms,
                          Perfetto async device lane, flight-dump device
                          snapshot, device_mem_high_water_bytes flat key
  - devicestats.py        note_call shape capture (bounded), static cost
                          model via lowered cost_analysis, roofline
                          classification, cost_table runtime join,
                          xfer_summary, device_status (/device payload)
  - models/state_machine  scratch-ring bucket retirement under workload
                          shift (gauges + cost rows + staging buffers)
  - tools/device_top      /device rendering, n/a degradation
  - tools/cluster_top     optional device columns on the replica table
  - tools/bench_gate      device gated keys, n/a vs BENCH_r06
  - tools/devhub          automatic pickup of the device series
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from tigerbeetle_tpu import devicestats, tracer, types  # noqa: E402


@pytest.fixture
def clean_tracer():
    """Enabled + reset tracer/devicestats, restored afterwards."""
    was = tracer.enabled()
    tracer.enable()
    tracer.reset()
    devicestats.reset()
    yield
    tracer.reset()
    devicestats.reset()
    if not was:
        tracer.disable()


def _load_tool(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"tool_{name}_dp", os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _jax_sm():
    """A small jax-backed StateMachine with 16 registered accounts
    (skips when the device fast path is unavailable)."""
    from tigerbeetle_tpu.constants import Config
    from tigerbeetle_tpu.models.state_machine import StateMachine

    config = Config(
        name="t", accounts_max=1 << 10, transfers_max=1 << 12,
        lsm_block_size=1 << 12, grid_block_count=1 << 10,
        grid_cache_blocks=16, index_memtable_rows=512,
    )
    sm = StateMachine(config, backend="jax")
    if sm._ops is None:
        pytest.skip("jax device path unavailable")
    n = 16
    ev = np.zeros(n, dtype=types.ACCOUNT_DTYPE)
    ev["id_lo"] = np.arange(1, n + 1)
    ev["ledger"] = 1
    ev["code"] = 10
    assert len(sm.create_accounts(ev, timestamp=n)) == 0
    return sm


def _transfer_batch(ids, amount=5):
    ev = np.zeros(len(ids), dtype=types.TRANSFER_DTYPE)
    ev["id_lo"] = ids
    ev["debit_account_id_lo"] = 1
    ev["credit_account_id_lo"] = 2
    ev["amount_lo"] = amount
    ev["ledger"] = 1
    ev["code"] = 7
    return ev


# --- device memory ledger -------------------------------------------------


class TestDeviceMemLedger:
    def test_set_retire_and_high_water(self, clean_tracer):
        tracer.device_mem_set("balances", 1000)
        tracer.device_mem_set("scratch.b512", 500)
        t = tracer.device_mem_totals()
        assert t["owners"] == {"balances": 1000, "scratch.b512": 500}
        assert t["total_bytes"] == 1500 and t["high_water_bytes"] == 1500
        # Retiring drops the owner AND its gauge; high-water persists.
        tracer.device_mem_retire_prefix("scratch.b512")
        t = tracer.device_mem_totals()
        assert "scratch.b512" not in t["owners"]
        assert t["total_bytes"] == 1000 and t["high_water_bytes"] == 1500
        g = tracer.gauges()
        assert g["device.mem.balances.bytes"] == 1000.0
        assert "device.mem.scratch.b512.bytes" not in g

    def test_retire_prefix_drops_owner_family(self, clean_tracer):
        tracer.device_mem_set("scratch.b256", 10)
        tracer.device_mem_set("scratch.b2048", 20)
        tracer.device_mem_set("balances", 30)
        tracer.device_mem_retire_prefix("scratch.b256")
        t = tracer.device_mem_totals()
        assert set(t["owners"]) == {"scratch.b2048", "balances"}
        g = tracer.gauges()
        assert "device.mem.scratch.b256.bytes" not in g
        assert "device.mem.scratch.b2048.bytes" in g

    def test_lifecycle_flat_key_gated_on_nonzero(self, clean_tracer):
        flat = tracer.lifecycle_summary()["flat"]
        assert "device_mem_high_water_bytes" not in flat
        tracer.device_mem_set("balances", 4096)
        flat = tracer.lifecycle_summary()["flat"]
        assert flat["device_mem_high_water_bytes"] == 4096.0

    def test_reset_rearms_ledger(self, clean_tracer):
        tracer.device_mem_set("balances", 4096)
        tracer.reset()
        t = tracer.device_mem_totals()
        assert not t["owners"] and t["high_water_bytes"] == 0

    def test_disabled_tracer_is_inert(self):
        was = tracer.enabled()
        tracer.disable()
        try:
            tracer.device_mem_set("balances", 4096)
            assert tracer.device_mem_totals()["owners"] == {}
        finally:
            if was:
                tracer.enable()


# --- dispatch/finish windows + transfer bandwidth -------------------------


class TestDispatchWindow:
    def test_dispatch_finish_records_step_and_bandwidth(self, clean_tracer):
        tok = tracer.device_dispatch(
            "create_transfers_fast", h2d_bytes=1_000_000
        )
        assert tok > 0
        time.sleep(0.002)
        tracer.device_finish("create_transfers_fast", tok, d2h_bytes=4096)
        snap = tracer.snapshot()
        assert snap["device.step.create_transfers_fast"]["count"] == 1
        assert snap["device.create_transfers_fast.dispatches"]["count"] == 1
        assert snap["device.h2d_bytes"]["count"] == 1_000_000
        assert snap["device.d2h_bytes"]["count"] == 4096
        # The bandwidth histograms hold RAW MB/s samples; the p50_us
        # convention reads back GB/s. 1 MB over ~2 ms ≈ 0.5 GB/s.
        h2d = snap["device.xfer.h2d.gbps"]
        assert h2d["count"] == 1 and 0 < h2d["p50_us"] < 1.0
        assert snap["device.xfer.d2h.gbps"]["count"] == 1

    def test_inflight_window_depth(self, clean_tracer):
        t1 = tracer.device_dispatch("create_transfers_fast")
        t2 = tracer.device_dispatch("create_transfers_fast")
        t3 = tracer.device_dispatch("read_balances")
        inflight = tracer.device_inflight()
        assert inflight["entries"] == {
            "create_transfers_fast": 2, "read_balances": 1,
        }
        assert inflight["window_depth"] == 3
        for e, t in (("create_transfers_fast", t1),
                     ("create_transfers_fast", t2), ("read_balances", t3)):
            tracer.device_finish(e, t)
        assert tracer.device_inflight()["window_depth"] == 0

    def test_abandoned_tokens_evicted_fifo(self, clean_tracer):
        for _ in range(tracer._DEVICE_INFLIGHT_MAX + 8):
            tracer.device_dispatch("create_transfers_fast")
        inflight = tracer.device_inflight()
        assert (inflight["entries"]["create_transfers_fast"]
                == tracer._DEVICE_INFLIGHT_MAX)

    def test_disabled_dispatch_returns_zero_token(self):
        was = tracer.enabled()
        tracer.disable()
        try:
            tok = tracer.device_dispatch("create_transfers_fast", h2d_bytes=1)
            assert tok == 0
            tracer.device_finish("create_transfers_fast", tok)
            assert tracer.device_inflight()["window_depth"] == 0
        finally:
            if was:
                tracer.enable()

    def test_unknown_entry_rejected(self, clean_tracer):
        with pytest.raises(ValueError, match="unknown device entry"):
            tracer.device_dispatch("mystery_kernel")


# --- Perfetto async device lane -------------------------------------------


class TestDeviceTraceLane:
    def test_overlapping_windows_render_as_async_pairs(self, clean_tracer):
        """Two in-flight dispatches of the same entry must export as
        overlapping 'b'/'e' async spans with distinct ids — the depth-N
        overlap the per-thread 'X' rows structurally cannot show."""
        t1 = tracer.device_dispatch("create_transfers_fast", h2d_bytes=100)
        time.sleep(0.001)
        t2 = tracer.device_dispatch("create_transfers_fast", h2d_bytes=200)
        time.sleep(0.001)
        tracer.device_finish("create_transfers_fast", t1, d2h_bytes=10)
        time.sleep(0.001)
        tracer.device_finish("create_transfers_fast", t2)
        doc = tracer.export_trace()
        dev = [e for e in doc["traceEvents"] if e.get("cat") == "device"]
        begins = [e for e in dev if e["ph"] == "b"]
        ends = [e for e in dev if e["ph"] == "e"]
        assert len(begins) == 2 and len(ends) == 2
        assert begins[0]["id"] != begins[1]["id"]
        assert begins[0]["args"]["h2d_bytes"] == 100
        assert begins[0]["args"]["d2h_bytes"] == 10
        # Overlap: window 2 begins before window 1 ends.
        end_by_id = {e["id"]: e["ts"] for e in ends}
        assert begins[1]["ts"] < end_by_id[begins[0]["id"]]
        # Every id pairs up b-with-e.
        assert {b["id"] for b in begins} == set(end_by_id)


# --- flight-recorder device snapshot (satellite b) ------------------------


class TestFlightDumpDeviceSnapshot:
    def test_dump_carries_device_block(self, clean_tracer, tmp_path):
        tracer.configure_flight(directory=str(tmp_path))
        tracer.device_mem_set("balances", 2048)
        tracer.device_mem_set("scratch.b256", 512)
        tok = tracer.device_dispatch("create_transfers_fast", h2d_bytes=64)
        path = tracer.flight_exception("RuntimeError('stage died')")
        tracer.device_finish("create_transfers_fast", tok)
        assert path is not None
        doc = json.loads(open(path).read())
        dev = doc["device"]
        assert dev["inflight"] == {"create_transfers_fast": 1}
        assert dev["window_depth"] == 1
        assert dev["mem"] == {"balances": 2048, "scratch.b256": 512}
        assert dev["mem_total_bytes"] == 2560
        assert dev["mem_high_water_bytes"] == 2560


# --- cost model: shape capture, static cost, roofline ---------------------


class TestCostModel:
    def test_note_call_captures_and_bounds_shapes(self, clean_tracer):
        a = np.zeros((256, 4), dtype=np.uint32)
        devicestats.note_call("create_transfers_fast", (a,), bucket=256)
        devicestats.note_call("create_transfers_fast", (a,), bucket=256)
        shapes = devicestats.observed_shapes()
        assert len(shapes["create_transfers_fast"]) == 1
        assert "256x4:uint32" in shapes["create_transfers_fast"][0]
        # Bounded per entry: distinct shapes past the cap are dropped.
        for n in range(devicestats._SHAPES_PER_ENTRY_MAX + 8):
            devicestats.note_call(
                "read_balances", (np.zeros(n + 1, np.int32),)
            )
        assert (len(devicestats.observed_shapes()["read_balances"])
                == devicestats._SHAPES_PER_ENTRY_MAX)

    def test_note_call_disabled_tracer_noop(self):
        was = tracer.enabled()
        tracer.disable()
        try:
            devicestats.note_call("read_balances", (np.zeros(4, np.int32),))
            assert "read_balances" not in devicestats.observed_shapes()
        finally:
            if was:
                tracer.enable()

    def test_retire_bucket_drops_rows_and_costs(self, clean_tracer):
        a = np.zeros(256, dtype=np.uint32)
        b = np.zeros(512, dtype=np.uint32)
        devicestats.note_call("create_transfers_fast", (a,), bucket=256)
        devicestats.note_call("create_transfers_fast", (b,), bucket=512)
        devicestats.note_call("read_balances", (a,), bucket=256)
        devicestats.retire_bucket(256)
        shapes = devicestats.observed_shapes()
        assert len(shapes["create_transfers_fast"]) == 1
        assert "512" in shapes["create_transfers_fast"][0]
        assert "read_balances" not in shapes  # entry emptied entirely

    def test_classify_thresholds_and_env_override(self, clean_tracer,
                                                  monkeypatch):
        assert devicestats.classify(None, 100) == "n/a"
        assert devicestats.classify(100, None) == "n/a"
        monkeypatch.setenv("TIGERBEETLE_TPU_ROOFLINE_FLOP_PER_BYTE", "1.0")
        assert devicestats.classify(100, 10) == "compute"  # intensity 10 > 1
        monkeypatch.setenv("TIGERBEETLE_TPU_ROOFLINE_FLOP_PER_BYTE", "50.0")
        assert devicestats.classify(100, 10) == "memory"  # 10 < 50

    def test_classify_by_device_kind_never_by_an_assumed_peak(
            self, clean_tracer, monkeypatch):
        """On an accelerator the balance point comes from the peaks
        table keyed by device_kind (v5e: 197e12 / 819e9 ≈ 240 FLOP/B);
        a device the table does not list gets NO classification."""
        monkeypatch.delenv("TIGERBEETLE_TPU_ROOFLINE_FLOP_PER_BYTE",
                           raising=False)

        class _Dev:
            def __init__(self, kind):
                self.device_kind = kind

        class _Jax:
            def __init__(self, kind):
                self._kind = kind

            def default_backend(self):
                return "tpu"

            def devices(self):
                return [_Dev(self._kind)]

        monkeypatch.setattr(devicestats, "_jax_if_loaded",
                            lambda: _Jax("TPU v5 lite"))
        assert devicestats.classify(100, 1) == "memory"    # 100 < 240
        assert devicestats.classify(1000, 1) == "compute"  # 1000 > 240
        monkeypatch.setattr(devicestats, "_jax_if_loaded",
                            lambda: _Jax("TPU v9 imaginary"))
        assert devicestats.classify(100, 1) is None
        assert devicestats.classify(None, 1) == "n/a"

    def test_cost_for_unknown_entry_is_na(self, clean_tracer):
        devicestats.note_call("create_transfers_fast",
                              (np.zeros(4, np.int32),))
        key = devicestats.observed_shapes()["create_transfers_fast"][0]
        # Not a lowerable callable in any loaded module → None, no raise.
        assert devicestats.cost_for("create_transfers_fast", key) is None

    def test_cost_table_joins_live_jax_workload(self, clean_tracer):
        """Drive the real device fast path, then the table must hold a
        row per observed bucket shape with measured ms/call joined in;
        where the backend reports static costs the achieved-GB/s and
        roofline-bound columns light up."""
        sm = _jax_sm()
        for i in range(3):
            sm.create_transfers(
                _transfer_batch(np.arange(100 + i * 16, 116 + i * 16)),
                timestamp=100 + i,
            )
        rows = devicestats.cost_table()
        fast = [r for r in rows if r["entry"] == "create_transfers_fast"]
        assert fast, f"no create_transfers_fast rows in {rows}"
        r = fast[0]
        assert r["calls"] >= 3
        assert r["ms_per_call"] and r["ms_per_call"] > 0
        assert r["bound"] in ("compute", "memory", "n/a")
        if r["flops"]:
            assert r["achieved_gflops"] > 0
        if r["bytes_accessed"]:
            assert r["achieved_gbps"] > 0
            assert r["bound"] in ("compute", "memory")
        # The device_status payload carries the same rows + live ledgers.
        st = devicestats.device_status()
        assert st["backend"] != "none"
        assert st["tracing"] is True
        assert any(e["entry"] == "create_transfers_fast"
                   for e in st["entries"])
        assert st["mem"]["owners"].get("balances", 0) > 0
        assert st["xfer"]["h2d_bytes"] > 0

    def test_device_status_commit_depth_passthrough(self, clean_tracer):
        class _R:
            commit_depth = 4

        assert devicestats.device_status(_R())["commit_depth"] == 4
        assert "commit_depth" not in devicestats.device_status(object())


# --- transfer summary -----------------------------------------------------


class TestXferSummary:
    def test_percentiles_bytes_and_per_transfer(self, clean_tracer):
        tok = tracer.device_dispatch("create_transfers_fast",
                                     h2d_bytes=500_000)
        time.sleep(0.001)
        tracer.device_finish("create_transfers_fast", tok, d2h_bytes=100_000)
        tracer.count("sm.stored_transfers", 100)
        out = devicestats.xfer_summary()
        assert out["h2d_bytes"] == 500_000 and out["d2h_bytes"] == 100_000
        assert out["h2d_windows"] == 1 and out["d2h_windows"] == 1
        assert out["h2d_gbps_p50"] > 0 and out["h2d_gbps_p99"] > 0
        assert out["bytes_per_transfer"] == 6000.0

    def test_empty_registry_degrades(self, clean_tracer):
        out = devicestats.xfer_summary()
        assert out["h2d_bytes"] == 0 and out["d2h_bytes"] == 0
        assert "h2d_gbps_p50" not in out
        assert "bytes_per_transfer" not in out


# --- scratch-ring bucket retirement (satellite a) -------------------------


class TestScratchBucketRetirement:
    def test_workload_shift_retires_stale_bucket(self, clean_tracer):
        """After a workload shift the old bucket's staging buffers,
        mem gauges, and cost rows must all retire once it goes
        SCRATCH_STALE_AFTER dispatches without reuse — the ring and the
        registry stay bounded under bucket churn."""
        sm = _jax_sm()
        sm.SCRATCH_STALE_AFTER = 4
        # Bucket 16 (n=16 pads to 16), then shift to bucket 32.
        sm.create_transfers(_transfer_batch(np.arange(100, 116)), 100)
        assert 16 in sm._scratch_last_use
        g = tracer.gauges()
        assert g.get("device.mem.scratch.b16.bytes", 0) > 0
        assert any("16" in k
                   for k in devicestats.observed_shapes().get(
                       "create_transfers_fast", []))
        for i in range(6):
            sm.create_transfers(
                _transfer_batch(np.arange(200 + i * 32, 232 + i * 32)),
                200 + i,
            )
        # Bucket 16 idle past the threshold: fully retired.
        assert 16 not in sm._scratch_last_use
        assert 32 in sm._scratch_last_use
        assert not any(k[1] == 16 for slot in sm._disp_scratch for k in slot)
        g = tracer.gauges()
        assert "device.mem.scratch.b16.bytes" not in g
        assert g.get("device.mem.scratch.b32.bytes", 0) > 0
        shapes = devicestats.observed_shapes().get("create_transfers_fast", [])
        assert shapes and not any(s.startswith("16x") for s in shapes)

    def test_registry_bounded_under_bucket_churn(self, clean_tracer):
        """Cycling through bucket sizes must not grow the gauge registry
        or the ring: at most the live working set survives."""
        sm = _jax_sm()
        sm.SCRATCH_STALE_AFTER = 2
        sizes = (16, 32, 64, 128)
        for round_ in range(3):
            for j, n in enumerate(sizes):
                base = 1000 + round_ * 1000 + j * 200
                sm.create_transfers(
                    _transfer_batch(np.arange(base, base + n)),
                    base,
                )
        scratch_gauges = [k for k in tracer.gauges()
                          if k.startswith("device.mem.scratch.")]
        assert len(scratch_gauges) <= sm.SCRATCH_STALE_AFTER + 1
        assert len(sm._scratch_last_use) <= sm.SCRATCH_STALE_AFTER + 1


# --- numpy backend: graceful degradation, jax-free parent (satellite d) ---


class TestNumpyGracefulDegradation:
    def test_device_plane_answers_without_jax(self):
        """The whole device surface must answer on a jax-free numpy
        process — and must not pull jax in to do it (the observability
        endpoint is telemetry, not a dependency)."""
        code = """
import sys
import numpy as np
from tigerbeetle_tpu import devicestats, tracer, types
from tigerbeetle_tpu.constants import Config
from tigerbeetle_tpu.models.state_machine import StateMachine

assert "jax" not in sys.modules, "importing the device plane pulled in jax"
tracer.enable()
tracer.reset()
config = Config(name="t", accounts_max=1 << 10, transfers_max=1 << 12,
                lsm_block_size=1 << 12, grid_block_count=1 << 10,
                grid_cache_blocks=16, index_memtable_rows=512)
sm = StateMachine(config, backend="numpy")
ev = np.zeros(4, dtype=types.ACCOUNT_DTYPE)
ev["id_lo"] = np.arange(1, 5)
ev["ledger"] = 1
ev["code"] = 10
sm.create_accounts(ev, timestamp=4)
tr = np.zeros(4, dtype=types.TRANSFER_DTYPE)
tr["id_lo"] = np.arange(100, 104)
tr["debit_account_id_lo"] = 1
tr["credit_account_id_lo"] = 2
tr["amount_lo"] = 1
tr["ledger"] = 1
tr["code"] = 7
sm.create_transfers(tr, timestamp=10)
st = devicestats.device_status()
assert st["backend"] == "none", st
assert st["entries"] == []
assert st["inflight"]["window_depth"] == 0
assert st["xfer"]["h2d_bytes"] == 0
assert devicestats.cost_table() == []
assert "jax" not in sys.modules, "the device plane lazily imported jax"
print("DEVICE_PLANE_NUMPY_OK")
"""
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120, cwd=REPO,
        )
        assert out.returncode == 0, out.stderr
        assert "DEVICE_PLANE_NUMPY_OK" in out.stdout


# --- telemetry on/off determinism (satellite d) ---------------------------


class TestTelemetryDeterminism:
    """Device telemetry observes the commit path, it never steers it:
    the SAME jax depth-2 cluster workload with telemetry OFF and ON must
    produce byte-identical hash_log commit chains and checkpoint trailer
    digests."""

    def test_on_vs_off_byte_identical(self, tmp_path):
        from tests.test_cluster import TestOverlappedPipeline
        from tigerbeetle_tpu.lsm.store import NativeU128Map, _hostops
        from tigerbeetle_tpu.models.state_machine import make_u128_index
        from tigerbeetle_tpu.testing.hash_log import HashLog

        if _hostops() is None or not isinstance(
            make_u128_index(64), NativeU128Map
        ):
            pytest.skip("split-phase dispatch needs the native staging shim")
        harness = TestOverlappedPipeline()
        was = tracer.enabled()
        tracer.disable()
        try:
            create = HashLog(str(tmp_path / "chain.log"), "create")
            off = harness._drive(overlap=True, hash_log=create,
                                 sm_backend="jax", commit_depth=2)
            create.close()
            tracer.enable()
            tracer.reset()
            devicestats.reset()
            check = HashLog(str(tmp_path / "chain.log"), "check")
            on = harness._drive(overlap=True, hash_log=check,
                                sm_backend="jax", commit_depth=2)
            check.close()
            # The ON run actually recorded device telemetry.
            snap = tracer.snapshot()
            assert any(k.startswith("device.step.") for k in snap), (
                "telemetry-on run recorded no device steps"
            )
            assert tracer.device_mem_totals()["high_water_bytes"] > 0
            harness._check_runs_identical(off, on)
        finally:
            tracer.reset()
            devicestats.reset()
            if was:
                tracer.enable()
            else:
                tracer.disable()


# --- tools: device_top + cluster_top device columns (satellite c) ---------


class TestDeviceTools:
    STATUS = {
        "backend": "cpu", "tracing": True,
        "entries": [{
            "entry": "create_transfers_fast",
            "shape": "2048x2:uint32|2048:int32", "calls": 24,
            "ms_per_call": 0.61, "flops": 1.0e6, "bytes_accessed": 1.7e6,
            "bound": "memory", "achieved_gflops": 1.6,
            "achieved_gbps": 2.76,
        }],
        "mem": {
            "owners": {"balances": 294912, "scratch.b2048": 1376256},
            "total_bytes": 1671168, "high_water_bytes": 1671168,
            "backend_reported": {"bytes_in_use": 2000000,
                                 "peak_bytes_in_use": 3000000},
        },
        "xfer": {"h2d_bytes": 4096, "d2h_bytes": 1024,
                 "h2d_gbps_p50": 0.1, "d2h_gbps_p50": 0.0,
                 "bytes_per_transfer": 91.9},
        "inflight": {"entries": {"create_transfers_fast": 2},
                     "window_depth": 2},
    }

    def test_device_top_render(self):
        top = _load_tool("device_top")
        text = top.render([self.STATUS, None], [8081, 8082])
        assert "port 8082: UNREACHABLE" in text
        assert "inflight_depth=2" in text
        assert "create_transfers_fast" in text
        assert "memory" in text and "2.76" in text
        assert "high_water=1671168" in text
        assert "scratch.b2048" in text
        assert "in_use=2000000" in text
        assert "bytes/transfer=91.9" in text

    def test_device_top_degrades_to_na(self):
        top = _load_tool("device_top")
        bare = {"backend": "none", "tracing": False, "entries": [
            {"entry": "read_balances", "shape": "16:int32", "calls": 0,
             "ms_per_call": None, "flops": None, "bytes_accessed": None,
             "bound": "n/a"},
        ], "mem": {"owners": {}, "total_bytes": 0, "high_water_bytes": 0},
            "xfer": {"h2d_bytes": 0, "d2h_bytes": 0},
            "inflight": {"entries": {}, "window_depth": 0}}
        text = top.render([bare], [8081])
        assert "backend=none" in text
        line = next(ln for ln in text.splitlines() if "read_balances" in ln)
        assert "-" in line and "n/a" in line

    def test_cluster_top_device_columns(self):
        top = _load_tool("cluster_top")
        with_dev = {
            "replica": 0, "view": 1, "status": "normal", "is_primary": 1,
            "op": 10, "commit_min": 10, "clock": {},
            "device": {"mem_high_water_bytes": 1671168,
                       "inflight_depth": 2},
            "peers": {},
        }
        without = {
            "replica": 1, "view": 1, "status": "normal", "is_primary": 0,
            "op": 10, "commit_min": 10, "clock": {}, "peers": {},
        }
        text = top.render([with_dev, without, None], [8081, 8082, 8083])
        assert "dev_mem_hw" in text and "inflt" in text
        rows = text.splitlines()
        assert "1671168" in rows[1] and rows[1].rstrip().endswith("2")
        # A pre-device-plane replica renders '-', not a KeyError.
        assert rows[2].rstrip().endswith("-")
        assert "UNREACHABLE" in rows[3]

    def test_cluster_status_carries_device_block(self, clean_tracer):
        from tigerbeetle_tpu.vsr.peerstats import cluster_status

        class _R:
            replica = 0
            replica_count = 1
            view = 1
            status = "normal"
            is_primary = True
            op = 0
            commit_min = 0
            commit_max = 0
            peer_stats = None
            clocksync = None

        st = cluster_status(_R())
        assert "device" not in st  # no device traffic → no block
        tracer.device_mem_set("balances", 512)
        tok = tracer.device_dispatch("create_transfers_fast")
        st = cluster_status(_R())
        assert st["device"]["mem_high_water_bytes"] == 512
        assert st["device"]["inflight_depth"] == 1
        tracer.device_finish("create_transfers_fast", tok)


# --- bench_gate: device keys, n/a vs BENCH_r06 (satellite e) --------------


class TestBenchGateDevicePlane:
    DEVICE = {
        "device_mem_high_water_bytes": 1671168.0,
        "xfer_h2d_gbps_p50": 0.1,
        "xfer_d2h_gbps_p50": 0.0,
        "create_transfers_fast_gbps": 2.76,
        "read_balances_gbps": 0.003,
    }

    def _gate(self, tmp_path, monkeypatch, baseline_extra, current_extra):
        gate = _load_tool("bench_gate")
        (tmp_path / "BENCH_r97.json").write_text(
            json.dumps({"parsed": {"extra": baseline_extra}})
        )
        monkeypatch.setattr(gate, "REPO", str(tmp_path))
        return gate.main([
            "--current-json", json.dumps({"extra": current_extra}),
            "--devhub", str(tmp_path / "devhub.jsonl"),
        ])

    def test_na_tolerance_vs_bench_r06(self, tmp_path, monkeypatch, capsys):
        """The shipped BENCH_r06 baseline predates the device plane: a
        candidate that RECORDS the new keys must gate n/a on them and
        numerically on everything else."""
        with open(os.path.join(REPO, "BENCH_r06.json")) as f:
            r06 = json.load(f)
        base_extra = (r06.get("parsed") or r06)["extra"]
        cur = json.loads(json.dumps(base_extra))
        cur["device"] = dict(self.DEVICE)
        rc = self._gate(tmp_path, monkeypatch, base_extra, cur)
        out = capsys.readouterr().out
        assert rc == 0
        for key in ("device.xfer_h2d_gbps_p50",
                    "device.device_mem_high_water_bytes",
                    "device.create_transfers_fast_gbps"):
            line = next(ln for ln in out.splitlines() if key in ln)
            assert "n/a" in line

    def test_bandwidth_regression_fails_once_baselined(
        self, tmp_path, monkeypatch,
    ):
        base = {
            "end_to_end": {"load_accepted_tx_per_s": 1000.0},
            "device": dict(self.DEVICE),
        }
        cur = json.loads(json.dumps(base))
        cur["device"]["create_transfers_fast_gbps"] = 2.0  # −28%
        assert self._gate(tmp_path, monkeypatch, base, cur) == 1

    def test_mem_high_water_growth_fails(self, tmp_path, monkeypatch):
        """device_mem_high_water_bytes gates lower-is-better: a ledger
        that grows past tolerance is a regression."""
        base = {
            "end_to_end": {"load_accepted_tx_per_s": 1000.0},
            "device": dict(self.DEVICE),
        }
        cur = json.loads(json.dumps(base))
        cur["device"]["device_mem_high_water_bytes"] *= 1.5
        assert self._gate(tmp_path, monkeypatch, base, cur) == 1

    def test_missing_after_baselined_fails_closed(self, tmp_path, monkeypatch):
        base = {
            "end_to_end": {"load_accepted_tx_per_s": 1000.0},
            "device": dict(self.DEVICE),
        }
        cur = {"end_to_end": {"load_accepted_tx_per_s": 1000.0}}
        assert self._gate(tmp_path, monkeypatch, base, cur) == 1

    def test_list_names_the_keys(self, capsys):
        gate = _load_tool("bench_gate")
        assert gate.main(["--list"]) == 0
        out = capsys.readouterr().out
        for key in ("device.xfer_h2d_gbps_p50", "device.xfer_d2h_gbps_p50",
                    "device.device_mem_high_water_bytes",
                    "device.create_transfers_fast_gbps",
                    "device.read_balances_gbps"):
            assert key in out

    def test_devhub_picks_up_device_series(self):
        """devhub derives METRICS from bench_gate.GATED — the device
        rows must arrive automatically, with their directions intact."""
        devhub = _load_tool("devhub")
        metrics = dict(devhub.METRICS)
        assert metrics["device.xfer_h2d_gbps_p50"] is True
        assert metrics["device.create_transfers_fast_gbps"] is True
        assert metrics["device.device_mem_high_water_bytes"] is False
