"""Per-operation lifecycle layer: queue-wait vs service decomposition,
flight recorder (anomaly trip + dump), device-step profiler, and the
`--ops` waterfall view (ISSUE 6 tentpole; tracer.py lifecycle section).

The scripted tests inject known stamp times, so the expected component
split is EXACT — component means come from the aggregate totals, which
quantize nothing (only percentiles ride the log-bucketed histograms)."""

import json
import subprocess
import sys
import threading

import pytest

from tigerbeetle_tpu import tracer

REPO = __file__.rsplit("/tests/", 1)[0]

# A scripted op: (stamp index, offset ns from the op's arrival).
SCRIPT = (
    (tracer.OP_ARRIVE, 0),
    (tracer.OP_PREPARE, 1_000_000),      # queue.request   1.0 ms
    (tracer.OP_WAL_ENQUEUE, 1_500_000),  # service.prepare 0.5 ms
    (tracer.OP_WAL_WRITE, 3_500_000),    # queue.wal       2.0 ms
    (tracer.OP_WAL_DURABLE, 7_500_000),  # service.wal     4.0 ms
    (tracer.OP_COMMIT_SUBMIT, 8_000_000),   # queue.quorum 0.5 ms
    (tracer.OP_EXEC_START, 9_000_000),      # queue.commit 1.0 ms
    (tracer.OP_EXEC_END, 17_000_000),       # service.execute 8.0 ms
    (tracer.OP_REPLY, 18_000_000),          # service.reply 1.0 ms
    (tracer.OP_STORE_SUBMIT, 17_100_000),
    (tracer.OP_STORE_START, 20_100_000),    # queue.store   3.0 ms
    (tracer.OP_STORE_END, 26_100_000),      # service.store 6.0 ms
)
EXPECT_MS = {
    "queue.request": 1.0, "service.prepare": 0.5, "queue.wal": 2.0,
    "service.wal": 4.0, "queue.quorum": 0.5, "queue.commit": 1.0,
    "service.execute": 8.0, "service.reply": 1.0,
    "queue.store": 3.0, "service.store": 6.0,
}


def scripted_op(i, base_ns=1_000_000_000, exec_extra_ns=0):
    """Finalize one op with the scripted stamps (known sleeps → known
    wait/service split)."""
    rec = tracer.op_begin()
    t0 = base_ns + i * 50_000_000
    tracer.op_meta(rec, op=i, client=7, request=i, operation=130, n_events=8190)
    for idx, off in SCRIPT:
        extra = exec_extra_ns if idx >= tracer.OP_EXEC_END else 0
        tracer.op_stamp(rec, idx, t0 + off + extra)
    tracer.op_finish(rec)
    tracer.op_store_done(rec)
    return rec


@pytest.fixture
def traced():
    tracer.reset()
    tracer.enable()
    # Quiet flight policy so unrelated tests never dump to disk.
    tracer.configure_flight(
        latency_mult=8.0, stall_ms=2000.0, min_ops=64, max_dumps=3,
        cooldown_s=5.0, ring=tracer.OP_RING_DEFAULT,
    )
    yield
    tracer.disable()
    tracer.reset()


# --- exact decomposition --------------------------------------------------


def test_scripted_decomposition_exact(traced):
    """Known stamps → exact per-component means, and the window
    components sum EXACTLY to the perceived (arrive→reply) latency."""
    for i in range(5):
        scripted_op(i)
    s = tracer.lifecycle_summary()
    assert s["ops"] == 5
    for name, want in EXPECT_MS.items():
        assert s["components"][name]["mean_ms"] == pytest.approx(want), name
    window = sum(
        s["components"][n]["mean_ms"] for n in EXPECT_MS if ".store" not in n
    )
    assert s["perceived"]["mean_ms"] == pytest.approx(18.0)
    assert window == pytest.approx(18.0)  # telescoping sum, no slack
    # Queue/service totals are real per-op distributions too.
    assert s["flat"]["queue_wait_total_ms"] == pytest.approx(4.5)
    assert s["flat"]["service_total_ms"] == pytest.approx(13.5)
    # p50s land within the histogram's 12.5% bucket resolution.
    assert s["flat"]["lifecycle_perceived_p50_ms"] == pytest.approx(18.0, rel=0.13)


def test_commit_inflight_flat_keys(traced):
    """The cross-batch commit-window occupancy export: the per-depth
    counters → commit_inflight_mean/max/p99, exact, plus the configured
    depth from the pipeline.commit.depth_config gauge (recorded so A/Bs
    can see which depth the adaptive default selected)."""
    for d in (1, 2, 3, 4, 4, 4):
        tracer.count(f"pipeline.commit.inflight.d{d}")
    tracer.gauge("pipeline.commit.depth_config", 4)
    flat = tracer.lifecycle_summary()["flat"]
    assert flat["commit_inflight_mean"] == pytest.approx(3.0)
    assert flat["commit_inflight_max"] == 4
    assert flat["commit_inflight_p99"] == 4.0
    assert flat["commit_depth"] == 4.0
    assert "pipeline.commit.inflight_depth" not in tracer.snapshot()


def test_commit_inflight_absent_without_samples(traced):
    """No window samples (serial commits, numpy backend before any op):
    the flat export omits the occupancy keys rather than fabricating
    zeros a gate would then compare against."""
    flat = tracer.lifecycle_summary()["flat"]
    assert "commit_inflight_mean" not in flat
    assert "commit_depth" not in flat


def test_partial_stamps_skip_components(traced):
    """A journal-path op (no arrival/reply) contributes only the
    components whose both stamps landed — never garbage."""
    rec = tracer.op_begin()
    tracer.op_stamp(rec, tracer.OP_COMMIT_SUBMIT, 1_000_000)
    tracer.op_stamp(rec, tracer.OP_EXEC_START, 2_000_000)
    tracer.op_stamp(rec, tracer.OP_EXEC_END, 5_000_000)
    tracer.op_finish(rec)
    s = tracer.lifecycle_summary()
    assert s["components"]["queue.commit"]["mean_ms"] == pytest.approx(1.0)
    assert s["components"]["service.execute"]["mean_ms"] == pytest.approx(3.0)
    assert "queue.request" not in s["components"]
    assert s["perceived"]["count"] == 0  # no arrive/reply pair
    # Partial records must NOT dilute the gated totals distributions —
    # those are full-window (arrive→reply) ops only.
    assert "queue_wait_total_ms" not in s["flat"]
    assert "service_total_ms" not in s["flat"]


def test_finish_is_idempotent_and_stamp_first(traced):
    rec = tracer.op_begin()
    tracer.op_stamp(rec, tracer.OP_ARRIVE, 1000)
    tracer.op_stamp(rec, tracer.OP_REPLY, 2000)
    tracer.op_finish(rec)
    tracer.op_finish(rec)  # double completion application must not recount
    assert tracer.lifecycle_summary()["ops"] == 1
    rec2 = tracer.op_begin()
    tracer.op_stamp(rec2, tracer.OP_EXEC_START, 5000)
    tracer.op_stamp_first(rec2, tracer.OP_EXEC_START)  # dispatch won: no overwrite
    assert rec2.t[tracer.OP_EXEC_START] == 5000


def test_occupancy_littles_law(traced):
    """Occupancy = component time / summary window: 5 ops of 8 ms
    execute across a ~200 ms window ≈ 0.2 prepares resident."""
    import time as _time

    t0 = _time.perf_counter_ns()
    scripted_op(0, base_ns=t0)
    _time.sleep(0.2)
    scripted_op(1, base_ns=t0 + 150_000_000)
    s = tracer.lifecycle_summary()
    assert s["window_s"] >= 0.19
    occ = s["occupancy"]
    # 2 ops × 18 ms perceived over the real window between finalizes.
    assert occ["total"] == pytest.approx(0.036 / s["window_s"], rel=0.2)
    assert occ["execute"] == pytest.approx(0.018 / s["window_s"], rel=0.2)


# --- flight recorder ------------------------------------------------------


def test_flight_latency_trip_and_dump_schema(traced, tmp_path):
    """An op far beyond the running p99 trips the recorder; the dump
    holds the full ring with the documented schema, plus a Perfetto
    companion."""
    tracer.configure_flight(
        latency_mult=2.0, min_ops=4, directory=str(tmp_path), max_dumps=2
    )
    # Live device state at trip time (ISSUE 18): a dispatched-but-
    # unfinished kernel window plus a mem-ledger owner must surface in
    # the dump's device snapshot.
    tracer.device_mem_set("balances", 8192)
    dev_tok = tracer.device_dispatch("create_transfers_fast", h2d_bytes=256)
    for i in range(8):
        scripted_op(i)
    assert tracer.lifecycle_summary()["flight"]["dumps"] == 0
    scripted_op(8, exec_extra_ns=500_000_000)  # ~28x the running p99
    s = tracer.lifecycle_summary()
    assert s["flight"]["dumps"] == 1
    dumps = sorted(tmp_path.glob("tbtpu_flight_*_1.json"))
    assert len(dumps) == 1
    doc = json.loads(dumps[0].read_text())
    assert doc["reason"].startswith("latency:")
    assert len(doc["ops"]) == 9
    last = doc["ops"][-1]
    assert last["op"] == 8 and last["operation"] == 130
    assert last["n_events"] == 8190
    assert set(last["stamps"]) == set(tracer.OP_STAMP_NAMES)
    assert last["components"]["op.service.execute"] == pytest.approx(508.0)
    assert last["perceived_ms"] == pytest.approx(518.0)
    # Device snapshot rides in every dump: open windows + ledger totals.
    dev = doc["device"]
    assert dev["inflight"] == {"create_transfers_fast": 1}
    assert dev["window_depth"] == 1
    assert dev["mem"]["balances"] == 8192
    assert dev["mem_total_bytes"] == 8192
    assert dev["mem_high_water_bytes"] == 8192
    tracer.device_finish("create_transfers_fast", dev_tok)
    # Perfetto companion rides along (same perf_counter timebase).
    trace = json.loads(
        (tmp_path / (dumps[0].name[:-5] + "_trace.json")).read_text()
    )
    assert "traceEvents" in trace


def test_flight_stall_trip(traced, tmp_path):
    tracer.configure_flight(stall_ms=100.0, directory=str(tmp_path))
    scripted_op(0, exec_extra_ns=300_000_000)  # execute 308 ms > 100 ms
    dumps = list(tmp_path.glob("tbtpu_flight_*_1.json"))
    assert len(dumps) == 1
    assert json.loads(dumps[0].read_text())["reason"].startswith("stall:")


def test_flight_exception_trip(traced, tmp_path):
    tracer.configure_flight(directory=str(tmp_path))
    scripted_op(0)
    path = tracer.flight_exception("RuntimeError('stage died')")
    assert path is not None
    doc = json.loads(open(path).read())
    assert doc["reason"].startswith("exception:")
    assert doc["ops"]


def test_flight_dump_rate_limit(traced, tmp_path):
    tracer.configure_flight(directory=str(tmp_path), max_dumps=2, cooldown_s=0.0)
    for _ in range(5):
        tracer.flight_exception("boom")
    assert len(list(tmp_path.glob("tbtpu_flight_*.json"))) == 2 * 2  # +trace each


def test_ring_recycles_only_released_records(traced):
    """An evicted record still held by a store thread (op_store_done
    never ran) must NOT be recycled — a trailing stamp into a reset
    record would corrupt a fresh op. Released records DO pool."""
    tracer.configure_flight(ring=1)

    def finish_only(i):  # finalize without the store phase
        rec = tracer.op_begin()
        tracer.op_stamp(rec, tracer.OP_ARRIVE, 1000 + i)
        tracer.op_stamp(rec, tracer.OP_REPLY, 2000 + i)
        tracer.op_finish(rec)
        return rec

    a = finish_only(0)
    finish_only(1)  # evicts a (unreleased → GC, not the pool)
    assert tracer.op_begin() is not a
    b = finish_only(2)
    tracer.op_store_done(b)  # released
    finish_only(3)  # evicts b → pooled
    assert tracer.op_begin() is b


def test_configure_flight_ring_clamps_to_one(traced):
    tracer.configure_flight(ring=0)
    scripted_op(0)  # must not raise on the empty-ring eviction path
    assert len(tracer.flight_records()) == 1


def test_flight_ring_wraparound(traced):
    """The completed-op ring is bounded and holds exactly the LAST N
    records; evicted records recycle through the pool."""
    tracer.configure_flight(ring=8)
    for i in range(20):
        scripted_op(i)
    recs = tracer.flight_records()
    assert [r["op"] for r in recs] == list(range(12, 20))
    # Aggregates are NOT ring-bounded: every op counted.
    assert tracer.lifecycle_summary()["ops"] == 20


# --- disabled path --------------------------------------------------------


def test_disabled_lifecycle_is_allocation_free():
    """TIGERBEETLE_TPU_TRACE=0: op_begin returns None and every stamp/
    finish/device call returns on the flag check, allocating nothing
    (the same guard as the null-span test)."""
    import gc

    tracer.disable()
    tracer.reset()
    for _ in range(16):  # warm lazy interning
        rec = tracer.op_begin()
        tracer.op_stamp(rec, tracer.OP_ARRIVE)
        tracer.op_finish(rec)
    gc.collect()
    before = sys.getallocatedblocks()
    for _ in range(2000):
        rec = tracer.op_begin()
        assert rec is None
        tracer.op_stamp(rec, tracer.OP_ARRIVE)
        tracer.op_stamp_first(rec, tracer.OP_EXEC_START)
        tracer.op_finish(rec)
        tracer.op_store_done(rec)
        tracer.device_finish("create_transfers_fast", 0)
        tracer.device_bytes(h2d=64)
        with tracer.device_step("create_transfers_fast"):
            pass
    delta = sys.getallocatedblocks() - before
    assert delta < 32, f"disabled lifecycle allocated {delta} blocks"
    assert tracer.snapshot() == {}


def test_enabled_overhead_under_two_percent_of_batch():
    """Acceptance bar: full per-op lifecycle cost (begin + 12 stamps +
    finalize + store components + anomaly check) stays well under 2% of
    a 25 ms batch (= 500 µs/op). Typical is tens of µs; the bound
    leaves CI-noise headroom."""
    import time as _time

    tracer.reset()
    tracer.enable()
    try:
        for i in range(50):  # warm pools and arenas
            scripted_op(i)
        n = 300
        t0 = _time.perf_counter_ns()
        for i in range(n):
            rec = tracer.op_begin()
            tracer.op_meta(rec, op=i, client=1, operation=130, n_events=8190)
            for idx, off in SCRIPT:
                tracer.op_stamp(rec, idx)
            tracer.op_finish(rec)
            tracer.op_store_done(rec)
        per_op_ns = (_time.perf_counter_ns() - t0) / n
        assert per_op_ns < 500_000, f"{per_op_ns / 1e3:.1f} µs/op"
    finally:
        tracer.disable()
        tracer.reset()


# --- device-step profiler -------------------------------------------------


def test_device_entry_names_are_manifest_checked(traced):
    """An entry the jaxlint JIT_ENTRIES manifest has never heard of
    raises — kernel numbers stay attributable to declared entries."""
    with pytest.raises(ValueError, match="unknown device entry"):
        tracer.device_step("mystery_kernel")
    with pytest.raises(ValueError, match="unknown device entry"):
        tracer.device_dispatch("mystery_kernel")
    tracer.register_device_entry("mesh_kernel_0")
    with tracer.device_step("mesh_kernel_0"):
        pass
    assert "device.mesh_kernel_0" in tracer.snapshot()


def test_device_step_and_transfer_counters(traced):
    with tracer.device_step("read_balances"):
        pass
    tracer.device_bytes(h2d=1024, d2h=256)
    token = tracer.device_dispatch("create_transfers_fast", h2d_bytes=4096)
    assert token > 0
    tracer.device_finish("create_transfers_fast", token, d2h_bytes=512)
    snap = tracer.snapshot()
    assert snap["device.read_balances"]["count"] == 1
    assert snap["device.step.create_transfers_fast"]["count"] == 1
    assert snap["device.create_transfers_fast.dispatches"]["count"] == 1
    assert snap["device.h2d_bytes"]["count"] == 1024 + 4096
    assert snap["device.d2h_bytes"]["count"] == 256 + 512


def test_device_step_wired_through_state_machine(traced):
    """The balance-access jit entries report device spans + bytes when a
    device backend is present; the numpy backend stays silent."""
    jax = pytest.importorskip("jax")
    del jax
    import numpy as np

    from tigerbeetle_tpu.constants import config_by_name
    from tigerbeetle_tpu.models.state_machine import StateMachine
    from tigerbeetle_tpu import types

    sm = StateMachine(config_by_name("test_min"), backend="jax")
    ev = np.zeros(2, dtype=types.ACCOUNT_DTYPE)
    ev["id_lo"] = [1, 2]
    ev["ledger"] = 1
    ev["code"] = 10
    assert len(sm.create_accounts(ev)) == 0
    snap = tracer.snapshot()
    assert snap.get("device.register_accounts", {}).get("count", 0) >= 1
    assert snap.get("device.h2d_bytes", {}).get("count", 0) > 0


# --- live pipeline integration --------------------------------------------


def test_lifecycle_on_serial_cluster(traced):
    """Driving a real replica records the full lifecycle: components in
    the registry, records in the flight ring, decomposition consistent
    with the perceived window."""
    from tigerbeetle_tpu.testing.cluster import Cluster, account_batch
    from tigerbeetle_tpu.vsr.header import Operation

    from tests.test_cluster import do_request, setup_client

    cl = Cluster(replica_count=1)
    c = setup_client(cl)
    do_request(cl, c, Operation.CREATE_ACCOUNTS, account_batch([1, 2, 3]))
    s = tracer.lifecycle_summary()
    assert s["ops"] >= 2  # register + create_accounts
    for comp in ("queue.request", "service.wal", "service.execute",
                 "service.reply", "service.store"):
        assert comp in s["components"], comp
    assert s["perceived"]["count"] >= 2
    window = sum(
        v["mean_ms"] for k, v in s["components"].items() if ".store" not in k
    )
    assert window == pytest.approx(s["perceived"]["mean_ms"], rel=0.10)
    recs = tracer.flight_records()
    assert recs and recs[-1]["operation"] in (
        int(Operation.CREATE_ACCOUNTS), int(Operation.REGISTER),
    )


def test_lifecycle_multithreaded_store_stamps(traced):
    """Store stamps written from a worker thread (the async stage shape)
    land in the record already filed in the ring."""
    rec = tracer.op_begin()
    for idx, off in SCRIPT[:9]:
        tracer.op_stamp(rec, idx, 1_000_000_000 + off)
    tracer.op_finish(rec)  # filed before the store phase completes

    def store_side():
        tracer.op_stamp(rec, tracer.OP_STORE_SUBMIT, 1_017_100_000)
        tracer.op_stamp(rec, tracer.OP_STORE_START, 1_020_100_000)
        tracer.op_stamp(rec, tracer.OP_STORE_END, 1_026_100_000)
        tracer.op_store_done(rec)

    t = threading.Thread(target=store_side, name="store-test")
    t.start()
    t.join()
    s = tracer.lifecycle_summary()
    assert s["components"]["service.store"]["mean_ms"] == pytest.approx(6.0)
    assert tracer.flight_records()[-1]["components"][
        "op.service.store"
    ] == pytest.approx(6.0)


# --- scrape surface + tools -----------------------------------------------


def test_lifecycle_http_endpoints(traced):
    """GET /lifecycle returns the summary JSON, /flight the op ring."""
    import asyncio

    scripted_op(0)
    scripted_op(1)  # two finalizes open the occupancy window

    async def fetch(port, path):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
        await writer.drain()
        data = await reader.read()
        writer.close()
        return data

    async def go():
        server = await tracer.serve_metrics(0)
        port = server.sockets[0].getsockname()[1]
        try:
            return (
                await fetch(port, "/lifecycle"),
                await fetch(port, "/flight"),
                await fetch(port, "/metrics"),
            )
        finally:
            server.close()
            await server.wait_closed()

    lc_raw, fl_raw, metrics = asyncio.run(go())
    lc = json.loads(lc_raw.partition(b"\r\n\r\n")[2])
    assert lc["ops"] == 2
    assert lc["components"]["service.execute"]["mean_ms"] == pytest.approx(8.0)
    assert "queue_wait_total_p50_ms" in lc["flat"]
    fl = json.loads(fl_raw.partition(b"\r\n\r\n")[2])
    assert len(fl["ops"]) == 2
    # /metrics carries the occupancy gauges + the op.* span summaries.
    body = metrics.partition(b"\r\n\r\n")[2]
    assert b'name="op.occupancy.total"' in body
    assert b'event="op.service.execute"' in body


def test_trace_summary_ops_waterfall(traced, tmp_path):
    """`trace_summary --ops <dump>` renders per-op waterfalls with the
    wait/service segments and the critical-path ranking."""
    tracer.configure_flight(directory=str(tmp_path))
    for i in range(3):
        scripted_op(i)
    path = tracer.flight_exception("scripted")
    out = subprocess.run(
        [sys.executable, f"{REPO}/tools/trace_summary.py", "--ops",
         "--limit", "2", path],
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    assert "queue.request" in out.stdout
    assert "service.execute" in out.stdout
    assert "critical-path ranking" in out.stdout
    assert "op 2" in out.stdout and "op 0" not in out.stdout  # --limit 2


# --- bench gate: lifecycle metrics tolerate old baselines -----------------


class TestBenchGateLifecycle:
    OLD_BASE = {
        "end_to_end": {
            "load_accepted_tx_per_s": 300000.0,
            "perceived_p50_ms": 80.0,
            "perceived_p99_ms": 200.0,
        },
        "config5_lsm": {
            "ingest_rows_per_s": 4.0e6,
            "major_compaction_rows_per_s": 2.0e6,
        },
        "config1_default": {"steady_compiles": 0},
        "config2_zipf": {"steady_compiles": 0},
    }
    LIFECYCLE = {
        "queue_wait_total_p50_ms": 40.0,
        "service_total_p50_ms": 20.0,
        "occupancy_total": 6.0,
    }

    def _gate(self, tmp_path, monkeypatch, baseline, current):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "tool_bench_gate_lc", f"{REPO}/tools/bench_gate.py"
        )
        gate = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(gate)
        (tmp_path / "BENCH_r97.json").write_text(
            json.dumps({"parsed": {"extra": baseline}})
        )
        monkeypatch.setattr(gate, "REPO", str(tmp_path))
        return gate.main([
            "--current-json", json.dumps({"extra": current}),
            "--devhub", str(tmp_path / "devhub.jsonl"),
        ])

    def test_absent_in_old_baseline_is_na_not_failure(self, tmp_path, monkeypatch):
        cur = json.loads(json.dumps(self.OLD_BASE))
        cur["end_to_end"].update(self.LIFECYCLE)
        assert self._gate(tmp_path, monkeypatch, self.OLD_BASE, cur) == 0

    def test_regression_fails_once_baselined(self, tmp_path, monkeypatch):
        base = json.loads(json.dumps(self.OLD_BASE))
        base["end_to_end"].update(self.LIFECYCLE)
        cur = json.loads(json.dumps(base))
        cur["end_to_end"]["queue_wait_total_p50_ms"] = 60.0  # +50% wait
        assert self._gate(tmp_path, monkeypatch, base, cur) == 1

    def test_missing_after_baselined_fails(self, tmp_path, monkeypatch):
        base = json.loads(json.dumps(self.OLD_BASE))
        base["end_to_end"].update(self.LIFECYCLE)
        assert self._gate(tmp_path, monkeypatch, base, self.OLD_BASE) == 1
