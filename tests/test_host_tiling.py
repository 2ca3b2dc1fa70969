"""The host threads' time adds up (docs/OBSERVABILITY.md, "Thread tiling").

What the tracer promises once it is on: every second the commit thread and
the store thread are not waiting belongs to a named leaf span; those spans
(and the parents and waits around them) sit on the profiler's clock beside
the device planes; a compile is a span on the thread that compiled; the
exact kernel's sweeps are counted; the time with no dispatch window open is
`device.unfed`. And, off, none of it exists.

The harness is test_commit_depth's: sealed requests fed straight into one
replica on the jax backend, here with BOTH worker threads attached (commit
executor and store executor), at test_min size.
"""

import glob
import os
import subprocess
import sys
import threading
import time
from collections import deque

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tigerbeetle_tpu import tracer, types
from tigerbeetle_tpu.constants import TEST_MIN
from tigerbeetle_tpu.flags import AccountFlags, TransferFlags
from tigerbeetle_tpu.io.storage import MemStorage, Zone
from tigerbeetle_tpu.vsr import header as hdr
from tigerbeetle_tpu.vsr.header import Command, Message, Operation
from tigerbeetle_tpu.vsr.replica import Replica

CLIENT = 0x7111E
N = TEST_MIN.batch_max
WARM = 4  # batches before the measured stretch: every shape compiled

from tigerbeetle_tpu.tracer import (  # noqa: E402 — the tiling's own tables
    COMMIT_LEAVES, COMMIT_WAITS, STORE_LEAVES, STORE_WAITS,
)


def _native_staging() -> bool:
    from tigerbeetle_tpu.lsm.store import NativeU128Map, _hostops
    from tigerbeetle_tpu.models.state_machine import make_u128_index

    return _hostops() is not None and isinstance(make_u128_index(64), NativeU128Map)


needs_staging = pytest.mark.skipif(
    not _native_staging(), reason="the tiled path needs the native staging shim"
)


@pytest.fixture
def traced():
    was = tracer.enabled()
    tracer.enable()
    tracer.reset()
    yield
    tracer.reset()
    if not was:
        tracer.disable()


class _Bus:
    def __init__(self):
        self.replies = []

    def send_to_replica(self, r, msg):
        pass

    def send_to_client(self, c, msg):
        self.replies.append(msg)


# The exact route's two cases: a batch with a post/void event waits for the
# store and writes it inline; a batch without one (linked chains only) reads
# nothing from the store and hands its rows to the store thread.
EXACT = ("exact", "exact_chains")


def _counter(route: str) -> str:
    return ("exact" if route in EXACT else route) + "_batches"


def _batch(route: str, i: int) -> np.ndarray:
    t = np.zeros(N, dtype=types.TRANSFER_DTYPE)
    t["id_lo"] = 1000 + N * i + np.arange(N)
    t["debit_account_id_lo"] = 1 + (np.arange(N) % 8)
    t["credit_account_id_lo"] = 9 + (np.arange(N) % 8)
    t["amount_lo"] = 1 + i
    t["ledger"] = 1
    t["code"] = 7
    if route in EXACT:  # linked chains, a pending and, in one case, a post of the batch before
        t["flags"][0:8:2] = int(TransferFlags.LINKED)
        t["flags"][10] = int(TransferFlags.PENDING)
        if route == "exact" and i > 0:
            t["flags"][11] = int(TransferFlags.POST_PENDING_TRANSFER)
            t["pending_id_lo"][11] = 1000 + N * (i - 1) + 10
    if route == "serial":  # a duplicate id inside the batch
        t["id_lo"][1] = t["id_lo"][0]
    return t


def _drive(route: str, depth: int, ops: int = 40) -> dict:
    """WARM batches, then `ops - WARM` measured ones. Returns what each
    thread recorded over the measured stretch, its length, the routes taken
    and the committed bytes' digests."""
    from tigerbeetle_tpu.vsr import snapshot as snapshot_mod

    config = TEST_MIN
    zone = Zone.for_config(
        config.journal_slot_count, config.message_size_max,
        grid_block_count=config.grid_block_count,
        grid_block_size=config.lsm_block_size,
    )
    storage = MemStorage(zone.total_size, seed=4242)
    Replica.format(storage, zone, 0, 0, 1)
    bus = _Bus()
    replica = Replica(
        cluster=0, replica_index=0, replica_count=1, storage=storage,
        zone=zone, config=config, bus=bus, sm_backend="jax",
    )
    replica.open()
    posts = deque()
    replica.attach_executor(posts.append, commit_depth=depth)
    replica.attach_store_executor(posts.append)

    def pump():
        while posts:
            posts.popleft()()

    def settle(expect):
        t_end = time.perf_counter() + 120.0
        while len(bus.replies) < expect:
            pump()
            assert time.perf_counter() < t_end, f"stalled at {len(bus.replies)}/{expect}"
            time.sleep(0.002)  # a slow pump: the workers keep the interpreter

    def quiesce():
        replica._quiesce_commit_stage()
        pump()
        replica.store_executor.drain()

    reqno = 0

    def request(operation, body=b""):
        nonlocal reqno
        reqno += 1
        h = hdr.make(Command.REQUEST, 0, client=CLIENT, request=reqno, operation=operation)
        replica.on_message(Message(h, body).seal())
        pump()

    try:
        request(Operation.REGISTER)
        settle(1)
        ev = np.zeros(16, dtype=types.ACCOUNT_DTYPE)
        ev["id_lo"] = np.arange(1, 17)
        ev["ledger"] = 1
        ev["code"] = 10
        request(Operation.CREATE_ACCOUNTS, ev.tobytes())
        settle(2)
        fed = 2
        for i in range(WARM):
            request(Operation.CREATE_TRANSFERS, _batch(route, i).tobytes())
            fed += 1
        settle(fed)
        quiesce()
        tracer.reset()
        t0 = time.perf_counter_ns()
        for base in range(WARM, ops, 4):
            for i in range(base, min(base + 4, ops)):
                request(Operation.CREATE_TRANSFERS, _batch(route, i).tobytes())
                fed += 1
            settle(fed)
        quiesce()
        elapsed = time.perf_counter_ns() - t0
        snap = tracer.snapshot()
        return {
            "elapsed_ns": elapsed,
            "threads": tracer.by_thread(),
            "routes": {k[len("sm.route."):]: v["count"] for k, v in snap.items()
                       if k.startswith("sm.route.")},
            "deferred": snap.get("sm.exact.store_deferred", {}).get("count"),
            "chain": dict(replica.commit_checksums),
            "digest": hdr.checksum(snapshot_mod.encode(replica)),
        }
    finally:
        replica.executor.stop()
        replica.store_executor.stop()
        if replica.wal_writer is not None:
            replica.wal_writer.stop()


def _tiled_share(run: dict, thread: str, leaves, waits) -> float:
    """Leaf seconds over busy seconds (elapsed minus waits), whole stretch."""
    spans = run["threads"][thread]
    seconds = lambda events: sum(spans.get(e, (0, 0))[1] for e in events)  # noqa: E731
    busy = run["elapsed_ns"] - seconds(waits)
    return seconds(leaves) / busy


def _cycle_share(thread: str, leaves, waits, boundary: str) -> float:
    """The same share from the thread's span ring, job by job (a job's
    cycle runs from one `boundary` span's end to the next one's), with the
    tenth of the cycles that have the most unnamed time left out: at this
    size the other thread's turn at the interpreter lock can cost one cycle
    more than ten cycles last, and the question here is whether a stage of
    the work lacks a span, which shows in EVERY cycle."""
    evs = sorted((t0, t1, e) for e, name, _tid, t0, t1 in tracer.trace_events()
                 if name == thread and (e in leaves or e in waits or e == boundary))
    ends = [t1 for _t0, t1, e in evs if e == boundary]
    cycles = []
    for a, b in zip(ends, ends[1:]):
        inside = [(min(t1, b) - max(t0, a), e) for t0, t1, e in evs if t0 < b and t1 > a]
        busy = (b - a) - sum(ns for ns, e in inside if e in waits)
        if busy > 0:
            cycles.append((busy - sum(ns for ns, e in inside if e in leaves), busy))
    assert len(cycles) >= 100, len(cycles)
    kept = sorted(cycles)[: len(cycles) * 9 // 10]
    return 1.0 - sum(hole for hole, _busy in kept) / sum(busy for _hole, busy in kept)


TILED = 204  # batches a tiling stretch commits, WARM of them before it starts


@needs_staging
@pytest.mark.parametrize("route,thread", [
    ("fast", "commit-executor"),
    ("exact", "commit-executor"),
    ("exact_chains", "commit-executor"),
    ("serial", "commit-executor"),
    ("fast", "store-executor"),
])
def test_leaf_spans_tile_the_thread(traced, route, thread):
    """Leaf seconds >= 90% of the thread's busy seconds (elapsed minus its
    waits), at commit depth 2: the split-phase path a TPU serves by. At this
    size a batch's leaves last 20 to 300 microseconds each, so the
    interpreter's own steps between them are some 7% of busy; the best of
    three stretches of 200 batches is judged. A stage with no span at all
    reads tens of percent short in every one."""
    leaves, waits, boundary = (
        (COMMIT_LEAVES, COMMIT_WAITS, "stage.complete") if thread == "commit-executor"
        else (STORE_LEAVES, STORE_WAITS, "stage.store_async")
    )
    best = 0.0
    for _attempt in range(3):
        run = _drive(route, 2, ops=TILED)
        assert run["routes"] == {_counter(route): TILED - WARM}
        best = max(best, _cycle_share(thread, leaves, waits, boundary))
        if best >= 0.90:
            break
    assert best >= 0.90, f"{thread} on the {route} route: leaves cover {best:.1%} of busy"


ROUTE_LEAVES = {  # route, commit depth -> the commit thread's leaves, each at least once a batch
    ("fast", 2): ("sm.ct.stage", "sm.ct.dispatch", "sm.ct.sync", "sm.ct.post"),
    ("fast", 1): ("sm.ct.stage", "sm.ct.dispatch", "sm.ct.sync", "sm.ct.post"),
    ("exact", 2): ("sm.ct.prefetch", "sm.ct.stage", "sm.ct.dispatch", "sm.ct.sync",
                   "sm.ct.post", "sm.store.barrier"),
    ("exact_chains", 2): ("sm.ct.prefetch", "sm.ct.stage", "sm.ct.dispatch", "sm.ct.sync",
                          "sm.ct.post"),
    ("serial", 2): ("sm.ct.stage", "sm.ct.serial", "sm.store.barrier"),
}


@needs_staging
@pytest.mark.parametrize("route,depth", sorted(ROUTE_LEAVES))
def test_every_stage_of_the_route_has_its_leaf(traced, route, depth):
    """Each route records its own leaves once a batch or more (a batch the
    dispatch-ahead refuses is staged twice), `replica.execute.tail`,
    `stage.reply` and `stage.complete` beside them and `sm.beat` on the store thread; no leaf
    lies inside another on its thread (else the sum counts a second twice),
    and the leaves never reach past the thread's busy seconds. (A wait in
    progress when the stretch began is recorded whole when it ends, so busy
    seconds read that wait's head too short: 5% of room.)"""
    batches = 16 - WARM
    run = _drive(route, depth, ops=16)
    commit, store = run["threads"]["commit-executor"], run["threads"]["store-executor"]
    for event in ROUTE_LEAVES[route, depth] + (
            "replica.execute.tail", "stage.reply", "stage.complete"):
        assert commit.get(event, (0, 0))[0] >= batches, (event, commit.get(event))
    assert commit["replica.execute"][0] == store["sm.beat"][0] == batches
    if route in ("fast", "exact_chains"):  # the store thread applies the batch
        assert store["sm.store.log"][0] == batches and "sm.store.log" not in commit
        assert "sm.store.barrier" not in commit
    else:  # it is inline, in sm.ct.post (exact) or sm.ct.serial, behind the barrier
        assert commit["sm.store.log"][0] == batches and "sm.store.log" not in store
        assert commit["sm.store.barrier"][0] == batches
    # every exact batch says whether it deferred its store; no other route does
    assert run["deferred"] == {"exact": 0, "exact_chains": batches}.get(route)
    assert _tiled_share(run, "commit-executor", COMMIT_LEAVES, COMMIT_WAITS) <= 1.05
    mine = sorted(
        (t0, t1, event) for event, name, _tid, t0, t1 in tracer.trace_events()
        if name == "commit-executor" and event in COMMIT_LEAVES
    )
    for (_a0, a1, a), (b0, _b1, b) in zip(mine, mine[1:]):
        assert b0 >= a1, f"{b} starts inside {a}"


# --- compiles ------------------------------------------------------------------


@pytest.mark.parametrize("where", ["worker", "main"])
def test_compile_is_a_span_on_the_thread_that_compiled(traced, where):
    import jax
    import jax.numpy as jnp

    width = 977 if where == "worker" else 983  # a shape nothing else uses

    @jax.jit
    def fresh(x):
        return (x * 3 + 1).sum()

    def call():
        fresh(jnp.zeros(width, jnp.int32)).block_until_ready()

    def run():
        if where == "main":
            call()
        else:
            t = threading.Thread(target=call, name="compiling-worker")
            t.start()
            t.join(120)
            assert not t.is_alive()

    name = threading.current_thread().name if where == "main" else "compiling-worker"
    others = lambda: sum(  # noqa: E731
        spans.get("device.compile", (0, 0))[0]
        for thread, spans in tracer.by_thread().items() if thread != name
    )
    before_others = others()
    run()
    count, total_ns = tracer.by_thread()[name]["device.compile"]
    assert count >= 1 and total_ns > 0
    assert others() == before_others  # nobody else is charged
    run()  # warm: the same shape compiles nothing
    assert tracer.by_thread()[name]["device.compile"][0] == count
    assert 'tbtpu_span_seconds_count{event="device.compile"}' in tracer.prometheus_text()


# --- sweeps --------------------------------------------------------------------


def _limited_accounts(sm, count: int) -> None:
    ev = np.zeros(count, dtype=types.ACCOUNT_DTYPE)
    ev["id_lo"] = np.arange(1, count + 1)
    ev["ledger"] = 1
    ev["code"] = 10
    ev["flags"][1:] = int(AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS)
    assert len(sm.create_accounts(ev, timestamp=count)) == 0


@pytest.mark.parametrize("depth", [0, 1, 2, 4])
def test_sweeps_counter_is_the_kernels_own_carry(traced, monkeypatch, depth):
    """A line of `depth` transfers, each funded by the one before through an
    account that may not be overdrawn (account k pays account k + 1, and
    only account 1 has no limit): the fixed point needs one more sweep per
    link. `depth` 0 is an exact batch with no such line at all."""
    from tigerbeetle_tpu.models.state_machine import StateMachine
    from tigerbeetle_tpu.ops import commit as commit_ops

    sm = StateMachine(TEST_MIN, backend="jax")
    _limited_accounts(sm, 8)
    carried = []
    real = commit_ops.create_transfers_exact

    def spy(*args, **kw):
        out = real(*args, **kw)
        carried.append(int(out[-1]))
        return out

    monkeypatch.setattr(commit_ops, "create_transfers_exact", spy)
    if depth == 0:  # four transfers out of the one account without a limit
        debit, credit = np.ones(4, np.uint64), 2 + np.arange(4, dtype=np.uint64)
    else:
        debit = 1 + np.arange(depth, dtype=np.uint64)
        credit = debit + 1
    t = np.zeros(len(debit), dtype=types.TRANSFER_DTYPE)
    t["id_lo"] = 500 + np.arange(len(debit))
    t["debit_account_id_lo"] = debit
    t["credit_account_id_lo"] = credit
    t["amount_lo"] = 10
    t["ledger"] = 1
    t["code"] = 7
    before = tracer.snapshot().get("sm.exact.sweeps", {"count": 0})["count"]
    results = sm.create_transfers(t, timestamp=1000)
    assert len(results) == 0, results  # every link was funded in the end
    assert sm.stats["exact_batches"] == 1 and len(carried) == 1
    after = tracer.snapshot()["sm.exact.sweeps"]["count"]
    assert after - before == carried[0] >= 1
    if depth >= 2:
        assert carried[0] >= depth  # one sweep per link, and the one that sees it stable


# --- device fed / unfed --------------------------------------------------------


def _unfed():
    rec = tracer.snapshot().get("device.unfed")
    return (0, 0.0) if rec is None else (rec["count"], rec["total_ms"])


ENTRY = "create_transfers_fast"
GAP = 0.02


@pytest.mark.parametrize("case", ["overlapping", "nested", "abandoned", "reset"])
def test_unfed_is_the_time_with_no_window_open(traced, case):
    first = tracer.device_dispatch(ENTRY)
    assert _unfed() == (0, 0.0)  # before the first window nothing was "unfed"
    if case == "overlapping":
        second = tracer.device_dispatch("create_transfers_exact")  # another entry, same count
        tracer.device_finish(ENTRY, first)
        time.sleep(GAP)  # `second` is still open: fed
        third = tracer.device_dispatch(ENTRY)
        assert _unfed() == (0, 0.0)
        tracer.device_finish("create_transfers_exact", second)
        tracer.device_finish(ENTRY, third)
    elif case == "nested":
        with tracer.device_step("read_balances"):  # a blocking entry inside a window
            pass
        time.sleep(GAP)  # `first` is still open: fed
        with tracer.device_step("read_balances"):
            pass
        assert _unfed() == (0, 0.0)
        tracer.device_finish(ENTRY, first)
    elif case == "abandoned":
        # More windows than an entry may hold open are never finished: the
        # oldest are evicted, and an evicted window counts as closed.
        tokens = [first] + [tracer.device_dispatch(ENTRY) for _ in range(80)]
        for token in tokens:  # the evicted ones among them close nothing twice
            tracer.device_finish(ENTRY, token)
        assert tracer.device_inflight()["window_depth"] == 0
    else:
        tracer.reset()  # forgets the open window
    t0 = time.perf_counter()
    time.sleep(GAP)
    with tracer.device_step("read_balances"):  # 0 -> 1: closes the unfed stretch
        waited = (time.perf_counter() - t0) * 1e3
    count, total_ms = _unfed()
    if case == "reset":
        assert (count, total_ms) == (0, 0.0)  # no window has closed since the reset
        return
    assert count == 1
    assert GAP * 1e3 * 0.9 <= total_ms <= waited + 1.0
    # and the next stretch is counted from the step's end, not from the first close
    time.sleep(GAP)
    tracer.device_finish(ENTRY, tracer.device_dispatch(ENTRY))
    count, again_ms = _unfed()
    assert count == 2 and again_ms - total_ms >= GAP * 1e3 * 0.9


# --- one clock with the device trace -------------------------------------------


def _host_events(trace_dir: str) -> tuple:
    """({name: [(start_ns, end_ns)]} over the host planes, first, last)."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    found, first, last = {}, None, None
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                a, b = int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns)
                first = a if first is None else min(first, a)
                last = b if last is None else max(last, b)
                if plane.name.startswith("/host:"):
                    found.setdefault(ev.name, []).append((a, b))
    return found, first, last


@pytest.mark.parametrize("event", sorted(tracer.ANNOTATED_SPANS))
def test_annotated_span_lands_in_the_profilers_trace(traced, tmp_path, event):
    """The profiler session the benchmark's child starts (host tracer level
    1, no Python tracer) holds a host event named for the span, inside the
    trace's own first-to-last interval, and none for a span off the list."""
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        jnp.arange(8).sum().block_until_ready()

        def work():
            with tracer.span(event):
                with tracer.span("lsm.not.on.the.list"):
                    time.sleep(0.001)

        t = threading.Thread(target=work, name="commit-executor")
        t.start()
        t.join(60)
        jnp.arange(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    found, first, last = _host_events(str(tmp_path))
    assert "lsm.not.on.the.list" not in found
    ((a, b),) = found[event]
    assert first <= a < b <= last and b - a >= 1_000_000
    assert tracer.snapshot()[event]["count"] == 1  # and it is still a span of the registry


# --- off means off -------------------------------------------------------------


@needs_staging
@pytest.mark.parametrize("route", ["fast", "exact", "exact_chains", "serial"])
def test_on_vs_off_byte_identical(route):
    """The spans, the sweeps read and the window accounting observe the
    commit path and never steer it: the same batches commit the same bytes
    with the tracer off and on, and off, no thread gets an arena."""
    was = tracer.enabled()
    try:
        tracer.disable()
        tracer.reset()
        off = _drive(route, 2, ops=12)
        assert off["threads"] == {} and off["routes"] == {}
        tracer.enable()
        tracer.reset()
        on = _drive(route, 2, ops=12)
        assert on["routes"] == {_counter(route): 12 - WARM}
        assert "sm.ct.stage" in on["threads"]["commit-executor"]
        assert (off["chain"], off["digest"]) == (on["chain"], on["digest"])
    finally:
        tracer.reset()
        if was:
            tracer.enable()
        else:
            tracer.disable()


def test_off_registers_no_listener_and_resolves_no_annotation():
    """In a process of its own (a listener cannot be taken off again): with
    the tracer off, the jax backend commits a fast and an exact batch, and
    the tracer has no arena, no `jax.monitoring` listener and no annotation
    class; switched on, it has all three."""
    code = r"""
import numpy as np
import jax
from jax._src import monitoring
from tigerbeetle_tpu import tracer, types
from tigerbeetle_tpu.constants import TEST_MIN
from tigerbeetle_tpu.flags import TransferFlags
from tigerbeetle_tpu.models.state_machine import StateMachine

assert not tracer.enabled()
listeners = lambda: len(monitoring.get_event_duration_listeners())
at_start = listeners()
sm = StateMachine(TEST_MIN, backend="jax")
ev = np.zeros(4, dtype=types.ACCOUNT_DTYPE)
ev["id_lo"] = np.arange(1, 5); ev["ledger"] = 1; ev["code"] = 10
assert len(sm.create_accounts(ev, timestamp=4)) == 0
for i, flags in enumerate((0, int(TransferFlags.LINKED))):
    t = np.zeros(2, dtype=types.TRANSFER_DTYPE)
    t["id_lo"] = 100 + 2 * i + np.arange(2)
    t["debit_account_id_lo"] = 1; t["credit_account_id_lo"] = 2
    t["amount_lo"] = 1; t["ledger"] = 1; t["code"] = 7
    t["flags"][0] = flags
    assert len(sm.create_transfers(t, timestamp=10 + 10 * i)) == 0
assert sm.stats["fast_batches"] == 1 and sm.stats["exact_batches"] == 1
assert tracer.by_thread() == {} and tracer.snapshot() == {}
assert listeners() == at_start and tracer._annotation is None
tracer.enable()
assert listeners() == at_start + 1 and tracer._annotation is jax.profiler.TraceAnnotation
tracer.enable(); tracer.attach_jax()
assert listeners() == at_start + 1
print("OFF_IS_OFF")
"""
    env = {k: v for k, v in os.environ.items() if k != "TIGERBEETLE_TPU_TRACE"}
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=300,
        cwd=REPO, env={**env, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OFF_IS_OFF" in out.stdout
