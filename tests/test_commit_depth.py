"""Depth-N cross-batch commit pipelining (docs/COMMIT_PIPELINE.md):
determinism and occupancy guards for the commit stage's dispatch window.

The harness feeds sealed REQUEST messages straight into a single
replica's on_message (profile_e2e's shape — deterministic op order, the
jax backend so the split-phase device path actually dispatches) with the
CommitExecutor attached at a forced window depth. The committed chain,
the final state-machine snapshot, and the checkpoint trailer bytes must
be identical at every depth — the window moves device dispatch timing,
never the committed bytes.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tigerbeetle_tpu import types
from tigerbeetle_tpu.constants import HEADER_SIZE, Config
from tigerbeetle_tpu.io.storage import MemStorage, Zone
from tigerbeetle_tpu.vsr import header as hdr
from tigerbeetle_tpu.vsr.header import Command, Message, Operation
from tigerbeetle_tpu.vsr.replica import Replica

# TEST_MIN-sized state with the PRODUCTION pipeline depth (8): the
# window cap clamps to pipeline_max, and the depth-8 runs need all of it.
DEPTH_CONFIG = Config(
    name="depth_test",
    accounts_max=1 << 10,
    transfers_max=1 << 12,
    batch_max=64,
    journal_slot_count=64,
    pipeline_max=8,
    clients_max=4,
    checkpoint_interval=16,
    state_runs_max=2,
    message_size_max=HEADER_SIZE + 64 * 128,
    lsm_block_size=1 << 12,
    grid_block_count=1 << 12,
    grid_cache_blocks=64,
    index_memtable_rows=512,
)

CLIENT = 0xD0117
OPS = 24  # transfer batches: crosses the checkpoint interval (16)
WAVE = 8  # requests per burst = pipeline_max (no admission sheds)


def _dispatch_available() -> bool:
    """The split-phase device path needs the C staging shim + native
    account map (state_machine._ct_stage_native); without them every
    dispatch refuses and the window tests would be vacuous."""
    from tigerbeetle_tpu.lsm.store import NativeU128Map, _hostops
    from tigerbeetle_tpu.models.state_machine import make_u128_index

    return _hostops() is not None and isinstance(
        make_u128_index(64), NativeU128Map
    )


class _Bus:
    def __init__(self):
        self.replies = []

    def send_to_replica(self, r, msg):
        pass

    def send_to_client(self, c, msg):
        self.replies.append(msg)


def _plain_accounts() -> np.ndarray:
    ev = np.zeros(16, dtype=types.ACCOUNT_DTYPE)
    ev["id_lo"] = np.arange(1, 17)
    ev["ledger"] = 1
    ev["code"] = 10
    return ev


def _plain_batch(i: int) -> np.ndarray:
    t = np.zeros(4, dtype=types.TRANSFER_DTYPE)
    t["id_lo"] = 1000 + 10 * i + np.arange(4)
    t["debit_account_id_lo"] = 1 + (i % 8)
    t["credit_account_id_lo"] = 9 + (i % 8)
    t["amount_lo"] = 1 + i
    t["ledger"] = 1
    t["code"] = 7
    return t


def _drive(depth: int, ops: int = OPS):
    """One full run of simple transfers at the given window depth (0 =
    serial inline commits, no executor). Returns (commit_checksums,
    snapshot digest, trailer digest, inflight high-water)."""
    run = _run(depth, _plain_accounts(), [_plain_batch(i) for i in range(ops)])
    return run["chains"], run["snapshot"], run["trailer"], run["inflight"]


def _run(depth: int, accounts: np.ndarray, batches: list, store_stage: bool = False) -> dict:
    """Register, create the accounts, then feed `batches` in
    pipeline-deep bursts at the given window depth (0 = serial inline
    commits, no executor). What the run committed: the checksum chain,
    the snapshot's and the checkpoint trailer's digests, every reply's
    bytes, every account and every transfer id read back,
    `commit_timestamp`; and the window's high-water."""
    from collections import deque

    from tigerbeetle_tpu.vsr import snapshot as snapshot_mod

    ops = len(batches)
    config = DEPTH_CONFIG
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
    if depth:
        replica.attach_executor(posts.append, commit_depth=depth)
        assert replica.commit_depth == depth
        if store_stage:
            replica.attach_store_executor(posts.append)

    def pump():
        while posts:
            posts.popleft()()

    def settle(expect):
        import time

        t_end = time.perf_counter() + 120.0
        while len(bus.replies) < expect:
            pump()
            if time.perf_counter() > t_end:
                raise RuntimeError(
                    f"stalled: {len(bus.replies)}/{expect} replies"
                )
            time.sleep(0.0002)

    reqno = 0

    def request(operation, body=b""):
        nonlocal reqno
        reqno += 1
        h = hdr.make(
            Command.REQUEST, 0, client=CLIENT, request=reqno,
            operation=operation,
        )
        replica.on_message(Message(h, body).seal())
        pump()

    try:
        request(Operation.REGISTER)
        settle(1)
        request(Operation.CREATE_ACCOUNTS, accounts.tobytes())
        settle(2)

        # Transfer batches in pipeline-deep bursts: the stage queue holds a
        # full wave before the executor settles it, so the dispatch window
        # deterministically reaches its configured depth.
        fed = 2
        for base in range(0, ops, WAVE):
            for t in batches[base:base + WAVE]:
                request(Operation.CREATE_TRANSFERS, t.tobytes())
                fed += 1
            settle(fed)

        # Quiesce: every staged op applied, trailing store/beat drained.
        if replica.executor is not None:
            replica._quiesce_commit_stage()
            pump()
        if replica.store_executor is not None:
            replica.store_executor.drain()
        assert replica.commit_min == ops + 2, (replica.commit_min, ops + 2)
        assert replica.superblock.state.op_checkpoint >= 16

        sm = replica.state_machine
        ids = accounts["id_lo"].astype(np.uint64)
        tids = np.concatenate([t["id_lo"] for t in batches]).astype(np.uint64)
        return {
            "chains": dict(replica.commit_checksums),
            "snapshot": hdr.checksum(snapshot_mod.encode(replica)),
            "trailer": hdr.checksum(
                replica._trailer_read(replica.superblock.state.trailer_block)
            ),
            "inflight": replica.stage_inflight_max,
            "replies": [m.to_bytes() for m in bus.replies],
            "accounts": sm.lookup_accounts(ids, np.zeros(len(ids), np.uint64)).tobytes(),
            "transfers": sm.lookup_transfers(tids, np.zeros(len(tids), np.uint64)).tobytes(),
            "commit_timestamp": sm.commit_timestamp,
            "routes": dict(sm.stats),
        }
    finally:
        if replica.executor is not None:
            replica.executor.stop()
        if replica.store_executor is not None:
            replica.store_executor.stop()
        if replica.wal_writer is not None:
            replica.wal_writer.stop()


@pytest.mark.skipif(
    not _dispatch_available(),
    reason="split-phase dispatch needs the native staging shim",
)
class TestDepthDeterminism:
    """Byte-identical committed chain + snapshot + checkpoint trailer at
    every window depth, with the window PROVEN to have formed."""

    serial = None

    def _serial(self):
        if TestDepthDeterminism.serial is None:
            TestDepthDeterminism.serial = _drive(0)
        return TestDepthDeterminism.serial

    @pytest.mark.parametrize("depth", [2, 4, 8])
    def test_depth_matches_serial(self, depth):
        s_chains, s_snap, s_trailer, _ = self._serial()
        chains, snap, trailer, inflight = _drive(depth)
        assert chains == s_chains, "commit checksum chain diverged"
        assert snap == s_snap, "state-machine snapshot bytes diverged"
        assert trailer == s_trailer, "checkpoint trailer bytes diverged"
        # The window genuinely formed: batches were in flight together.
        assert inflight >= min(depth, 2), (
            f"window never formed at depth {depth} (max {inflight})"
        )
        if depth >= 4:
            assert inflight >= 3, (inflight, depth)

    def test_depth1_is_serial_single_phase(self):
        """Depth 1 skips dispatch entirely — identical bytes, window
        never deeper than the one executing batch."""
        s_chains, s_snap, s_trailer, _ = self._serial()
        chains, snap, trailer, inflight = _drive(1)
        assert chains == s_chains
        assert snap == s_snap
        assert trailer == s_trailer
        assert inflight <= 1


@pytest.mark.skipif(
    not _dispatch_available(),
    reason="split-phase dispatch needs the native staging shim",
)
class TestIdOverlapFence:
    """Adjacent batches touching the same transfer ids (the host-visible
    routing hazard): the second batch must refuse dispatch-ahead — a
    window stall — and the committed bytes must equal the serial run."""

    def test_overlapping_ids_stall_not_corrupt(self):
        runs = []
        for depth in (0, 4):
            chains, snap, trailer, _ = self._drive_overlap(depth)
            runs.append((chains, snap, trailer))
        assert runs[0] == runs[1]

    @staticmethod
    def _drive_overlap(depth):
        """Every second batch re-submits an id from the batch before it:
        the dup must be reported EXISTS identically at any depth."""
        chains, snap, trailer, _ = _drive_overlap_workload(depth)
        return chains, snap, trailer, None


def _drive_overlap_workload(depth: int):
    """Like _drive, but the transfer stream interleaves fresh batches
    with batches that duplicate the PREVIOUS batch's ids (adjacent-batch
    id overlap → dispatch fence → stall) and post/voids naming them."""
    from collections import deque

    from tigerbeetle_tpu.flags import TransferFlags
    from tigerbeetle_tpu.vsr import snapshot as snapshot_mod

    config = DEPTH_CONFIG
    zone = Zone.for_config(
        config.journal_slot_count, config.message_size_max,
        grid_block_count=config.grid_block_count,
        grid_block_size=config.lsm_block_size,
    )
    storage = MemStorage(zone.total_size, seed=777)
    Replica.format(storage, zone, 0, 0, 1)
    bus = _Bus()
    replica = Replica(
        cluster=0, replica_index=0, replica_count=1, storage=storage,
        zone=zone, config=config, bus=bus, sm_backend="jax",
    )
    replica.open()
    posts = deque()
    if depth:
        replica.attach_executor(posts.append, commit_depth=depth)

    def pump():
        while posts:
            posts.popleft()()

    def settle(expect):
        import time

        t_end = time.perf_counter() + 120.0
        while len(bus.replies) < expect:
            pump()
            if time.perf_counter() > t_end:
                raise RuntimeError("stalled")
            time.sleep(0.0002)

    reqno = 0

    def request(operation, body=b""):
        nonlocal reqno
        reqno += 1
        h = hdr.make(
            Command.REQUEST, 0, client=CLIENT, request=reqno,
            operation=operation,
        )
        replica.on_message(Message(h, body).seal())
        pump()

    request(Operation.REGISTER)
    settle(1)
    ev = np.zeros(4, dtype=types.ACCOUNT_DTYPE)
    ev["id_lo"] = np.arange(1, 5)
    ev["ledger"] = 1
    ev["code"] = 10
    request(Operation.CREATE_ACCOUNTS, ev.tobytes())
    settle(2)

    fed = 2
    for base in range(0, 16, WAVE):
        for i in range(base, base + WAVE):
            t = np.zeros(3, dtype=types.TRANSFER_DTYPE)
            if i % 2 == 0:
                ids = 6000 + 10 * i + np.arange(3)
                flags = 0
                pend = 0
            else:
                # Overlap: re-create an id from the previous batch (a
                # dup the dispatch-time bloom cannot see) plus a pending
                # post referencing it — both must fence.
                ids = np.array(
                    [6000 + 10 * (i - 1), 7000 + i, 7100 + i], np.uint64
                )
                flags = int(TransferFlags.PENDING)
                pend = 0
            t["id_lo"] = ids
            t["debit_account_id_lo"] = 1
            t["credit_account_id_lo"] = 2
            t["amount_lo"] = 1 + i
            t["ledger"] = 1
            t["code"] = 7
            t["flags"] = flags
            t["pending_id_lo"] = pend
            request(Operation.CREATE_TRANSFERS, t.tobytes())
            fed += 1
        settle(fed)

    if replica.executor is not None:
        replica._quiesce_commit_stage()
        pump()
    chains = dict(replica.commit_checksums)
    blob = snapshot_mod.encode(replica)
    st = replica.superblock.state
    trailer = (
        replica._trailer_read(st.trailer_block)
        if st.op_checkpoint else b""
    )
    inflight = replica.stage_inflight_max
    if replica.executor is not None:
        replica.executor.stop()
    return chains, hdr.checksum(blob), hdr.checksum(trailer), inflight


class TestAdaptiveDepth:
    """Depth resolution: explicit > env > backend-adaptive, clamped to
    pipeline_max and the dispatch window cap."""

    def _replica(self, backend="numpy"):
        config = DEPTH_CONFIG
        zone = Zone.for_config(
            config.journal_slot_count, config.message_size_max,
            grid_block_count=config.grid_block_count,
            grid_block_size=config.lsm_block_size,
        )
        storage = MemStorage(zone.total_size, seed=1)
        Replica.format(storage, zone, 0, 0, 1)
        return Replica(
            cluster=0, replica_index=0, replica_count=1, storage=storage,
            zone=zone, config=config, bus=_Bus(), sm_backend=backend,
        )

    def test_explicit_clamps_to_window_cap(self):
        from tigerbeetle_tpu.models.state_machine import DISPATCH_WINDOW_MAX

        r = self._replica()
        assert r._resolve_commit_depth(64) == min(
            r.config.pipeline_max, DISPATCH_WINDOW_MAX
        )
        assert r._resolve_commit_depth(-3) == 1
        assert r._resolve_commit_depth(3) == 3

    def test_env_forces(self, monkeypatch):
        monkeypatch.setenv("TIGERBEETLE_TPU_COMMIT_DEPTH", "5")
        r = self._replica()
        assert r._resolve_commit_depth(0) == 5
        # Explicit beats env.
        assert r._resolve_commit_depth(2) == 2

    def test_numpy_backend_defaults_serial(self, monkeypatch):
        monkeypatch.delenv("TIGERBEETLE_TPU_COMMIT_DEPTH", raising=False)
        r = self._replica("numpy")
        assert r._resolve_commit_depth(0) == 1
        assert r.state_machine.dispatch_depth_default() == 1

    def test_adaptive_accelerator_default(self, monkeypatch):
        """On a tpu/gpu jax backend the adaptive default opens the
        window to min(pipeline_max, 4); on xla-cpu it stays serial."""
        monkeypatch.delenv("TIGERBEETLE_TPU_COMMIT_DEPTH", raising=False)
        r = self._replica("jax")
        import jax

        want = (
            min(r.config.pipeline_max, 4)
            if jax.default_backend() != "cpu" else 1
        )
        assert r.state_machine.dispatch_depth_default() == want
        # Any non-cpu backend counts as an accelerator.
        for backend in ("tpu", "gpu"):
            monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
            assert r.state_machine.dispatch_depth_default() == min(
                r.config.pipeline_max, 4
            )


# --- exact batches in the window -------------------------------------------
#
# Exact batches that read nothing from the store (no post/void event, no
# history account) are dispatched ahead like fast ones; the others are
# refused and run at their turn behind a settled window. Whatever the
# window does, the committed bytes are the serial run's.

LIMIT = 2  # AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS
HISTORY = 8
LINKED, PENDING, POST, VOID, BALANCING_DEBIT = 1, 2, 4, 8, 16


def _exact_accounts() -> np.ndarray:
    """1-8 plain; 9-16 may not be overdrawn; 17 keeps its history."""
    ev = np.zeros(17, dtype=types.ACCOUNT_DTYPE)
    ev["id_lo"] = np.arange(1, 18)
    ev["ledger"] = 1
    ev["code"] = 10
    ev["flags"][8:16] = LIMIT
    ev["flags"][16] = HISTORY
    return ev


def _rows(first_id: int, rows) -> np.ndarray:
    """rows: (debit, credit, amount, flags[, pending_id]) each."""
    t = np.zeros(len(rows), dtype=types.TRANSFER_DTYPE)
    t["id_lo"] = first_id + np.arange(len(rows))
    t["ledger"] = 1
    t["code"] = 7
    for i, (dr, cr, amount, flags, *pending) in enumerate(rows):
        t["debit_account_id_lo"][i] = dr
        t["credit_account_id_lo"][i] = cr
        t["amount_lo"][i] = amount
        t["flags"][i] = flags
        if pending:
            t["pending_id_lo"][i] = pending[0]
    return t


# Kinds of the stream. Dispatched ahead: `chains` (linked chains of three,
# one with a failing link), `limits` (a line of dependent debits that
# outruns its deposit: refusals for funds, more than one sweep),
# `balancing` (a debit clamped to what the account holds), `pendings`
# (holds against a limit, no post/void), `fast` (the fast kernel: a mixed
# window). Refused: `post` and `void` (of the pendings batch two before, or
# `*_inflight`: of the batch just before, which may still be in flight),
# `history` (an account that keeps history), `overlap` (an id of the batch
# just before).
EXACT_STREAM = (
    "chains", "limits", "balancing", "pendings", "fast", "post", "limits", "fast",
    "pendings", "void_inflight", "chains", "history", "limits", "overlap", "balancing", "chains",
    "fast", "fast", "limits", "pendings", "chains", "post", "limits", "chains",
    "balancing", "fast", "chains", "limits", "overlap", "chains", "pendings", "post_inflight",
)


def _exact_stream() -> list:
    out, pendings_at = [], []
    for i, kind in enumerate(EXACT_STREAM):
        first = 5000 + 100 * i
        limit = 9 + i % 8  # the limited account this batch works on
        if kind == "chains":
            rows = []
            for c in range(4):
                a, b = 1 + (i + c) % 8, 1 + (i + c + 3) % 8
                rows += [(a, b, 5 + i, LINKED), (b, limit, 0 if c == i % 4 else 3, LINKED), (a, b, 2, 0)]
        elif kind == "limits":
            rows = [(1, limit, 100, 0)] + [(limit, 2, 25 + i, 0)] * 6 + [(3, limit, 40, 0), (limit, 4, 35, 0)]
        elif kind == "balancing":
            rows = [(1, limit, 50 + i, 0), (limit, 2, 1000, BALANCING_DEBIT), (limit, 3, 7, 0)]
        elif kind == "pendings":
            pendings_at.append(i)
            rows = [(1, limit, 60, 0)] + [(limit, 2 + k, 25, PENDING) for k in range(3)]
        elif kind == "fast":
            rows = [(1 + (i + k) % 8, 1 + (i + k + 1) % 8, 1 + i, 0) for k in range(4)]
        elif kind in ("post", "void", "post_inflight", "void_inflight"):
            held = pendings_at[-1]
            assert (held == i - 1) == kind.endswith("_inflight"), (i, kind, held)
            flag = POST if kind.startswith("post") else VOID
            rows = [(1, 2, 4, 0), (0, 0, 0, flag, 5000 + 100 * held + 1), (3, limit, 9, 0),
                    (0, 0, 0, flag, 5000 + 100 * held + 3)]  # that hold was refused: not found
        elif kind == "history":
            rows = [(1, 17, 5 + i, 0), (17, 2, 3, 0), (3, limit, 8, 0)]
        else:
            assert kind == "overlap"
            rows = [(1, limit, 10, 0), (limit, 2, 5, 0), (2, 3, 1, 0)]
        t = _rows(first, rows)
        if kind == "overlap":
            t[1] = out[-1][1]  # a row of the batch before, byte for byte: `exists`
        assert len(t) <= DEPTH_CONFIG.batch_max
        out.append(t)
    return out


DISPATCHED = sum(k in ("chains", "limits", "balancing", "pendings", "fast") for k in EXACT_STREAM)


@pytest.mark.skipif(
    not _dispatch_available(),
    reason="split-phase dispatch needs the native staging shim",
)
class TestExactBatchesInTheWindow:
    serial = None

    def _serial(self):
        if TestExactBatchesInTheWindow.serial is None:
            TestExactBatchesInTheWindow.serial = _run(0, _exact_accounts(), _exact_stream())
        return TestExactBatchesInTheWindow.serial

    def test_the_stream_is_what_it_says(self):
        """Serial run: every kind took its route, and the balance check,
        the chains and the duplicate answered."""
        from tigerbeetle_tpu.results import CreateTransferResult as TR

        run = self._serial()
        fast = EXACT_STREAM.count("fast")
        serial = EXACT_STREAM.count("overlap")
        assert run["routes"] == {
            "fast_batches": fast, "exact_batches": len(EXACT_STREAM) - fast - serial,
            "serial_batches": serial, "bail_batches": 0,
        }
        codes = set()
        for reply in run["replies"][2:]:
            res = np.frombuffer(reply[HEADER_SIZE:], dtype=types.EVENT_RESULT_DTYPE)
            codes |= set(res["result"].tolist())
        assert {int(TR.EXCEEDS_CREDITS), int(TR.LINKED_EVENT_FAILED), int(TR.EXISTS),
                int(TR.PENDING_TRANSFER_NOT_FOUND)} <= codes

    @pytest.mark.parametrize("depth,store_stage", [
        (1, False), (2, False), (4, False), (8, False), (4, True),
    ])
    def test_depth_matches_serial(self, depth, store_stage):
        """Replies, balances, stored transfers, `commit_timestamp`, the
        checksum chain, the snapshot and the checkpoint trailer, byte
        for byte; with the store thread attached too."""
        serial = self._serial()
        run = _run(depth, _exact_accounts(), _exact_stream(), store_stage=store_stage)
        inflight = run.pop("inflight")
        for key, value in run.items():
            assert value == serial[key], f"{key} diverged at depth {depth}"
        if depth == 1:
            assert inflight <= 1
        else:
            assert inflight >= 2, f"window never formed at depth {depth}"

    def test_counters_read_what_happened(self, traced):
        """Depth 4: every deferring exact batch went through the pair,
        every other was refused under its reason, and no barrier was
        taken with a handle outstanding."""
        from tigerbeetle_tpu.models.state_machine import StateMachine

        barrier = StateMachine.store_barrier
        outstanding = []

        def watched(sm):
            outstanding.append(len(sm._ct_pending))
            return barrier(sm)

        StateMachine.store_barrier = watched
        try:
            run = _run(4, _exact_accounts(), _exact_stream())
        finally:
            StateMachine.store_barrier = barrier
        assert run["replies"] == self._serial()["replies"]
        assert outstanding and not any(outstanding)
        snap = traced.snapshot()
        count = lambda name: snap.get(name, {}).get("count", 0)  # noqa: E731
        refused = {k[len("sm.ct.dispatch_refused."):]: v["count"]
                   for k, v in snap.items() if k.startswith("sm.ct.dispatch_refused.")}
        n = EXACT_STREAM.count
        assert refused.pop("pv") == n("post") + n("post_inflight") + n("void_inflight")
        assert refused.pop("history") == n("history")
        # the batch before it may have settled already: then its ids are stored
        assert refused.pop("overlap", 0) + refused.pop("stored_id", 0) == n("overlap")
        assert not refused, refused
        assert count("sm.exact.dispatched_ahead") == DISPATCHED - n("fast")
        assert count("sm.route.exact_batches") == len(EXACT_STREAM) - n("fast") - n("overlap")
        assert count("sm.exact.store_deferred") == count("sm.exact.dispatched_ahead")
        assert count("sm.exact.sweeps") > count("sm.route.exact_batches")
        for kernel in ("create_transfers_exact", "create_transfers_fast"):
            assert count(f"device.{kernel}.dispatches") == count(f"device.step.{kernel}") > 0

    def test_fault_at_settle_abandons_exact_handles(self, traced):
        """An exact batch's finish (its deferred store, its beat) meets a
        GridReadFault with a fast and an exact batch dispatched behind it:
        the stage hands both back unexecuted, the state token is the one
        the committed batch left, and each device window is closed under
        its own kernel's name."""
        from collections import deque

        from tigerbeetle_tpu.io.grid import GridReadFault

        config = DEPTH_CONFIG
        zone = Zone.for_config(
            config.journal_slot_count, config.message_size_max,
            grid_block_count=config.grid_block_count,
            grid_block_size=config.lsm_block_size,
        )

        def state_machine_of(replica):
            replica.open()
            sm = replica.state_machine
            assert len(sm.create_accounts(_exact_accounts(), timestamp=100)) == 0
            return sm

        def replica():
            storage = MemStorage(zone.total_size, seed=99)
            Replica.format(storage, zone, 0, 0, 1)
            return Replica(
                cluster=0, replica_index=0, replica_count=1, storage=storage,
                zone=zone, config=config, bus=_Bus(), sm_backend="jax",
            )

        kinds = ("limits", "fast", "chains")
        stream = _exact_stream()
        batches = [stream[EXACT_STREAM.index(kind)] for kind in kinds]
        r = replica()
        sm, ref = state_machine_of(r), state_machine_of(replica())
        r.attach_executor(deque().append, commit_depth=4)
        r.executor.stop()  # the stage's steps are taken by hand below
        jobs = []
        for i, t in enumerate(batches):
            h = hdr.make(
                Command.PREPARE, 0, client=CLIENT, request=1 + i, op=3 + i,
                operation=Operation.CREATE_TRANSFERS, timestamp=1000 + 100 * i,
            )
            jobs.append({"op": 3 + i, "msg": Message(h, t.tobytes()), "entry": None, "lc": None})
        fault = GridReadFault(7, None)

        def finish_commit(lc):
            raise fault

        r._finish_commit = finish_commit
        traced.reset()
        assert r._stage_process(jobs[0]) == (None, [], True)
        assert r._stage_process(jobs[1]) == (None, [], True)
        assert [h["kernel"] for h in sm._ct_pending] == [
            "create_transfers_exact", "create_transfers_fast",
        ]
        # The third is the second exact handle: the bound settles the first.
        publish, leftovers, ok = r._stage_process(jobs[2])
        assert not ok and publish == {"op": 3, "finish_fault": fault, "lc": None}
        assert leftovers == jobs[1:] and not any("_handle" in j for j in leftovers)
        assert not sm._ct_pending and not r._stage_window
        snap = traced.snapshot()
        for kernel, n in (("create_transfers_exact", 2), ("create_transfers_fast", 1)):
            assert snap[f"device.{kernel}.dispatches"]["count"] == n
            assert snap[f"device.step.{kernel}"]["count"] == n
        # The first batch committed; the others re-execute on what it left.
        del r._finish_commit
        sm.flush_deferred()
        outs = [np.frombuffer(jobs[0]["spec"]["body"], dtype=types.EVENT_RESULT_DTYPE)]
        outs += [sm.create_transfers(b, timestamp=1000 + 100 * i)
                 for i, b in enumerate(batches) if i]
        refs = [ref.create_transfers(b, timestamp=1000 + 100 * i) for i, b in enumerate(batches)]
        assert [o.tobytes() for o in outs] == [x.tobytes() for x in refs]
        ids = np.arange(1, 18, dtype=np.uint64)
        zeros = np.zeros(len(ids), np.uint64)
        assert sm.lookup_accounts(ids, zeros).tobytes() == ref.lookup_accounts(ids, zeros).tobytes()
