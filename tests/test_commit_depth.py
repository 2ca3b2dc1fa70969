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


def _drive(depth: int, ops: int = OPS):
    """One full run at the given window depth (0 = serial inline
    commits, no executor). Returns (commit_checksums, snapshot digest,
    trailer digest, inflight high-water)."""
    from collections import deque

    from tigerbeetle_tpu.vsr import snapshot as snapshot_mod

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

    request(Operation.REGISTER)
    settle(1)
    ev = np.zeros(16, dtype=types.ACCOUNT_DTYPE)
    ev["id_lo"] = np.arange(1, 17)
    ev["ledger"] = 1
    ev["code"] = 10
    request(Operation.CREATE_ACCOUNTS, ev.tobytes())
    settle(2)

    # Transfer batches in pipeline-deep bursts: the stage queue holds a
    # full wave before the executor settles it, so the dispatch window
    # deterministically reaches its configured depth.
    fed = 2
    for base in range(0, ops, WAVE):
        for i in range(base, min(base + WAVE, ops)):
            t = np.zeros(4, dtype=types.TRANSFER_DTYPE)
            t["id_lo"] = 1000 + 10 * i + np.arange(4)
            t["debit_account_id_lo"] = 1 + (i % 8)
            t["credit_account_id_lo"] = 9 + (i % 8)
            t["amount_lo"] = 1 + i
            t["ledger"] = 1
            t["code"] = 7
            request(Operation.CREATE_TRANSFERS, t.tobytes())
            fed += 1
        settle(fed)

    # Quiesce: every staged op applied, trailing store/beat drained.
    if replica.executor is not None:
        replica._quiesce_commit_stage()
        pump()
    assert replica.commit_min == ops + 2, (replica.commit_min, ops + 2)
    assert replica.superblock.state.op_checkpoint >= 16

    chains = dict(replica.commit_checksums)
    blob = snapshot_mod.encode(replica)
    trailer = replica._trailer_read(replica.superblock.state.trailer_block)
    inflight_max = replica.stage_inflight_max
    if replica.executor is not None:
        replica.executor.stop()
    if replica.wal_writer is not None:
        replica.wal_writer.stop()
    return chains, hdr.checksum(blob), hdr.checksum(trailer), inflight_max


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
