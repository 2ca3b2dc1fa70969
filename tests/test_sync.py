"""Chunked state-sync and pickle-free snapshot tests.

Covers VERDICT r2 task 4: the checkpoint blob uses only fixed structured
dtypes (np.load(allow_pickle=False) — no pickle anywhere), sync of a state
larger than one message frame flows as multiple checksummed chunks, and a
corrupted chunk is dropped by message verification and re-requested.
Reference: checkpoint_trailer.zig, sync.zig, docs/internals/sync.md.
"""

import numpy as np

from tigerbeetle_tpu import types
from tigerbeetle_tpu.constants import TEST_MIN
from tigerbeetle_tpu.testing.cluster import Cluster, account_batch, transfer_batch
from tigerbeetle_tpu.vsr import header as hdr
from tigerbeetle_tpu.vsr import snapshot
from tigerbeetle_tpu.vsr.header import Command, Header, Message, Operation


def do_request(cluster, client, operation, body, max_ticks=40_000):
    client.request(operation, body)
    cluster.run_until(lambda: client.idle, max_ticks)
    return client.replies[-1]


def setup_client(cluster, cid=100):
    c = cluster.clients[cid]
    c.register()
    cluster.run_until(lambda: c.registered)
    return c


def grow_state(cl, c, accounts=120, transfer_batches=28, id_base=1000,
               make_accounts=True):
    """Commit enough distinct state to exceed several TEST_MIN frames."""
    if make_accounts:
        ids = list(range(1, accounts + 1))
        for i in range(0, accounts, 20):
            do_request(cl, c, Operation.CREATE_ACCOUNTS, account_batch(ids[i : i + 20]))
    for b in range(transfer_batches):
        do_request(
            cl, c, Operation.CREATE_TRANSFERS,
            transfer_batch([
                dict(id=id_base + b * 20 + k, debit_account_id=1 + (k % accounts),
                     credit_account_id=1 + ((k + 1) % accounts), amount=1 + k,
                     ledger=1, code=1)
                for k in range(20)
            ]),
        )


class TestSnapshotFormat:
    def test_roundtrip_fixed_dtypes_no_pickle(self):
        cl = Cluster(replica_count=1)
        c = setup_client(cl)
        do_request(cl, c, Operation.CREATE_ACCOUNTS, account_batch([1, 2, 3]))
        # History accounts + pending/post so posted/history sections are
        # non-empty.
        do_request(
            cl, c, Operation.CREATE_ACCOUNTS,
            account_batch([9], flags=int(types_flags_history())),
        )
        do_request(
            cl, c, Operation.CREATE_TRANSFERS,
            transfer_batch([
                dict(id=50, debit_account_id=1, credit_account_id=9, amount=5,
                     ledger=1, code=1),
                dict(id=51, debit_account_id=1, credit_account_id=2, amount=7,
                     ledger=1, code=1, flags=2),  # pending
            ]),
        )
        do_request(
            cl, c, Operation.CREATE_TRANSFERS,
            transfer_batch([
                dict(id=52, pending_id=51, debit_account_id=1,
                     credit_account_id=2, amount=7, ledger=1, code=1,
                     flags=4),  # post_pending
            ]),
        )
        r0 = cl.replicas[0]
        blob = r0._save_snapshot()

        # The blob must load with pickle disabled and roundtrip byte-exactly.
        cl2 = Cluster(replica_count=1)
        r2 = cl2.replicas[0]
        r2._load_snapshot(blob)
        assert r2._save_snapshot() == blob
        # Posted + history grooves restored: byte-equal blobs imply equal
        # manifests; counts confirm the restore attached real state.
        assert r2.state_machine.posted.count == r0.state_machine.posted.count > 0
        assert r2.state_machine.history.count == r0.state_machine.history.count > 0
        out = r2.state_machine.lookup_accounts(
            np.array([1], dtype=np.uint64), np.array([0], dtype=np.uint64)
        )
        assert types.u128_of(out[0], "debits_posted") == 12

        # Groove CONTENT after a same-grid restore (r2's cross-grid blob
        # cannot read data blocks, but a crash+restart of r0 itself must
        # reload identical groove content, not just matching manifests).
        hist_before = r0.state_machine.get_account_history(9)
        posted_before = r0.state_machine.posted.count
        assert len(hist_before) > 0
        cl.storages[0].sync()
        cl.crash_replica(0)
        cl.restart_replica(0)
        r0b = cl.replicas[0]
        assert r0b.state_machine.get_account_history(9) == hist_before
        assert r0b.state_machine.posted.count == posted_before
        # Posted CONTENT: pending id=51 (posted by id=52) must still read
        # as POSTED, keyed by its original timestamp.
        from tigerbeetle_tpu.models.oracle import FULFILLMENT_POSTED

        p51 = r0b.state_machine._fetch_transfer(51)
        assert p51 is not None
        assert r0b.state_machine.posted.get(p51.timestamp) == FULFILLMENT_POSTED

    def test_client_table_replies_roundtrip(self):
        cl = Cluster(replica_count=1)
        c = setup_client(cl)
        do_request(cl, c, Operation.CREATE_ACCOUNTS, account_batch([1, 2]))
        r0 = cl.replicas[0]
        assert r0.clients, "client table must be populated"
        blob = r0._save_snapshot()
        cl2 = Cluster(replica_count=1)
        r2 = cl2.replicas[0]
        r2._load_snapshot(blob)
        assert set(r2.clients) == set(r0.clients)
        for cid in r0.clients:
            a, b = r0.clients[cid], r2.clients[cid]
            assert (a.session, a.request) == (b.session, b.request)
            assert (a.reply is None) == (b.reply is None)
            if a.reply is not None:
                assert a.reply.to_bytes() == b.reply.to_bytes()

    def test_history_dtype_u128_exact(self):
        from tigerbeetle_tpu.models.oracle import HistoryRow

        big = (1 << 127) + 12345
        rows = [
            HistoryRow(
                timestamp=7, dr_account_id=big, dr_debits_posted=big - 1,
                cr_account_id=3, cr_credits_pending=(1 << 64) + 9,
            )
        ]
        arr = snapshot.history_to_array(rows)
        back = snapshot.history_from_array(arr)
        assert back == rows


def types_flags_history() -> int:
    from tigerbeetle_tpu.flags import AccountFlags

    return AccountFlags.HISTORY


class _CorruptingNet:
    """Wraps PacketSimulator.send to corrupt the first non-announce sync
    chunk exactly once — the receiver must drop it (checksum) and
    re-request."""

    def __init__(self, cl):
        self.cl = cl
        self.corrupted = 0
        self.sync_chunks_seen = 0
        inner = cl.net.send

        def send(src, dst, data):
            h = Header.from_bytes(bytes(data[: hdr.HEADER_SIZE]))
            if h["command"] == Command.SYNC_CHECKPOINT:
                self.sync_chunks_seen += 1
                if h["op"] == 1 and self.corrupted == 0:
                    self.corrupted += 1
                    data = bytearray(data)
                    data[hdr.HEADER_SIZE + 3] ^= 0xFF
                    data = bytes(data)
            inner(src, dst, data)

        cl.net.send = send


class TestChunkedSync:
    def _lagging_backup_cluster(self):
        cl = Cluster(replica_count=3, seed=21)
        c = setup_client(cl)
        backup = next(r for r in cl.replicas if not r.is_primary)
        bi = backup.replica
        cl.storages[bi].sync()
        cl.crash_replica(bi)
        # Push the survivors far past the WAL ring (slot_count=32 in
        # TEST_MIN) so the backup cannot WAL-repair and must state-sync.
        grow_state(cl, c)
        live = [r for r in cl.replicas if r is not None]
        assert all(r.superblock.state.op_checkpoint >= 16 for r in live)
        primary = next(r for r in live if r.is_primary)
        blob = primary._trailer_read(primary.superblock.state.trailer_block)
        chunk = TEST_MIN.message_size_max - hdr.HEADER_SIZE
        assert len(blob) > 3 * chunk, "state must span several sync chunks"
        return cl, bi, c

    def test_multi_chunk_sync_converges(self):
        cl, bi, c = self._lagging_backup_cluster()
        net = _CorruptingNet(cl)  # also counts chunks
        cl.restart_replica(bi)
        target = max(r.commit_min for r in cl.replicas if r is not None)
        cl.run_until(
            lambda: cl.replicas[bi].commit_min >= target, max_ticks=120_000
        )
        assert net.sync_chunks_seen > 3
        assert net.corrupted == 1  # the corrupt-drop-rerequest path ran
        cl.check_state_convergence()
        rb = cl.replicas[bi]
        assert rb.checksum_floor >= 16  # state came from a snapshot install
        out = rb.state_machine.lookup_accounts(
            np.array([1], dtype=np.uint64), np.array([0], dtype=np.uint64)
        )
        assert len(out) == 1

    def test_sync_survives_backup_restart_after_install(self):
        cl, bi, c = self._lagging_backup_cluster()
        cl.restart_replica(bi)
        target = max(r.commit_min for r in cl.replicas if r is not None)
        cl.run_until(
            lambda: cl.replicas[bi].commit_min >= target, max_ticks=120_000
        )
        # The installed checkpoint must itself be durable: restart again.
        cl.storages[bi].sync()
        cl.crash_replica(bi)
        cl.restart_replica(bi)
        rb = cl.replicas[bi]
        assert rb.superblock.state.op_checkpoint >= 16
        out = rb.state_machine.lookup_accounts(
            np.array([1], dtype=np.uint64), np.array([0], dtype=np.uint64)
        )
        assert len(out) == 1

    def test_block_sync_traffic_proportional_to_delta(self):
        """A lagging replica whose grid already holds most of the state
        (it crashed with synced storage, then the cluster ran on past the
        WAL ring) fetches ONLY the blocks that changed — the reference's
        request_blocks/on_block delta property (replica.zig:2289,2413).
        A replica with an EMPTY grid fetches everything."""
        cl = Cluster(replica_count=3, seed=37)
        c = setup_client(cl)
        # Build up durable state + cross a checkpoint so the backup's grid
        # holds a real prefix of the cluster's blocks.
        grow_state(cl, c, accounts=120, transfer_batches=20)
        live0 = [r for r in cl.replicas if r is not None]
        assert all(r.superblock.state.op_checkpoint >= 16 for r in live0)
        backup = next(r for r in cl.replicas if not r.is_primary)
        bi = backup.replica
        cl.storages[bi].sync()
        cl.crash_replica(bi)
        # Advance well past the WAL ring with MORE state (two more
        # checkpoints' worth) so the backup must state-sync on rejoin.
        grow_state(cl, c, accounts=120, transfer_batches=30,
                   id_base=100_000, make_accounts=False)
        cl.restart_replica(bi)
        target = max(r.commit_min for r in cl.replicas if r is not None)
        cl.run_until(
            lambda: cl.replicas[bi].commit_min >= target, max_ticks=200_000
        )
        rb = cl.replicas[bi]
        stats = rb.block_sync_stats
        assert stats["wanted"] > 0
        # Delta property: a meaningful share of the referenced set was
        # already present locally and was NOT transferred. (TEST_MIN
        # geometry is tiny — compaction rewrites most table blocks between
        # checkpoints — so the retained share here is mostly the stable
        # prefix of the object log; at production geometry the retained
        # share grows with history.)
        retained = stats["wanted"] - stats["missing"]
        assert retained >= 10, stats
        assert stats["missing"] < stats["wanted"], stats
        cl.check_state_convergence()

    def test_block_sync_from_empty_grid_fetches_all(self):
        cl, bi, c = self._lagging_backup_cluster()
        # Wipe the backup's storage wholesale: rejoin must fetch every
        # referenced block (and still converge).
        from tigerbeetle_tpu.io.storage import MemStorage
        from tigerbeetle_tpu.vsr.replica import Replica

        cl.storages[bi] = MemStorage(cl.zone.total_size, seed=999)
        Replica.format(cl.storages[bi], cl.zone, cl.cluster_id, bi, 3)
        cl.restart_replica(bi)
        target = max(r.commit_min for r in cl.replicas if r is not None)
        cl.run_until(
            lambda: cl.replicas[bi].commit_min >= target, max_ticks=200_000
        )
        rb = cl.replicas[bi]
        stats = rb.block_sync_stats
        assert stats["missing"] == stats["wanted"] > 0, stats
        cl.check_state_convergence()

    def test_synced_accounts_are_found_only_once_their_log_arrived(self, monkeypatch):
        """A receiver with an empty grid installs the blob: it counts the
        accounts, but finds none by id until block sync has brought the
        account log's blocks and the accounts were rebuilt from them; then
        every account's 128 bytes read as on the primary."""
        from tigerbeetle_tpu.io.storage import MemStorage
        from tigerbeetle_tpu.vsr.replica import Replica

        cl, bi, c = self._lagging_backup_cluster()
        cl.storages[bi] = MemStorage(cl.zone.total_size, seed=998)
        Replica.format(cl.storages[bi], cl.zone, cl.cluster_id, bi, 3)
        ids = np.arange(1, 121, dtype=np.uint64)
        zeros = np.zeros(len(ids), dtype=np.uint64)
        seen = []
        finish = Replica._finish_block_sync

        def finish_and_look(replica):
            sm = replica.state_machine
            log_blocks = list(sm.account_log.blocks)
            seen.append((
                sm.account_count, len(log_blocks),
                all(sm.grid.local_checksum(b) == sm.grid.block_cks[b]
                    for b in log_blocks),
                len(sm.lookup_accounts(ids, zeros)),
            ))
            finish(replica)
            seen.append(len(sm.lookup_accounts(ids, zeros)))

        monkeypatch.setattr(Replica, "_finish_block_sync", finish_and_look)
        cl.restart_replica(bi)
        target = max(r.commit_min for r in cl.replicas if r is not None)
        cl.run_until(
            lambda: cl.replicas[bi].commit_min >= target, max_ticks=200_000
        )
        count, log_blocks, present, found = seen[0]
        assert count == 120 and log_blocks >= 1 and present and found == 0
        assert seen[1] == 120
        primary = next(r for r in cl.replicas if r is not None and r.is_primary)
        rb = cl.replicas[bi]
        assert rb.commit_min == primary.commit_min
        want = primary.state_machine.lookup_accounts(ids, zeros)
        assert rb.state_machine.lookup_accounts(ids, zeros).tobytes() == want.tobytes()
        assert types.u128_of(want[0], "debits_posted") > 0

    def test_a_sparse_checkpoint_syncs_its_balances_once_their_log_arrived(
        self, monkeypatch
    ):
        """The checkpoints a receiver syncs are sparse: the balance log
        holds rows for the 21 accounts the transfers touched, not the 120.
        A receiver with an empty grid installs the blob with every balance
        at zero, and has them from the log once block sync has brought its
        blocks; then the table reads as the primary's, byte for byte."""
        from tigerbeetle_tpu.io.storage import MemStorage
        from tigerbeetle_tpu.vsr.replica import Replica

        cl, bi, c = self._lagging_backup_cluster()
        primary = next(r for r in cl.replicas if r is not None and r.is_primary)
        slots = np.arange(120)
        assert 0 < primary.state_machine.balance_log.count < 120
        cl.storages[bi] = MemStorage(cl.zone.total_size, seed=997)
        Replica.format(cl.storages[bi], cl.zone, cl.cluster_id, bi, 3)
        seen = []
        finish = Replica._finish_block_sync

        def finish_and_look(replica):
            sm = replica.state_machine
            seen.append((sm.balance_log.count,
                         np.concatenate(sm._read_balances(slots), axis=1).any()))
            finish(replica)

        monkeypatch.setattr(Replica, "_finish_block_sync", finish_and_look)
        cl.restart_replica(bi)
        target = max(r.commit_min for r in cl.replicas if r is not None)
        cl.run_until(
            lambda: cl.replicas[bi].commit_min >= target, max_ticks=200_000
        )
        rows, any_balance = seen[0]
        assert 0 < rows < 120 and not any_balance
        rb = cl.replicas[bi]
        assert rb.commit_min == primary.commit_min
        got, want = (
            np.concatenate(r.state_machine._read_balances(slots), axis=1)
            for r in (rb, primary)
        )
        assert got.tobytes() == want.tobytes() and want.any()


class TestStaleInstallAbandon:
    def test_install_abandons_when_drain_overtakes_blob(self):
        """Regression (found by the vsrlint monotonicity pass):
        on_sync_checkpoint's freshness guard runs at chunk-assembly time,
        but _install_sync_checkpoint then calls _quiesce_commit_stage,
        and the drain applies staged completions that can advance
        commit_min (even the durable op_checkpoint) PAST the assembled
        blob. Installing anyway would regress commit_min/checksum_floor
        and re-point the superblock at an older checkpoint — the install
        must re-check and abandon after the drain."""
        from tigerbeetle_tpu import tracer

        cl, bi, c = TestChunkedSync()._lagging_backup_cluster()
        primary = next(
            r for r in cl.replicas if r is not None and r.is_primary
        )
        entry = primary._sync_blob()
        assert entry is not None
        cp_op, blob, _ck = entry
        cl.restart_replica(bi)
        rb = cl.replicas[bi]
        assert rb.commit_min < cp_op  # the arrival-time guard would pass

        orig = rb._quiesce_commit_stage

        def drain_overtakes():
            orig()
            # Simulate the race deterministically: the drained stage
            # carried completions up to (and past) the blob's checkpoint.
            rb.commit_min = cp_op

        rb._quiesce_commit_stage = drain_overtakes
        sm_before = rb.state_machine
        floor_before = rb.checksum_floor
        ckpt_before = rb.superblock.state.op_checkpoint
        was = tracer.enabled()
        tracer.enable()
        tracer.reset()
        try:
            rb._install_sync_checkpoint(cp_op, blob)
            counts = tracer.snapshot()
        finally:
            if not was:
                tracer.disable()
        assert counts["recovery.sync_stale_abandon"]["count"] == 1
        # Nothing was replaced or regressed: same state machine object,
        # same checksum floor, same durable checkpoint.
        assert rb.state_machine is sm_before
        assert rb.checksum_floor == floor_before
        assert rb.superblock.state.op_checkpoint == ckpt_before
