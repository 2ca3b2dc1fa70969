"""The balance log: each checkpoint appends the balances of the accounts the
ops since the last one touched, and a restore replays the rows in order.

Random op streams (post/void, linked chains, balancing transfers, refusals,
the serial path's batches) checkpointed every few batches: the log's replay
equals the table each time, on both backends and through the dispatch-ahead
pair, and the same stream writes the same log on every route; a chain of
sparse checkpoints, a dense one and a sparse one after it, restored after a
crash; three replicas' balance-log blocks, byte for byte.
"""

import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.constants import TEST_MIN
from tigerbeetle_tpu.flags import AccountFlags, TransferFlags
from tigerbeetle_tpu.models.state_machine import StateMachine
from tigerbeetle_tpu.testing.cluster import Cluster, parse_results
from tigerbeetle_tpu.vsr import snapshot
from tigerbeetle_tpu.vsr.header import Operation

ACCOUNTS = 48
PENDING = int(TransferFlags.PENDING)
POST = int(TransferFlags.POST_PENDING_TRANSFER)
VOID = int(TransferFlags.VOID_PENDING_TRANSFER)
LINKED = int(TransferFlags.LINKED)
BALANCING = int(TransferFlags.BALANCING_DEBIT)


def replayed(sm) -> np.ndarray:
    """The table the balance log gives, row by row in log order: (count, 16)
    u32, the four balances' limbs side by side."""
    table = np.zeros((sm.account_count, 16), dtype=np.uint32)
    for row in sm.balance_log.export_all():
        table[int(row["slot"])] = np.frombuffer(row.tobytes()[8:], dtype="<u4")
    return table


def live(sm) -> np.ndarray:
    return np.concatenate(sm._read_balances(np.arange(sm.account_count)), axis=1)


def open_accounts(sm) -> None:
    acc = np.zeros(ACCOUNTS, dtype=types.ACCOUNT_DTYPE)
    acc["id_lo"] = np.arange(1, ACCOUNTS + 1)
    acc["ledger"], acc["code"] = 1, 7
    acc["flags"][:4] = int(AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS)
    acc["flags"][4:8] = int(AccountFlags.CREDITS_MUST_NOT_EXCEED_DEBITS)
    acc["flags"][8:10] = int(AccountFlags.HISTORY)
    assert len(sm.create_accounts(acc)) == 0


def op_stream(seed: int, batches: int):
    """Batches of 1-40 events: transfers between any account and four ids
    that name none (a third of the batches plain: no flag, no account
    with a limit or a history), pending transfers, posts and voids (their account ids
    zero: the pending names the accounts) of pendings from earlier batches,
    plain transfers that name a pending all the same (refused), balancing
    debits, linked chains, amounts past the limits; now and then
    a duplicate id or a post of a pending from the same batch, which take
    the serial path."""
    rng = np.random.default_rng(seed)
    pendings, next_id = [], 1
    for _ in range(batches):
        n = int(rng.integers(1, 41))
        t = np.zeros(n, dtype=types.TRANSFER_DTYPE)
        t["id_lo"] = np.arange(next_id, next_id + n)
        next_id += n
        # A third of the batches are plain: no flag, no limited account.
        plain = rng.random() < 0.3
        low = 11 if plain else 1
        t["debit_account_id_lo"] = rng.integers(low, ACCOUNTS + 5, n)
        t["credit_account_id_lo"] = rng.integers(low, ACCOUNTS + 5, n)
        t["amount_lo"] = np.where(rng.random(n) < 0.1, 10**6, rng.integers(1, 1000, n))
        t["ledger"], t["code"] = 1, 1
        kind = np.zeros(n, dtype=np.int64) if plain else rng.integers(0, 10, n)
        t["flags"] = np.where(kind == 1, PENDING, np.where(kind == 2, BALANCING, 0))
        for i in np.flatnonzero(kind >= 8):
            if pendings:
                t["flags"][i] = POST if kind[i] == 8 else VOID
                t["pending_id_lo"][i] = pendings[int(rng.integers(0, len(pendings)))]
                t["debit_account_id_lo"][i] = t["credit_account_id_lo"][i] = 0
                t["amount_lo"][i] = 0
        stray = np.flatnonzero(kind == 3)
        if len(stray) and pendings:
            # A pending id on a plain transfer: refused, and no account of
            # that pending may be marked for it.
            t["pending_id_lo"][stray] = pendings[int(rng.integers(0, len(pendings)))]
        if not plain and rng.random() < 0.3 and n >= 3:
            i = int(rng.integers(0, n - 2))
            t["flags"][i : i + 2] |= LINKED
        if rng.random() < 0.1 and n >= 2:
            t["id_lo"][n - 1] = t["id_lo"][0]
        elif rng.random() < 0.1 and n >= 2 and t["flags"][0] == PENDING:
            t["flags"][n - 1] = POST
            t["pending_id_lo"][n - 1] = t["id_lo"][0]
            t["debit_account_id_lo"][n - 1] = t["credit_account_id_lo"][n - 1] = 0
            t["amount_lo"][n - 1] = 0
        pendings += t["id_lo"][(t["flags"] & PENDING) != 0].tolist()
        yield t


def commit(sm, events, ahead: bool) -> np.ndarray:
    """One batch, through the dispatch-ahead pair where it takes the batch."""
    if ahead:
        ts = sm.prepare("create_transfers", len(events))
        handle = sm.create_transfers_dispatch(events, ts)
        if handle is not None:
            return sm.create_transfers_finish(handle)
        return sm.create_transfers(events, ts)
    return sm.create_transfers(events)


def run_stream(backend: str, seed: int, ahead: bool = False):
    """The stream committed with a checkpoint's append every three batches;
    at each, the log's replay equals the table. Returns the state machine,
    every batch's results and the log's count after each append."""
    sm = StateMachine(TEST_MIN, backend=backend)
    open_accounts(sm)
    results, counts = [], []
    for b, events in enumerate(op_stream(seed, 36)):
        results.append(commit(sm, events, ahead).tobytes())
        if b % 3 == 2:
            snapshot.append_balances(sm)
            counts.append(sm.balance_log.count)
            assert replayed(sm).tobytes() == live(sm).tobytes(), f"batch {b}"
    return sm, results, counts


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_the_log_replays_to_the_table_at_every_checkpoint(backend, seed):
    sm, _results, counts = run_stream(backend, seed)
    assert sm.stats["serial_batches"] > 0 and sm.stats["fast_batches"] > 0
    if backend == "jax":
        assert sm.stats["exact_batches"] > 0
    grew = [b > a for a, b in zip(counts, counts[1:])]
    assert any(grew) and not all(grew)  # sparse appends and dense rewrites both


@pytest.mark.parametrize("seed", [4, 5, 6])
def test_every_route_writes_the_same_log(seed):
    """The same stream on the numpy backend (its exact batches take the
    serial path), on the device backend and through the dispatch-ahead pair
    answers alike and leaves the same balance log, row for row."""
    runs = [run_stream("numpy", seed), run_stream("jax", seed),
            run_stream("jax", seed, ahead=True)]
    logs = [sm.balance_log.export_all().tobytes() for sm, _, _ in runs]
    assert all(r[1] == runs[0][1] for r in runs)
    assert logs[0] == logs[1] == logs[2] and len(logs[0]) > 0


# --- through a replica: restore after a crash, and three replicas -------------------

N = 100  # accounts


def request(cluster, client, operation, body: bytes):
    client.request(operation, body)
    cluster.run_until(lambda: client.idle, 40_000)
    return client.replies[-1]


def open_cluster(cluster):
    client = cluster.clients[100]
    client.register()
    cluster.run_until(lambda: client.registered)
    for first in (1, 51):
        acc = np.zeros(50, dtype=types.ACCOUNT_DTYPE)
        acc["id_lo"] = np.arange(first, first + 50)
        acc["ledger"], acc["code"] = 1, 7
        reply = request(cluster, client, Operation.CREATE_ACCOUNTS, acc.tobytes())
        assert len(parse_results(reply)) == 0
    return client


class Payer:
    """Batches that each touch a given set of accounts, in pairs, and
    nothing else."""

    def __init__(self, cluster, client) -> None:
        self.cluster, self.client, self.next_id = cluster, client, 1

    def pay(self, ids) -> None:
        ids = np.asarray(ids, dtype=np.uint64)
        t = np.zeros(len(ids) // 2, dtype=types.TRANSFER_DTYPE)
        t["id_lo"] = np.arange(self.next_id, self.next_id + len(t))
        self.next_id += len(t)
        t["debit_account_id_lo"], t["credit_account_id_lo"] = ids[0::2], ids[1::2]
        t["amount_lo"] = np.arange(1, len(t) + 1)
        t["ledger"], t["code"] = 1, 1
        reply = request(self.cluster, self.client, Operation.CREATE_TRANSFERS, t.tobytes())
        assert len(parse_results(reply)) == 0

    def until_checkpoint(self, replica, ids) -> None:
        """Pay over `ids` until the replica's next checkpoint has run."""
        at = replica.superblock.state.op_checkpoint
        while replica.superblock.state.op_checkpoint == at:
            self.pay(ids)


# Each interval's accounts, and the balance log's rows after its checkpoint
# (100 accounts: a checkpoint that would leave more than 200 rewrites it).
PHASES = (
    (range(1, 61), 60),  # sparse
    (range(41, 101), 120),  # sparse
    (range(1, 101), 100),  # 120 + 100 > 200: dense
    (range(1, 11), 110),  # sparse again
)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_a_chain_of_sparse_and_dense_checkpoints_is_restored_to_the_byte(backend):
    """Two sparse checkpoints, a dense one and a sparse one after it, more
    ops past the last; crash, restart from the trailer, the logs and the
    WAL, and the table reads as the live one did, byte for byte."""
    cluster = Cluster(replica_count=1, sm_backend=backend)
    client = open_cluster(cluster)
    replica = cluster.replicas[0]
    payer = Payer(cluster, client)
    for ids, rows in PHASES:
        payer.until_checkpoint(replica, ids)
        assert replica.state_machine.balance_log.count == rows
    for _ in range(3):
        payer.pay(range(91, 101))
    sm = replica.state_machine
    before = live(sm)
    assert (before[90:] != 0).any() and sm.account_count == N
    cluster.storages[0].sync()
    cluster.crash_replica(0)
    cluster.restart_replica(0)
    cluster.run_until(lambda: cluster.replicas[0].status == "normal")
    restored = cluster.replicas[0].state_machine
    assert restored.balance_log.count == 110
    assert live(restored).tobytes() == before.tobytes()


@pytest.mark.parametrize("overlap", [False, True], ids=["inline", "overlapped"])
def test_three_replicas_write_the_same_balance_log(overlap):
    """The same rows in the same grid blocks on every replica, checkpoint
    after checkpoint, sparse and dense alike (the storage checker compares
    the blocks' checksums; here their bytes)."""
    cluster = Cluster(replica_count=3, sm_backend="jax", overlap=overlap,
                      commit_depth=2 if overlap else 0)
    client = open_cluster(cluster)
    payer = Payer(cluster, client)
    primary = next(r for r in cluster.replicas if r.is_primary)
    for ids, rows in PHASES:
        payer.until_checkpoint(primary, ids)
        cluster.run_until(lambda: all(
            r.superblock.state.op_checkpoint == primary.superblock.state.op_checkpoint
            for r in cluster.replicas), 40_000)
        assert cluster.check_storage_convergence() > 0
        logs = [r.state_machine.balance_log for r in cluster.replicas]
        assert all(log.count == rows for log in logs)
        blocks = [[(b, bytes(log.grid.read_block(b))) for b in log.blocks] for log in logs]
        assert blocks[0] and blocks[0] == blocks[1] == blocks[2]
        tails = [log.export_all()[len(log.blocks) * log.records_per_block:].tobytes()
                 for log in logs]
        assert tails[0] == tails[1] == tails[2]
