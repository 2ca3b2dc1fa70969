"""A balance table far larger than a batch (`production_16m`, the
deployment `tpcb_16m`: TPC-B at scale 160), at a small size on the CPU.

The preset's arithmetic against the configuration it was made for; the
fast and the exact commit kernel against the serial oracle with 2^16
slots and 64-event batches whose slots come from the top of the table
too, a hot cash slot among them; `create_accounts` over several batches
to the very last slot, and one more; a checkpoint of more accounts than a
batch registers, restored after a crash with every account byte-exact (its
immutable half from the account log, its balances from the balance log),
the spans and the counters the checkpoint records, and a checkpoint that
writes the balances of the touched accounts alone, to the balance log and
not to the blob.
"""

import dataclasses
import io
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from tigerbeetle_tpu import constants, tracer, types
from tigerbeetle_tpu.constants import PRODUCTION, PRODUCTION_16M, TEST_MIN
from tigerbeetle_tpu.models import oracle as om
from tigerbeetle_tpu.models.state_machine import StateMachine
from tigerbeetle_tpu.testing.cluster import Cluster, parse_results
from tigerbeetle_tpu.vsr import snapshot
from tigerbeetle_tpu.vsr.header import Operation

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
A = 1 << 16  # slots: a thousand times the batch
N = 64  # events a batch
CASH = A - 3  # the hot account, near the top of the table
LINKED = 1


def deployment() -> dict:
    with open(os.path.join(REPO, "benchmarks", "configs", "tpcb_16m.json")) as f:
        return json.load(f)


# --- the preset ---------------------------------------------------------------------


def test_the_preset_is_production_with_a_larger_table_and_grid():
    assert constants.config_by_name("production_16m") is PRODUCTION_16M
    changed = {f.name for f in dataclasses.fields(PRODUCTION)
               if getattr(PRODUCTION, f.name) != getattr(PRODUCTION_16M, f.name)}
    assert changed == {"name", "accounts_max", "grid_block_count"}
    with pytest.raises(KeyError):
        constants.config_by_name("production_1g")


def test_the_preset_holds_the_deployment_it_was_made_for():
    """Slots for every account; a grid for two live checkpoint trailers
    (the manifests), the account log (64 B an account, once) and the
    balance log at its largest (three copies of the table, 72 B a row, while
    a checkpoint rewrites it) beside the store's content of a run of 1,900
    full batches, under 85% of it."""
    config, preset = deployment(), PRODUCTION_16M
    assert config["start"]["config"] == preset.name
    assert config["accounts"] <= preset.accounts_max == config["accounts_max"]
    assert config["accounts"] == config["scale"] * (
        config["accounts_per_branch"] + config["tellers_per_branch"] + 2)
    assert config["transfers_max"] == preset.transfers_max
    payload = preset.lsm_block_size - 64
    trailer = -(-(8 << 20) // payload) + 1
    account_log = -(-config["accounts"] // (payload // 64))
    balance_log = 3 * -(-config["accounts"] // (payload // 72))
    # PRODUCTION's grid at its fullest (85% of 2^15 blocks, PERF.md section 4) held two
    # trailers of 515 blocks (128 B an account then): the rest was content.
    content = int(0.85 * PRODUCTION.grid_block_count) - 2 * 515
    assert (2 * trailer + account_log + balance_log + content
            <= 0.85 * preset.grid_block_count)
    # one trailer's chunks are listed in ONE index block (replica._trailer_write asserts it)
    assert trailer - 1 <= (payload - 32) // 4


# --- the kernels against the oracle, slots from the top of the table -----------------


def accounts_batch(first: int, count: int) -> np.ndarray:
    acc = np.zeros(count, dtype=types.ACCOUNT_DTYPE)
    acc["id_lo"] = np.arange(first, first + count, dtype=np.uint64)
    acc["ledger"] = 1
    acc["code"] = 7
    return acc


@pytest.fixture(scope="module")
def full_table():
    """A state machine and an oracle with all 2^16 slots taken, over nine
    batches of accounts (slot = id - 1: registration order)."""
    config = dataclasses.replace(TEST_MIN, accounts_max=A)
    sm, o = StateMachine(config, backend="jax"), om.Oracle()
    for first in range(1, A + 1, 8190):
        acc = accounts_batch(first, min(8190, A + 1 - first))
        ts = o.prepare("create_accounts", len(acc))
        assert o.create_accounts([om.account_from_numpy(r) for r in acc], ts) == []
        assert len(sm.create_accounts(acc)) == 0
    assert sm.account_count == A
    return sm, o


def transfers(rng, first_id: int, chains: bool) -> np.ndarray:
    """64 events: a third between the table's last 40 slots, a third against
    the hot cash account, the rest anywhere; with `chains`, three-event
    linked chains (one of them with a zero amount: it rolls back whole)."""
    t = np.zeros(N, dtype=types.TRANSFER_DTYPE)
    t["id_lo"] = np.arange(first_id, first_id + N, dtype=np.uint64)
    top = rng.integers(A - 40, A + 1, N)
    anywhere = rng.integers(1, A + 1, N)
    kind = np.arange(N) % 3
    dr = np.where(kind == 0, top, np.where(kind == 1, CASH, anywhere))
    cr = np.where(kind == 0, rng.integers(A - 40, A + 1, N), rng.integers(1, A + 1, N))
    cr = np.where(cr == dr, dr % A + 1, cr)
    t["debit_account_id_lo"], t["credit_account_id_lo"] = dr, cr
    t["amount_lo"] = rng.integers(1, 1_000_000, N)
    t["ledger"], t["code"] = 1, 1
    if chains:
        t["flags"][: N - N % 3] = np.tile([LINKED, LINKED, 0], N // 3)
        t["amount_lo"][3 * int(rng.integers(0, N // 3)) + 1] = 0
    return t


@pytest.mark.parametrize("chains", [False, True], ids=["fast", "exact"])
def test_a_kernel_posts_where_the_oracle_does(full_table, chains):
    sm, o = full_table
    rng = np.random.default_rng(160 + chains)
    routed = dict(sm.stats)
    touched = {CASH}
    for k in range(6):
        events = transfers(rng, 1 + (chains * 6 + k) * N, chains)
        ts = o.prepare("create_transfers", N)
        want = o.create_transfers([om.transfer_from_numpy(r) for r in events], ts)
        got = sm.create_transfers(events)
        assert [(int(r["index"]), int(r["result"])) for r in got] == want
        assert bool(want) == chains  # the rolled-back chain's three codes, else none
        touched |= set(events["debit_account_id_lo"].tolist())
        touched |= set(events["credit_account_id_lo"].tolist())
    route = "exact_batches" if chains else "fast_batches"
    assert sm.stats[route] - routed.get(route, 0) == 6
    assert sm.stats.get("serial_batches", 0) == routed.get("serial_batches", 0)
    assert max(touched) > A - 40 and min(touched) < A // 2
    ids = np.array(sorted(touched), dtype=np.uint64)
    served = sm.lookup_accounts(ids, np.zeros(len(ids), np.uint64))
    want = types.batch([om.account_to_numpy(a) for a in o.lookup_accounts(ids.tolist())],
                       types.ACCOUNT_DTYPE)
    assert served.tobytes() == want.tobytes()
    cash = served[ids.tolist().index(CASH)]
    assert types.u128_of(cash, "debits_posted") > 20 * 1_000  # some 21 postings a batch


def test_the_table_fills_to_its_last_slot_and_no_further(full_table):
    """The batch that ends on the last slot was taken whole (the fixture);
    one account more than the table holds is refused as it has always been."""
    sm, _ = full_table
    assert sm.account_count == sm.config.accounts_max
    last = sm.lookup_accounts(np.array([A], np.uint64), np.zeros(1, np.uint64))
    assert len(last) == 1 and int(last[0]["id_lo"]) == A
    with pytest.raises(RuntimeError, match="accounts table full"):
        sm.create_accounts(accounts_batch(A + 1, 1))
    assert sm.account_count == A
    # an account that exists already still gets its answer
    again = sm.create_accounts(accounts_batch(A, 1))
    assert len(again) == 1 and int(again[0]["result"]) != 0


# --- a checkpoint of more accounts than one batch registers --------------------------


def request(cluster, client, operation, body: bytes):
    client.request(operation, body)
    cluster.run_until(lambda: client.idle, 20_000)
    return client.replies[-1]


def all_accounts(cluster, client, count: int) -> bytes:
    out = b""
    for first in range(1, count + 1, 60):
        ids = np.zeros(min(60, count + 1 - first), dtype=types.ID_DTYPE)
        ids["lo"] = np.arange(first, first + len(ids))
        out += bytes(request(cluster, client, Operation.LOOKUP_ACCOUNTS, ids.tobytes()).body)
    return out


def pay(cluster, client, rng, first_id: int, count: int) -> None:
    """One batch of transfers between any of the first `count` accounts."""
    t = np.zeros(N, dtype=types.TRANSFER_DTYPE)
    t["id_lo"] = np.arange(first_id, first_id + N, dtype=np.uint64)
    t["debit_account_id_lo"] = rng.integers(1, count + 1, N)
    t["credit_account_id_lo"] = t["debit_account_id_lo"] % count + 1
    t["amount_lo"] = rng.integers(1, 1_000_000, N)
    t["ledger"], t["code"] = 1, 1
    reply = request(cluster, client, Operation.CREATE_TRANSFERS, t.tobytes())
    assert len(parse_results(reply)) == 0


def create(cluster, client, first: int) -> None:
    reply = request(cluster, client, Operation.CREATE_ACCOUNTS,
                    accounts_batch(first, N).tobytes())
    assert len(parse_results(reply)) == 0


@pytest.mark.parametrize("store_async", [False, True], ids=["inline", "store_stage"])
def test_a_checkpoint_of_many_batches_of_accounts_is_restored_to_the_byte(store_async):
    """Five batches of accounts, transfers over them, a sixth batch as the
    checkpoint's own op (its beat leaves its full account-log block
    pending, so the checkpoint flushes it), and a seventh after the
    checkpoint; crash, restart from the trailer and the WAL, and every
    account's 128 bytes read as they did. The blob carries the account
    log's blocks and its tail, the balance log's (one row for each account
    a transfer touched, as the table holds it), and none of the accounts'
    columns. The checkpoint left its span, its four leaves and its blob's
    length and its count of balance rows on the tracer."""
    was = tracer.enabled()
    tracer.enable()
    tracer.reset()
    try:
        cluster = Cluster(replica_count=1, sm_backend="jax", store_async=store_async)
        client = cluster.clients[100]
        client.register()
        cluster.run_until(lambda: client.registered)
        replica = cluster.replicas[0]
        sm = replica.state_machine
        interval = TEST_MIN.checkpoint_interval
        count = 5 * N
        for first in range(1, count + 1, N):
            create(cluster, client, first)
        rng = np.random.default_rng(16)
        next_id = 1
        while replica.commit_min < interval - 1:
            pay(cluster, client, rng, next_id, count)
            next_id += N

        pending = []

        def beat_that_writes_nothing(max_blocks=8, flush=True):
            type(sm).compact_beat(sm, 0, flush=flush)
            pending.append(len(sm.account_log._pending_blocks))

        sm.compact_beat = beat_that_writes_nothing
        create(cluster, client, count + 1)
        del sm.compact_beat
        count += N
        assert replica.superblock.state.op_checkpoint == interval
        assert pending == [1]
        rpb = sm.account_log.records_per_block
        blob = replica._trailer_read(replica.superblock.state.trailer_block)
        with np.load(io.BytesIO(blob)) as z:
            assert int(z["version"]) == 9 and int(z["account_count"]) == count
            assert not [k for k in z.files if k.startswith(("acc_", "bal_"))]
            assert len(z["alog_blocks"]) == count // rpb >= 2
            assert len(z["alog_tail"]) == count % rpb > 0
            assert len(z["blog_blocks"]) * sm.balance_log.records_per_block + len(
                z["blog_tail"]) == sm.balance_log.count
        logged = sm.balance_log.export_all()
        table = np.concatenate(sm._read_balances(np.arange(count)), axis=1)
        assert (np.diff(logged["slot"].astype(np.int64)) > 0).all()
        assert logged["slot"].tolist() == np.flatnonzero(table.any(axis=1)).tolist()
        assert 0 < len(logged) < count
        assert logged.view(np.uint64).reshape(-1, 9)[:, 1:].view(np.uint32).tobytes() == (
            table[logged["slot"].astype(np.int64)].tobytes())

        for _ in range(3):
            pay(cluster, client, rng, next_id, count)
            next_id += N
        create(cluster, client, count + 1)
        count += N
        for _ in range(2):
            pay(cluster, client, rng, next_id, count)
            next_id += N
        assert sm.account_count == count > TEST_MIN.batch_max
        before = all_accounts(cluster, client, count)
        assert len(before) == count * 128
        snap = tracer.snapshot()
        leaves = ("drain", "encode", "trailer", "sync")
        assert snap["vsr.checkpoint"]["count"] == 1 == snap["replica.checkpoint"]["count"]
        assert all(snap[f"vsr.checkpoint.{leaf}"]["count"] == 1 for leaf in leaves)
        assert (sum(snap[f"vsr.checkpoint.{leaf}"]["total_ms"] for leaf in leaves)
                <= snap["vsr.checkpoint"]["total_ms"] + 0.01)  # (each rounded to 1 us)
        assert snap["vsr.checkpoint.encode.read"]["count"] == 1
        assert (snap["vsr.checkpoint.encode.read"]["total_ms"]
                <= snap["vsr.checkpoint.encode"]["total_ms"] + 0.001)
        assert snap["vsr.checkpoint.blob_bytes"]["count"] == len(blob)
        assert snap["vsr.checkpoint.balance_rows"]["count"] == len(logged)
        assert "vsr.checkpoint.balance_dense" not in snap

        cluster.storages[0].sync()
        cluster.crash_replica(0)
        cluster.restart_replica(0)
        cluster.run_until(lambda: cluster.replicas[0].status == "normal")
        restored = cluster.replicas[0].state_machine
        assert restored.account_count == restored.account_log.count == count
        ids = np.arange(1, count + 1, dtype=np.uint64)
        after = restored.lookup_accounts(ids, np.zeros(count, np.uint64))
        assert after.tobytes() == before
        rows = np.frombuffer(before, types.ACCOUNT_DTYPE)
        assert (rows["debits_posted_lo"][-N:] > 0).any()  # an account made after the checkpoint
        assert (rows["credits_posted_lo"][: count - N] > 0).sum() > count // 2
    finally:
        tracer.reset()
        if not was:
            tracer.disable()


def test_a_checkpoint_blob_grows_by_the_balances_alone():
    """A checkpoint writes the balances of the accounts touched since the
    last one alone, to the balance log, and the blob carries none. Nine
    hundred registrations add no row, and the blob grows with them by the
    account log's block references (28 bytes a block of 63 accounts at this
    size) and its tail alone, under 2 bytes an account. Transfers over a few accounts add one row for
    each of them, ascending by slot, as the table holds it; transfers over
    all of them add a row for each until the log would hold more rows than
    two copies of the table: that checkpoint writes every slot afresh, and
    the next ones append again."""
    sm = StateMachine(TEST_MIN, backend="jax")
    at = SimpleNamespace(state_machine=sm, clients={}, committed_timestamp_max=0,
                               config_epoch=0, slot_epoch={})
    log = sm.balance_log
    sizes = {}
    for first in range(1, 15 * N + 1, N):
        assert len(sm.create_accounts(accounts_batch(first, N))) == 0
        sizes[sm.account_count] = len(snapshot.encode(at))
    assert log.count == 0
    (c0, b0), (c1, b1) = min(sizes.items()), max(sizes.items())
    assert 0 < (b1 - b0) / (c1 - c0) < 2
    count = sm.account_count
    next_id = iter(range(1, 1 << 20))

    def pay(dr) -> set:
        t = np.zeros(len(dr), dtype=types.TRANSFER_DTYPE)
        t["id_lo"] = [next(next_id) for _ in dr]
        t["debit_account_id_lo"], t["credit_account_id_lo"] = dr, dr % count + 1
        t["amount_lo"], t["ledger"], t["code"] = 3, 1, 1
        assert len(sm.create_transfers(t)) == 0
        return {int(i) - 1 for i in np.concatenate([dr, dr % count + 1])}

    def checkpoint() -> np.ndarray:
        """The rows this checkpoint appended (the whole log where it was
        rewritten); each equal to the table's slot."""
        before = log.count
        blob = snapshot.encode(at)
        assert len(blob) < 32 * count  # half of what the balances alone would take
        with np.load(io.BytesIO(blob)) as z:
            assert not [k for k in z.files if k.startswith("bal_")]
        rows = log.export_all()[before if log.count >= before else 0:]
        table = np.concatenate(sm._read_balances(np.arange(count)), axis=1)
        got = rows.view(np.uint64).reshape(-1, 9)[:, 1:].view(np.uint32)
        assert got.tobytes() == table[rows["slot"].astype(np.int64)].tobytes()
        return rows["slot"].tolist()

    rng = np.random.default_rng(43)
    touched = set()
    for _ in range(3):
        touched |= pay(rng.integers(1, count + 1, N).astype(np.uint64))
    assert checkpoint() == sorted(touched) and len(touched) < count // 2
    every = list(range(count))
    for first in range(1, count + 1, N):
        pay(np.arange(first, first + N, dtype=np.uint64))
    assert checkpoint() == every and log.count == len(touched) + count
    for first in range(1, count + 1, N):
        pay(np.arange(first, first + N, dtype=np.uint64))
    assert checkpoint() == every and log.count == count  # rewritten: 2 x 960 < 1,344 + 960
    again = pay(np.arange(5, 5 + N, dtype=np.uint64))
    assert checkpoint() == sorted(again) and log.count == count + len(again)
    assert checkpoint() == [] and log.count == count + len(again)
