"""A balance table far larger than a batch (`production_16m`, the
deployment `tpcb_16m`: TPC-B at scale 160), at a small size on the CPU.

The preset's arithmetic against the configuration it was made for; the
fast and the exact commit kernel against the serial oracle with 2^16
slots and 64-event batches whose slots come from the top of the table
too, a hot cash slot among them; `create_accounts` over several batches
to the very last slot, and one more; a checkpoint of more accounts than a
batch registers, restored after a crash with every balance byte-exact, and
the spans and the counter the checkpoint records.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from tigerbeetle_tpu import constants, tracer, types
from tigerbeetle_tpu.constants import PRODUCTION, PRODUCTION_16M, TEST_MIN
from tigerbeetle_tpu.models import oracle as om
from tigerbeetle_tpu.models.state_machine import StateMachine
from tigerbeetle_tpu.testing.cluster import Cluster, parse_results
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
    (128 B an account and the manifests) beside the store's content of a
    run of 1,900 full batches, under 85% of it."""
    config, preset = deployment(), PRODUCTION_16M
    assert config["start"]["config"] == preset.name
    assert config["accounts"] <= preset.accounts_max == config["accounts_max"]
    assert config["accounts"] == config["scale"] * (
        config["accounts_per_branch"] + config["tellers_per_branch"] + 2)
    assert config["transfers_max"] == preset.transfers_max
    payload = preset.lsm_block_size - 64
    trailer = -(-(config["accounts"] * 128 + (8 << 20)) // payload) + 1
    # PRODUCTION's grid at its fullest (85% of 2^15 blocks, PERF.md section 4) held two
    # trailers of 515 blocks: the rest was content.
    content = int(0.85 * PRODUCTION.grid_block_count) - 2 * 515
    assert 2 * trailer + content <= 0.85 * preset.grid_block_count
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


def test_a_checkpoint_of_many_batches_of_accounts_is_restored_to_the_byte():
    """Five batches of accounts, transfers over all of them past the
    checkpoint at op 16; crash, restart from the trailer, and every
    account reads as it did. The checkpoint left its span, its four leaves
    and its blob's length on the tracer."""
    was = tracer.enabled()
    tracer.enable()
    tracer.reset()
    try:
        cluster = Cluster(replica_count=1, sm_backend="jax")
        client = cluster.clients[100]
        client.register()
        cluster.run_until(lambda: client.registered)
        count = 5 * N
        for first in range(1, count + 1, N):
            reply = request(cluster, client, Operation.CREATE_ACCOUNTS,
                            accounts_batch(first, N).tobytes())
            assert len(parse_results(reply)) == 0
        rng = np.random.default_rng(16)
        for k in range(14):
            t = np.zeros(N, dtype=types.TRANSFER_DTYPE)
            t["id_lo"] = np.arange(1 + k * N, 1 + (k + 1) * N, dtype=np.uint64)
            t["debit_account_id_lo"] = rng.integers(1, count + 1, N)
            t["credit_account_id_lo"] = t["debit_account_id_lo"] % count + 1
            t["amount_lo"] = rng.integers(1, 1_000_000, N)
            t["ledger"], t["code"] = 1, 1
            reply = request(cluster, client, Operation.CREATE_TRANSFERS, t.tobytes())
            assert len(parse_results(reply)) == 0
        replica = cluster.replicas[0]
        assert replica.superblock.state.op_checkpoint == TEST_MIN.checkpoint_interval
        assert replica.state_machine.account_count == count > TEST_MIN.batch_max
        before = all_accounts(cluster, client, count)
        assert len(before) == count * 128
        snap = tracer.snapshot()
        leaves = ("drain", "encode", "trailer", "sync")
        assert snap["vsr.checkpoint"]["count"] == 1 == snap["replica.checkpoint"]["count"]
        assert all(snap[f"vsr.checkpoint.{leaf}"]["count"] == 1 for leaf in leaves)
        assert (sum(snap[f"vsr.checkpoint.{leaf}"]["total_ms"] for leaf in leaves)
                <= snap["vsr.checkpoint"]["total_ms"] + 0.01)  # (each rounded to 1 us)
        assert snap["vsr.checkpoint.blob_bytes"]["count"] > count * 128

        cluster.storages[0].sync()
        cluster.crash_replica(0)
        cluster.restart_replica(0)
        cluster.run_until(lambda: cluster.replicas[0].status == "normal")
        restored = cluster.replicas[0].state_machine
        assert restored.account_count == count
        ids = np.arange(1, count + 1, dtype=np.uint64)
        after = restored.lookup_accounts(ids, np.zeros(count, np.uint64))
        assert after.tobytes() == before
        assert np.frombuffer(before, types.ACCOUNT_DTYPE)["debits_posted_lo"].sum() > 0
    finally:
        tracer.reset()
        if not was:
            tracer.disable()
