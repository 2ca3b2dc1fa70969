"""Exact batches that read nothing from the store do not wait for it.

`_create_transfers_exact` decides from the staged batch: with a post/void
event, or on an account that keeps its history, it takes the store barrier
and writes its rows inline, as it always did; with neither (linked chains,
pendings, limits, balancing) it takes no barrier and hands its OK rows to
`_finish_commit`, the fast path's discipline: the store thread applies them
when the stage is attached. Here: one replica on the jax backend WITH the
async store stage, against `models/oracle.py` fed the same batches at the
same timestamps: result codes, balances, every id read back, the history
rows; the two batches that must NOT defer; and the same committed bytes with
and without the stage.
"""

import numpy as np
import pytest

from tests.test_cluster import do_request, setup_client
from tigerbeetle_tpu import tracer, types
from tigerbeetle_tpu.flags import AccountFlags, TransferFlags
from tigerbeetle_tpu.models import oracle as om
from tigerbeetle_tpu.results import CreateTransferResult as TR
from tigerbeetle_tpu.testing.cluster import Cluster, parse_results
from tigerbeetle_tpu.vsr.header import Operation

PLAIN = 8  # accounts 1..8; account 9 keeps its history
HISTORY = 9
LINKED = int(TransferFlags.LINKED)
PENDING = int(TransferFlags.PENDING)
POST = int(TransferFlags.POST_PENDING_TRANSFER)
CHAINS = 5  # chains of three a batch
BATCHES = 22  # past one test_min checkpoint (16 ops)


def _accounts() -> np.ndarray:
    acc = np.zeros(PLAIN + 1, dtype=types.ACCOUNT_DTYPE)
    acc["id_lo"] = np.arange(1, PLAIN + 2)
    acc["ledger"] = 1
    acc["code"] = 10
    acc["flags"][HISTORY - 1] = int(AccountFlags.HISTORY)
    return acc


def _chains(rng, first_id: int, accounts=PLAIN, fail_chain=None) -> np.ndarray:
    """CHAINS linked chains of three between drawn accounts; `fail_chain`
    gets a link that fails (amount zero) and rolls back whole."""
    n = 3 * CHAINS
    t = np.zeros(n, dtype=types.TRANSFER_DTYPE)
    t["id_lo"] = first_id + np.arange(n)
    dr = rng.integers(1, accounts + 1, n)
    t["debit_account_id_lo"] = dr
    t["credit_account_id_lo"] = 1 + (dr + rng.integers(0, accounts - 1, n)) % accounts
    t["amount_lo"] = rng.integers(1, 1000, n)
    t["ledger"] = 1
    t["code"] = 7
    t["flags"] = np.tile([LINKED, LINKED, 0], CHAINS)
    if fail_chain is not None:
        t["amount_lo"][3 * fail_chain + 1] = 0
    return t


def _script(seed: int) -> list:
    """(kind, events): `chains` batches defer; `repeat` names an id of the
    batch before it; `history` posts on account 9; `post` posts a pending of
    an earlier batch."""
    rng = np.random.default_rng(seed)
    out, next_id = [], 1000
    for i in range(BATCHES):
        kind = {7: "repeat", 11: "history", 15: "post"}.get(i, "chains")
        t = _chains(rng, next_id, fail_chain=i % CHAINS if i % 3 == 0 else None)
        if kind == "repeat":
            t[7] = out[-1][1][7]  # a stored row of it, byte for byte: `exists`
        elif kind == "history":
            t["credit_account_id_lo"][6] = HISTORY
        elif kind == "post":
            t["flags"][14] = POST
            t["pending_id_lo"][14] = pending_id
            t["amount_lo"][14] = 0
            t["debit_account_id_lo"][14] = t["credit_account_id_lo"][14] = 0
        if i == 3:  # a pending alone does not make a batch wait
            t["flags"][14] = PENDING
            pending_id = int(t["id_lo"][14])
        out.append((kind, t))
        next_id += len(t)
    return out


def _cluster(store_async: bool, overlap: bool = False) -> Cluster:
    from tigerbeetle_tpu.vsr.clock import Clock, DeterministicTime

    cl = Cluster(replica_count=1, seed=29, sm_backend="jax",
                 store_async=store_async, overlap=overlap)
    for r in cl.replicas:  # timestamps from the op stream alone
        r.time = DeterministicTime(tick_ns=0)
        r.clock = Clock(r.time, cl.replica_count, r.replica)
    return cl


def _lookup(cl, c, operation, ids, dtype) -> np.ndarray:
    """A reply holds 64 records at this size: asked for in as many requests."""
    keys = np.zeros(len(ids), dtype=types.ID_DTYPE)
    keys["lo"] = ids
    return np.concatenate([
        np.frombuffer(bytearray(do_request(cl, c, operation, keys[at:at + 64].tobytes()).body),
                      dtype=dtype)
        for at in range(0, len(keys), 64)
    ])


@pytest.fixture(scope="module", params=[False, True], ids=["store_stage", "both_stages"])
def replayed(request):
    """The script through the replica and through the oracle, which is
    given each batch at the timestamp the replica gave it."""
    was = tracer.enabled()
    tracer.enable()
    tracer.reset()
    cl = _cluster(store_async=True, overlap=request.param)
    try:
        sm = cl.replicas[0].state_machine
        c = setup_client(cl)
        o = om.Oracle()
        acc = _accounts()
        assert len(parse_results(do_request(cl, c, Operation.CREATE_ACCOUNTS, acc.tobytes()))) == 0
        assert o.create_accounts([om.account_from_numpy(r) for r in acc], sm.prepare_timestamp) == []
        out = {"kinds": [], "codes": [], "want_codes": [], "routes": []}
        script = _script(41)
        for kind, events in script:
            before = dict(sm.stats)
            reply = do_request(cl, c, Operation.CREATE_TRANSFERS, events.tobytes())
            out["kinds"].append(kind)
            out["routes"].append([k for k, v in sm.stats.items() if v != before[k]])
            out["codes"].append([(int(r["index"]), int(r["result"])) for r in parse_results(reply)])
            out["want_codes"].append(o.create_transfers(
                [om.transfer_from_numpy(r) for r in events], sm.prepare_timestamp))
        ids = np.concatenate([t["id_lo"] for _kind, t in script])
        out["transfers"] = _lookup(cl, c, Operation.LOOKUP_TRANSFERS, ids, types.TRANSFER_DTYPE)
        out["want_transfers"] = types.batch(
            [om.transfer_to_numpy(t) for t in o.lookup_transfers(ids.tolist())],
            types.TRANSFER_DTYPE)
        account_ids = np.arange(1, PLAIN + 2)
        out["accounts"] = _lookup(cl, c, Operation.LOOKUP_ACCOUNTS, account_ids, types.ACCOUNT_DTYPE)
        out["want_accounts"] = types.batch(
            [om.account_to_numpy(a) for a in o.lookup_accounts(account_ids.tolist())],
            types.ACCOUNT_DTYPE)
        cl.quiesce()
        out["history"] = sm.get_account_history(HISTORY)
        out["want_history"] = o.get_account_history(HISTORY)
        out["count"] = {k: v["count"] for k, v in tracer.snapshot().items()}
        return out
    finally:
        cl.close()
        tracer.reset()
        if not was:
            tracer.disable()


def test_result_codes_are_the_oracles(replayed):
    assert replayed["codes"] == replayed["want_codes"]
    flat = [code for batch in replayed["codes"] for _ix, code in batch]
    assert int(TR.LINKED_EVENT_FAILED) in flat and int(TR.EXISTS) in flat  # something to compare


def test_balances_are_the_oracles(replayed):
    assert replayed["accounts"].tobytes() == replayed["want_accounts"].tobytes()
    assert replayed["accounts"]["debits_posted_lo"].any()


def test_every_id_reads_back_as_the_oracle_stored_it(replayed):
    """Timestamps included, so also the order in which the rows were given
    to the store, on whichever thread."""
    assert len(replayed["transfers"]) == len(replayed["want_transfers"]) > 0
    assert replayed["transfers"].tobytes() == replayed["want_transfers"].tobytes()


def test_batches_without_a_store_read_deferred_and_the_others_did_not(replayed):
    """Every batch ran the exact kernel but the one that repeats an id
    (serial); of those, the post and the history batch kept the barrier."""
    for kind, routes in zip(replayed["kinds"], replayed["routes"]):
        assert routes == (["serial_batches"] if kind == "repeat" else ["exact_batches"])
    count = replayed["count"]
    assert count["sm.route.exact_batches"] == BATCHES - 1
    assert count["sm.exact.store_deferred"] == BATCHES - 3


def test_the_history_account_has_its_row(replayed):
    assert replayed["history"] == replayed["want_history"] and len(replayed["history"]) == 1


# --- with the store thread held: what is read from the pending write buffer -----


def _held(cl):
    """The stage's condition, held by this (the loop's) thread: the worker
    cannot pop a job; a barrier's wait() lets go of it, so the worker
    catches up exactly where the commit path asks it to."""
    return cl.replicas[0].store_executor._cond


def test_a_repeated_id_of_a_queued_batch_answers_exists():
    """The batch before is acknowledged and its rows are still queued: the
    repeat is found in the pending write buffer, routes serial and answers
    `exists`, and its chain rolls back."""
    rng = np.random.default_rng(5)
    cl = _cluster(store_async=True)
    try:
        sm, se = cl.replicas[0].state_machine, cl.replicas[0].store_executor
        c = setup_client(cl)
        do_request(cl, c, Operation.CREATE_ACCOUNTS, _accounts().tobytes())
        do_request(cl, c, Operation.CREATE_TRANSFERS, _chains(rng, 100).tobytes())  # compiled
        cl.quiesce()
        with _held(cl):
            first = _chains(rng, 200)
            assert len(parse_results(do_request(cl, c, Operation.CREATE_TRANSFERS, first.tobytes()))) == 0
            # no barrier was taken: the acknowledged rows are not in the store yet
            (queued,) = se.unapplied_stores()
            assert queued[0]["id_lo"].tolist() == first["id_lo"].tolist()
            assert sm.stats["exact_batches"] == 2 and not sm.stats["serial_batches"]
            again = _chains(rng, 300)
            again[4] = first[4]  # the middle of the second chain, byte for byte
            again["flags"][4] = LINKED
            res = parse_results(do_request(cl, c, Operation.CREATE_TRANSFERS, again.tobytes()))
            assert [(int(r["index"]), int(r["result"])) for r in res] == [
                (3, int(TR.LINKED_EVENT_FAILED)), (4, int(TR.EXISTS)),
                (5, int(TR.LINKED_EVENT_FAILED))]
            assert sm.stats["serial_batches"] == 1
            assert se.unapplied_stores() == []  # the serial path drained the stage
        got = _lookup(cl, c, Operation.LOOKUP_TRANSFERS,
                      np.concatenate([first["id_lo"], again["id_lo"]]), types.TRANSFER_DTYPE)
        # all of the first batch, and of the second all but its rolled-back chain
        # (whose middle link IS the first batch's row, found once more)
        assert got["id_lo"].tolist() == first["id_lo"].tolist() + [
            int(i) for k, i in enumerate(again["id_lo"]) if k not in (3, 5)]
    finally:
        cl.close()


def test_a_history_account_keeps_the_barrier_and_writes_its_row():
    """Behind a queued batch, a chain batch on the history account drains
    the stage before it writes the groove inline."""
    rng = np.random.default_rng(6)
    cl = _cluster(store_async=True)
    try:
        sm, se = cl.replicas[0].state_machine, cl.replicas[0].store_executor
        c = setup_client(cl)
        do_request(cl, c, Operation.CREATE_ACCOUNTS, _accounts().tobytes())
        with _held(cl):
            do_request(cl, c, Operation.CREATE_TRANSFERS, _chains(rng, 100).tobytes())
            assert len(se.unapplied_stores()) == 1
            t = _chains(rng, 200)
            t["debit_account_id_lo"][0] = HISTORY
            t["credit_account_id_lo"][0] = 1
            assert len(parse_results(do_request(cl, c, Operation.CREATE_TRANSFERS, t.tobytes()))) == 0
            assert se.unapplied_stores() == [] and sm.stats["exact_batches"] == 2
            assert sm._deferred_store is None  # stored inline, nothing handed on
        rows = sm.get_account_history(HISTORY)
        assert len(rows) == 1 and rows[0][2] == int(t["amount_lo"][0])  # debits_posted
    finally:
        cl.close()


def test_with_and_without_the_stage_the_same_bytes():
    """The deferred rows are applied before the batch's beat wherever that
    runs (store(N), beat(N), store(N+1)): commit checksums and the
    checkpoint's trailer digests are those of the inline store."""
    runs = []
    for store_async in (False, True):
        cl = _cluster(store_async=store_async)
        try:
            c = setup_client(cl)
            do_request(cl, c, Operation.CREATE_ACCOUNTS, _accounts().tobytes())
            for _kind, events in _script(43):
                do_request(cl, c, Operation.CREATE_TRANSFERS, events.tobytes())
            cl.quiesce()
            assert cl.replicas[0].superblock.state.op_checkpoint >= 16
            runs.append((dict(cl.replicas[0].commit_checksums), dict(cl._checkpoint_history)))
        finally:
            cl.close()
    assert runs[0][0] == runs[1][0] and len(runs[0][0]) == BATCHES + 2
    assert runs[0][1] and runs[0][1] == runs[1][1]
