"""StateMachine (device kernels + host orchestration) vs Oracle byte-equality.

The acceptance bar from SURVEY.md §7: byte-identical balances and result
arrays between the TPU-path state machine and the serial oracle, across all
semantic features (linked chains, pending/post/void, balancing, limits,
duplicates). Random workloads are generated so that both the parallel fast
path and the serial fallback are exercised (see `sm.stats` assertions).
"""

import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.constants import TEST_MIN, Config
from tigerbeetle_tpu.flags import AccountFlags, TransferFlags
from tigerbeetle_tpu.models.oracle import (
    Oracle,
    account_from_numpy,
    transfer_from_numpy,
)
from tigerbeetle_tpu.models.state_machine import StateMachine
from tigerbeetle_tpu.results import CreateTransferResult as TR

CFG = Config(name="unit", accounts_max=1 << 12, transfers_max=1 << 14, batch_max=64)


def run_both(account_batches, transfer_batches, backend="jax"):
    """Run the same batches through StateMachine and Oracle; compare exactly."""
    sm = StateMachine(CFG, backend=backend)
    orc = Oracle()
    for batch in account_batches:
        ts = orc.prepare("create_accounts", len(batch))
        expected = orc.create_accounts([account_from_numpy(r) for r in batch], ts)
        got = sm.create_accounts(batch)
        assert [(int(i), int(r)) for i, r in zip(got["index"], got["result"])] == [
            (i, r) for i, r in expected
        ], f"create_accounts results diverge"
    for batch in transfer_batches:
        ts = orc.prepare("create_transfers", len(batch))
        expected = orc.create_transfers([transfer_from_numpy(r) for r in batch], ts)
        got = sm.create_transfers(batch)
        assert [(int(i), int(r)) for i, r in zip(got["index"], got["result"])] == [
            (i, r) for i, r in expected
        ], f"create_transfers results diverge"
    check_equal(sm, orc)
    return sm, orc


def check_equal(sm: StateMachine, orc: Oracle):
    """Byte-compare every account and transfer between the two."""
    ids = sorted(orc.accounts.keys())
    lo = np.array([i & types.U64_MAX for i in ids], dtype=np.uint64)
    hi = np.array([i >> 64 for i in ids], dtype=np.uint64)
    recs = sm.lookup_accounts(lo, hi)
    assert len(recs) == len(ids)
    for rec, ident in zip(recs, ids):
        a = orc.accounts[ident]
        assert types.u128_of(rec, "id") == a.id
        for f in ("debits_pending", "debits_posted", "credits_pending", "credits_posted"):
            assert types.u128_of(rec, f) == getattr(a, f), (
                f"account {ident} field {f}: {types.u128_of(rec, f)} != {getattr(a, f)}"
            )
        assert int(rec["ledger"]) == a.ledger
        assert int(rec["flags"]) == a.flags
        assert int(rec["timestamp"]) == a.timestamp

    tids = sorted(orc.transfers.keys())
    tlo = np.array([i & types.U64_MAX for i in tids], dtype=np.uint64)
    thi = np.array([i >> 64 for i in tids], dtype=np.uint64)
    trecs = sm.lookup_transfers(tlo, thi)
    assert len(trecs) == len(tids)
    for rec, ident in zip(trecs, tids):
        t = orc.transfers[ident]
        got = transfer_from_numpy(rec)
        assert got == t, f"transfer {ident}: {got} != {t}"

    assert sm.commit_timestamp == orc.commit_timestamp


def simple_accounts(n, ledger=1, flags=0, start_id=1):
    return types.batch(
        [types.account(id=start_id + i, ledger=ledger, code=10, flags=flags) for i in range(n)],
        types.ACCOUNT_DTYPE,
    )


class TestFastPath:
    def test_simple_transfers(self):
        accounts = simple_accounts(4)
        transfers = types.batch(
            [
                types.transfer(id=100 + i, debit_account_id=1 + (i % 3), credit_account_id=4,
                               amount=10 + i, ledger=1, code=7)
                for i in range(16)
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [transfers])
        assert sm.stats["fast_batches"] == 1
        assert sm.stats["serial_batches"] == 0

    def test_pending_transfers_fast(self):
        accounts = simple_accounts(2)
        transfers = types.batch(
            [
                types.transfer(id=100 + i, debit_account_id=1, credit_account_id=2,
                               amount=5, timeout=100, ledger=1, code=7,
                               flags=TransferFlags.PENDING)
                for i in range(8)
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [transfers])
        assert sm.stats["fast_batches"] == 1

    def test_validation_errors_fast(self):
        accounts = simple_accounts(3)
        bad = [
            types.transfer(id=0, debit_account_id=1, credit_account_id=2, amount=1, ledger=1, code=1),
            types.transfer(id=types.U128_MAX, debit_account_id=1, credit_account_id=2, amount=1, ledger=1, code=1),
            types.transfer(id=201, debit_account_id=0, credit_account_id=2, amount=1, ledger=1, code=1),
            types.transfer(id=202, debit_account_id=1, credit_account_id=1, amount=1, ledger=1, code=1),
            types.transfer(id=203, debit_account_id=1, credit_account_id=2, amount=0, ledger=1, code=1),
            types.transfer(id=204, debit_account_id=1, credit_account_id=2, amount=1, ledger=0, code=1),
            types.transfer(id=205, debit_account_id=1, credit_account_id=2, amount=1, ledger=1, code=0),
            types.transfer(id=206, debit_account_id=1, credit_account_id=2, amount=1, ledger=1, code=1, timeout=5),
            types.transfer(id=207, debit_account_id=99, credit_account_id=2, amount=1, ledger=1, code=1),
            types.transfer(id=208, debit_account_id=1, credit_account_id=99, amount=1, ledger=1, code=1),
            types.transfer(id=209, debit_account_id=1, credit_account_id=2, amount=1, ledger=2, code=1),
            types.transfer(id=210, debit_account_id=1, credit_account_id=2, amount=1, ledger=1, code=1, pending_id=5),
            types.transfer(id=211, debit_account_id=1, credit_account_id=2, amount=1, ledger=1, code=1, timestamp=77),
            types.transfer(id=212, debit_account_id=1, credit_account_id=2, amount=1, ledger=1, code=1),
        ]
        sm, orc = run_both([accounts], [types.batch(bad, types.TRANSFER_DTYPE)])
        assert sm.stats["fast_batches"] == 1

    def test_ledger_mismatch_between_accounts(self):
        a1 = simple_accounts(2, ledger=1, start_id=1)
        a2 = simple_accounts(2, ledger=2, start_id=10)
        transfers = types.batch(
            [types.transfer(id=100, debit_account_id=1, credit_account_id=10, amount=1,
                            ledger=1, code=1)],
            types.TRANSFER_DTYPE,
        )
        run_both([a1, a2], [transfers])


class TestSerialPath:
    def test_linked_chain_rollback(self):
        accounts = simple_accounts(4)
        L = TransferFlags.LINKED
        transfers = types.batch(
            [
                types.transfer(id=1, debit_account_id=1, credit_account_id=2, amount=10, ledger=1, code=1, flags=L),
                types.transfer(id=2, debit_account_id=1, credit_account_id=2, amount=0, ledger=1, code=1),  # fails → chain rolls back
                types.transfer(id=3, debit_account_id=3, credit_account_id=4, amount=5, ledger=1, code=1),
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [transfers])
        assert sm.stats["exact_batches"] == 1  # linked chains run on-device (r3)

    def test_duplicate_ids_nonadjacent_after_lo_sort(self):
        # Regression: ids (hi=1,lo=5),(hi=2,lo=5),(hi=1,lo=5) — a lo-only
        # stable sort leaves the duplicates non-adjacent; the dup check
        # must still route the batch serial for the exists ladder.
        accounts = simple_accounts(2)
        t = []
        for hi in (1, 2, 1):
            rec = types.transfer(id=5 | (hi << 64), debit_account_id=1,
                                 credit_account_id=2, amount=3, ledger=1, code=1)
            t.append(rec)
        sm, orc = run_both([accounts], [types.batch(t, types.TRANSFER_DTYPE)])
        assert sm.stats["serial_batches"] == 1
        assert 5 | (1 << 64) in orc.transfers

    def test_pending_post_void(self):
        accounts = simple_accounts(2)
        P = TransferFlags.PENDING
        transfers1 = types.batch(
            [
                types.transfer(id=1, debit_account_id=1, credit_account_id=2, amount=100, ledger=1, code=1, flags=P),
                types.transfer(id=2, debit_account_id=1, credit_account_id=2, amount=50, ledger=1, code=1, flags=P),
            ],
            types.TRANSFER_DTYPE,
        )
        transfers2 = types.batch(
            [
                types.transfer(id=10, pending_id=1, ledger=1, code=1,
                               flags=TransferFlags.POST_PENDING_TRANSFER),
                types.transfer(id=11, pending_id=2, ledger=1, code=1,
                               flags=TransferFlags.VOID_PENDING_TRANSFER),
                types.transfer(id=12, pending_id=1, ledger=1, code=1,
                               flags=TransferFlags.POST_PENDING_TRANSFER),  # already posted
            ],
            types.TRANSFER_DTYPE,
        )
        run_both([accounts], [transfers1, transfers2])

    def test_post_pending_same_batch(self):
        accounts = simple_accounts(2)
        transfers = types.batch(
            [
                types.transfer(id=1, debit_account_id=1, credit_account_id=2, amount=100,
                               ledger=1, code=1, flags=TransferFlags.PENDING),
                types.transfer(id=2, pending_id=1, amount=40, ledger=1, code=1,
                               flags=TransferFlags.POST_PENDING_TRANSFER),
            ],
            types.TRANSFER_DTYPE,
        )
        run_both([accounts], [transfers])

    def test_balancing_transfers(self):
        accounts = types.batch(
            [
                types.account(id=1, ledger=1, code=1),
                types.account(id=2, ledger=1, code=1),
            ],
            types.ACCOUNT_DTYPE,
        )
        seed = types.batch(
            [types.transfer(id=1, debit_account_id=2, credit_account_id=1, amount=70, ledger=1, code=1)],
            types.TRANSFER_DTYPE,
        )
        balancing = types.batch(
            [
                types.transfer(id=2, debit_account_id=1, credit_account_id=2, amount=100,
                               ledger=1, code=1, flags=TransferFlags.BALANCING_DEBIT),
                types.transfer(id=3, debit_account_id=1, credit_account_id=2, amount=100,
                               ledger=1, code=1, flags=TransferFlags.BALANCING_DEBIT),
            ],
            types.TRANSFER_DTYPE,
        )
        run_both([accounts], [seed, balancing])

    def test_limit_flags_route_exact_kernel(self):
        accounts = types.batch(
            [
                types.account(id=1, ledger=1, code=1,
                              flags=AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS),
                types.account(id=2, ledger=1, code=1),
            ],
            types.ACCOUNT_DTYPE,
        )
        transfers = types.batch(
            [
                types.transfer(id=1, debit_account_id=2, credit_account_id=1, amount=30, ledger=1, code=1),
                types.transfer(id=2, debit_account_id=1, credit_account_id=2, amount=20, ledger=1, code=1),
                types.transfer(id=3, debit_account_id=1, credit_account_id=2, amount=20, ledger=1, code=1),  # exceeds
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [transfers])
        assert sm.stats["exact_batches"] >= 1
        assert sm.stats["serial_batches"] == 0

    def test_duplicate_ids_in_batch(self):
        accounts = simple_accounts(2)
        transfers = types.batch(
            [
                types.transfer(id=7, debit_account_id=1, credit_account_id=2, amount=3, ledger=1, code=1),
                types.transfer(id=7, debit_account_id=1, credit_account_id=2, amount=3, ledger=1, code=1),
                types.transfer(id=7, debit_account_id=1, credit_account_id=2, amount=4, ledger=1, code=1),
            ],
            types.TRANSFER_DTYPE,
        )
        run_both([accounts], [transfers])

    def test_exists_across_batches(self):
        accounts = simple_accounts(2)
        t1 = types.batch(
            [types.transfer(id=7, debit_account_id=1, credit_account_id=2, amount=3, ledger=1, code=1)],
            types.TRANSFER_DTYPE,
        )
        t2 = types.batch(
            [
                types.transfer(id=7, debit_account_id=1, credit_account_id=2, amount=3, ledger=1, code=1),
                types.transfer(id=7, debit_account_id=1, credit_account_id=2, amount=9, ledger=1, code=1),
            ],
            types.TRANSFER_DTYPE,
        )
        run_both([accounts], [t1, t2])

    def test_history_accounts(self):
        accounts = types.batch(
            [
                types.account(id=1, ledger=1, code=1, flags=AccountFlags.HISTORY),
                types.account(id=2, ledger=1, code=1),
            ],
            types.ACCOUNT_DTYPE,
        )
        transfers = types.batch(
            [
                types.transfer(id=1, debit_account_id=1, credit_account_id=2, amount=5, ledger=1, code=1),
                types.transfer(id=2, debit_account_id=2, credit_account_id=1, amount=3, ledger=1, code=1),
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [transfers])
        got = sm.get_account_history(1)
        want = orc.get_account_history(1)
        assert got == want and len(got) == 2


class TestRandomized:
    """Property tests: random mixed workloads, fast+serial interleaved."""

    @pytest.mark.parametrize("seed", range(8))
    def test_random_workload(self, seed):
        rng = np.random.default_rng(seed)
        n_accounts = 12
        account_batches = []
        recs = []
        for i in range(n_accounts):
            flags = 0
            r = rng.random()
            if r < 0.15:
                flags = int(AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS)
            elif r < 0.25:
                flags = int(AccountFlags.CREDITS_MUST_NOT_EXCEED_DEBITS)
            elif r < 0.3:
                flags = int(AccountFlags.HISTORY)
            recs.append(
                types.account(id=i + 1, ledger=int(rng.integers(1, 3)), code=1, flags=flags)
            )
        account_batches.append(types.batch(recs, types.ACCOUNT_DTYPE))

        transfer_batches = []
        next_id = 1000
        pending_ids = []
        for _ in range(6):
            batch = []
            bn = int(rng.integers(1, 24))
            for _ in range(bn):
                kind = rng.random()
                flags = 0
                pending_id = 0
                amount = int(rng.integers(0, 50))
                timeout = 0
                if kind < 0.12 and pending_ids:
                    flags = int(
                        TransferFlags.POST_PENDING_TRANSFER
                        if rng.random() < 0.5
                        else TransferFlags.VOID_PENDING_TRANSFER
                    )
                    pending_id = int(rng.choice(pending_ids))
                    amount = int(rng.integers(0, 30))
                elif kind < 0.3:
                    flags = int(TransferFlags.PENDING)
                    timeout = int(rng.integers(0, 5))
                    pending_ids.append(next_id)
                elif kind < 0.4:
                    flags = int(
                        TransferFlags.BALANCING_DEBIT
                        if rng.random() < 0.5
                        else TransferFlags.BALANCING_CREDIT
                    )
                if rng.random() < 0.2:
                    flags |= int(TransferFlags.LINKED)
                # occasionally duplicate an id
                tid = next_id
                if rng.random() < 0.08 and next_id > 1000:
                    tid = int(rng.integers(1000, next_id))
                else:
                    next_id += 1
                batch.append(
                    types.transfer(
                        id=tid,
                        debit_account_id=int(rng.integers(0, n_accounts + 2)),
                        credit_account_id=int(rng.integers(1, n_accounts + 2)),
                        amount=amount,
                        pending_id=pending_id,
                        timeout=timeout,
                        ledger=int(rng.integers(1, 3)),
                        code=int(rng.integers(0, 3)),
                        flags=flags,
                    )
                )
            # last event must not leave a chain open *sometimes* — leave as
            # generated; the oracle handles chain-open errors too.
            transfer_batches.append(types.batch(batch, types.TRANSFER_DTYPE))
        run_both(account_batches, transfer_batches)

    @pytest.mark.parametrize("seed", range(4))
    def test_random_simple_heavy(self, seed):
        """Mostly-fast-path workload with occasional hard batches."""
        rng = np.random.default_rng(1000 + seed)
        accounts = simple_accounts(32)
        batches = []
        next_id = 1
        for b in range(5):
            bn = int(rng.integers(16, 64))
            batch = []
            for _ in range(bn):
                batch.append(
                    types.transfer(
                        id=next_id,
                        debit_account_id=int(rng.integers(1, 33)),
                        credit_account_id=int(rng.integers(1, 33)),
                        amount=int(rng.integers(1, 1000)),
                        ledger=1,
                        code=1,
                        flags=int(TransferFlags.PENDING) if rng.random() < 0.2 else 0,
                    )
                )
                next_id += 1
            batches.append(types.batch(batch, types.TRANSFER_DTYPE))
        sm, orc = run_both([accounts], batches)
        assert sm.stats["fast_batches"] >= 3


class TestReadOps:
    def test_get_account_transfers(self):
        accounts = simple_accounts(3)
        transfers = types.batch(
            [
                types.transfer(id=i + 1, debit_account_id=1 + (i % 2), credit_account_id=3,
                               amount=i + 1, ledger=1, code=1)
                for i in range(10)
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [transfers])
        from tigerbeetle_tpu.flags import AccountFilterFlags as FF

        for aid in (1, 2, 3):
            for flags in (FF.DEBITS, FF.CREDITS, FF.DEBITS | FF.CREDITS,
                          FF.DEBITS | FF.CREDITS | FF.REVERSED):
                got = sm.get_account_transfers(aid, flags=int(flags), limit=5)
                want = orc.get_account_transfers(aid, flags=int(flags), limit=5)
                assert len(got) == len(want)
                for rec, t in zip(got, want):
                    assert transfer_from_numpy(rec) == t

    def test_get_account_transfers_timestamp_window(self):
        """timestamp_min/max windows + limit + REVERSED, vs the oracle
        (reference AccountFilter semantics, tigerbeetle.zig:268)."""
        accounts = simple_accounts(3)
        transfers = types.batch(
            [
                types.transfer(id=i + 1, debit_account_id=1 + (i % 2),
                               credit_account_id=3, amount=i + 1, ledger=1, code=1)
                for i in range(12)
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [transfers])
        from tigerbeetle_tpu.flags import AccountFilterFlags as FF

        all_ts = sorted(
            int(t["timestamp"]) for t in sm.get_account_transfers(3, limit=100)
        )
        assert len(all_ts) == 12
        lo, hi = all_ts[3], all_ts[8]
        for ts_min, ts_max in ((lo, hi), (0, hi), (lo, 0), (hi, lo)):
            for flags in (FF.DEBITS | FF.CREDITS,
                          FF.DEBITS | FF.CREDITS | FF.REVERSED):
                for limit in (2, 100):
                    got = sm.get_account_transfers(
                        3, timestamp_min=ts_min, timestamp_max=ts_max,
                        limit=limit, flags=int(flags),
                    )
                    want = orc.get_account_transfers(
                        3, timestamp_min=ts_min, timestamp_max=ts_max,
                        limit=limit, flags=int(flags),
                    )
                    assert len(got) == len(want), (ts_min, ts_max, flags, limit)
                    for rec, t in zip(got, want):
                        assert transfer_from_numpy(rec) == t

    def test_get_account_history_filters(self):
        """History filter axes (window/limit/REVERSED/side flags) vs the
        oracle, over the durable history groove."""
        from tigerbeetle_tpu.flags import AccountFlags
        from tigerbeetle_tpu.flags import AccountFilterFlags as FF

        accounts = types.batch(
            [
                types.account(id=1, ledger=1, code=10,
                              flags=int(AccountFlags.HISTORY)),
                types.account(id=2, ledger=1, code=10),
                types.account(id=3, ledger=1, code=10,
                              flags=int(AccountFlags.HISTORY)),
            ],
            types.ACCOUNT_DTYPE,
        )
        transfers = types.batch(
            [
                types.transfer(id=i + 1, debit_account_id=1 + (i % 2),
                               credit_account_id=3, amount=5 + i, ledger=1, code=1)
                for i in range(10)
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [transfers])
        rows = sm.get_account_history(1)
        assert len(rows) == len(orc.get_account_history(1)) > 0
        ts_mid = rows[len(rows) // 2][0]
        for aid in (1, 2, 3):
            for ts_min, ts_max in ((0, 0), (ts_mid, 0), (0, ts_mid)):
                for flags in (FF.DEBITS, FF.CREDITS, FF.DEBITS | FF.CREDITS,
                              FF.DEBITS | FF.CREDITS | FF.REVERSED):
                    for limit in (3, 100):
                        got = sm.get_account_history(
                            aid, timestamp_min=ts_min, timestamp_max=ts_max,
                            limit=limit, flags=int(flags),
                        )
                        want = orc.get_account_history(
                            aid, timestamp_min=ts_min, timestamp_max=ts_max,
                            limit=limit, flags=int(flags),
                        )
                        assert got == want, (aid, ts_min, ts_max, flags, limit)

    def test_lookup_missing(self):
        sm = StateMachine(CFG)
        out = sm.lookup_accounts(np.array([5], dtype=np.uint64), np.array([0], dtype=np.uint64))
        assert len(out) == 0


class TestNumpyBackend:
    """The CPU-fallback fast path (models/host_kernel.py) must be byte-exact
    too — rerun the representative suites with backend='numpy'."""

    def test_simple_transfers_numpy(self):
        accounts = simple_accounts(4)
        transfers = types.batch(
            [
                types.transfer(id=100 + i, debit_account_id=1 + (i % 3),
                               credit_account_id=4, amount=10 + i, ledger=1, code=7)
                for i in range(16)
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [transfers], backend="numpy")
        assert sm.stats["fast_batches"] == 1

    def test_validation_errors_numpy(self):
        accounts = simple_accounts(3)
        bad = [
            types.transfer(id=0, debit_account_id=1, credit_account_id=2, amount=1, ledger=1, code=1),
            types.transfer(id=201, debit_account_id=0, credit_account_id=2, amount=1, ledger=1, code=1),
            types.transfer(id=203, debit_account_id=1, credit_account_id=2, amount=0, ledger=1, code=1),
            types.transfer(id=206, debit_account_id=1, credit_account_id=2, amount=1, ledger=1, code=1, timeout=5),
            types.transfer(id=207, debit_account_id=99, credit_account_id=2, amount=1, ledger=1, code=1),
            types.transfer(id=211, debit_account_id=1, credit_account_id=2, amount=1, ledger=1, code=1, timestamp=77),
            types.transfer(id=212, debit_account_id=1, credit_account_id=2, amount=1, ledger=1, code=1,
                           flags=TransferFlags.PENDING, timeout=3),
        ]
        run_both([accounts], [types.batch(bad, types.TRANSFER_DTYPE)], backend="numpy")

    @pytest.mark.parametrize("seed", range(4))
    def test_random_workload_numpy(self, seed):
        rng = np.random.default_rng(4000 + seed)
        accounts = simple_accounts(16)
        batches = []
        next_id = 1
        for _ in range(4):
            bn = int(rng.integers(8, 48))
            batch = []
            for _ in range(bn):
                batch.append(
                    types.transfer(
                        id=next_id,
                        debit_account_id=int(rng.integers(0, 18)),
                        credit_account_id=int(rng.integers(1, 18)),
                        amount=int(rng.integers(0, 1000)),
                        ledger=int(rng.integers(1, 3)),
                        code=int(rng.integers(0, 3)),
                        flags=int(TransferFlags.PENDING) if rng.random() < 0.3 else 0,
                        timeout=int(rng.integers(0, 3)),
                    )
                )
                next_id += 1
            batches.append(types.batch(batch, types.TRANSFER_DTYPE))
        sm, orc = run_both([accounts], batches, backend="numpy")
        assert sm.stats["fast_batches"] >= 2


class TestExactKernel:
    """Fixed-point sweep kernel (ops/commit_exact.py): convergence under
    deep same-account dependency chains, clamp exactness, history balances."""

    def test_balancing_chain_on_hot_account(self):
        # Many balancing debits draining ONE account: each clamp depends on
        # every predecessor (worst-case dependency depth). Must still be
        # byte-exact — either by converging or by bailing to serial.
        accounts = types.batch(
            [types.account(id=i, ledger=1, code=1) for i in (1, 2, 3)],
            types.ACCOUNT_DTYPE,
        )
        seed = types.batch(
            [types.transfer(id=1, debit_account_id=2, credit_account_id=1,
                            amount=100, ledger=1, code=1)],
            types.TRANSFER_DTYPE,
        )
        drains = types.batch(
            [
                types.transfer(id=10 + k, debit_account_id=1, credit_account_id=3,
                               amount=9, ledger=1, code=1,
                               flags=TransferFlags.BALANCING_DEBIT)
                for k in range(20)
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [seed, drains])
        # 100/9 → 11 full drains, the 12th clamps to 1, the rest EXCEEDS.
        assert orc.transfers[10 + 11].amount == 1

    def test_balancing_zero_amount_sentinel(self):
        # amount=0 + balancing → drain everything available (u64-max sentinel).
        accounts = types.batch(
            [types.account(id=i, ledger=1, code=1) for i in (1, 2)],
            types.ACCOUNT_DTYPE,
        )
        seed = types.batch(
            [types.transfer(id=1, debit_account_id=2, credit_account_id=1,
                            amount=12345, ledger=1, code=1)],
            types.TRANSFER_DTYPE,
        )
        drain = types.batch(
            [types.transfer(id=2, debit_account_id=1, credit_account_id=2,
                            amount=0, ledger=1, code=1,
                            flags=TransferFlags.BALANCING_DEBIT)],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [seed, drain])
        assert orc.transfers[2].amount == 12345

    def test_limit_and_history_mixed_batch(self):
        accounts = types.batch(
            [
                types.account(id=1, ledger=1, code=1,
                              flags=AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS
                              | AccountFlags.HISTORY),
                types.account(id=2, ledger=1, code=1, flags=AccountFlags.HISTORY),
                types.account(id=3, ledger=1, code=1),
            ],
            types.ACCOUNT_DTYPE,
        )
        transfers = types.batch(
            [
                types.transfer(id=1, debit_account_id=3, credit_account_id=1,
                               amount=50, ledger=1, code=1),
                types.transfer(id=2, debit_account_id=1, credit_account_id=2,
                               amount=30, ledger=1, code=1),
                types.transfer(id=3, debit_account_id=1, credit_account_id=2,
                               amount=30, ledger=1, code=1),  # exceeds credits
                types.transfer(id=4, debit_account_id=1, credit_account_id=3,
                               amount=20, ledger=1, code=1),
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [transfers])
        assert sm.stats["exact_batches"] == 1
        for acct in (1, 2):
            assert sm.get_account_history(acct) == orc.get_account_history(acct)

    @pytest.mark.parametrize("seed", range(6))
    def test_random_balancing_limits_heavy(self, seed):
        # BASELINE config-4-shaped randomized workload: balancing flags +
        # must_not_exceed accounts, no linked/post/void — all batches must
        # take the exact kernel (or bail), never diverge from the oracle.
        rng = np.random.default_rng(1000 + seed)
        n_accounts = 8
        recs = []
        for i in range(n_accounts):
            r = rng.random()
            flags = 0
            if r < 0.3:
                flags = int(AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS)
            elif r < 0.5:
                flags = int(AccountFlags.CREDITS_MUST_NOT_EXCEED_DEBITS)
            elif r < 0.6:
                flags = int(AccountFlags.HISTORY)
            recs.append(types.account(id=i + 1, ledger=1, code=1, flags=flags))
        account_batches = [types.batch(recs, types.ACCOUNT_DTYPE)]

        batches = []
        next_id = 1
        for _ in range(5):
            batch = []
            for _ in range(int(rng.integers(4, 32))):
                r = rng.random()
                flags = 0
                if r < 0.4:
                    flags = int(
                        TransferFlags.BALANCING_DEBIT
                        if rng.random() < 0.5
                        else TransferFlags.BALANCING_CREDIT
                    )
                elif r < 0.5:
                    flags = int(TransferFlags.PENDING)
                batch.append(
                    types.transfer(
                        id=next_id,
                        debit_account_id=int(rng.integers(1, n_accounts + 1)),
                        credit_account_id=int(rng.integers(1, n_accounts + 1)),
                        amount=int(rng.integers(0, 60)),
                        timeout=int(rng.integers(0, 3)) if flags == int(TransferFlags.PENDING) else 0,
                        ledger=1,
                        code=1,
                        flags=flags,
                    )
                )
                next_id += 1
            batches.append(types.batch(batch, types.TRANSFER_DTYPE))
        sm, orc = run_both(account_batches, batches)
        assert sm.stats["exact_batches"] + sm.stats["bail_batches"] >= 1


class TestExactKernelChainsAndPostVoid:
    """Round-3 kernel coverage: linked chains and pending post/void on
    device (reference state_machine.zig:1002-1088, :1391-1498)."""

    def test_chain_first_fail_reports_own_code(self):
        # Two failing events in one chain: serially only the FIRST is
        # evaluated (keeps its code); the rest report LINKED_EVENT_FAILED.
        accounts = simple_accounts(4)
        L = TransferFlags.LINKED
        transfers = types.batch(
            [
                types.transfer(id=1, debit_account_id=1, credit_account_id=2,
                               amount=10, ledger=1, code=1, flags=L),
                types.transfer(id=2, debit_account_id=1, credit_account_id=2,
                               amount=0, ledger=1, code=1, flags=L),  # first fail
                types.transfer(id=3, debit_account_id=1, credit_account_id=2,
                               amount=0, ledger=0, code=1),  # also bad, masked
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [transfers])
        assert sm.stats["exact_batches"] == 1

    def test_chain_open_trailing(self):
        accounts = simple_accounts(4)
        L = TransferFlags.LINKED
        transfers = types.batch(
            [
                types.transfer(id=1, debit_account_id=1, credit_account_id=2,
                               amount=10, ledger=1, code=1),
                types.transfer(id=2, debit_account_id=1, credit_account_id=2,
                               amount=10, ledger=1, code=1, flags=L),
                types.transfer(id=3, debit_account_id=3, credit_account_id=4,
                               amount=5, ledger=1, code=1, flags=L),  # open chain
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [transfers])
        assert sm.stats["exact_batches"] == 1

    def test_chain_open_in_broken_chain(self):
        # Earlier chain failure + unterminated tail: tail still reports
        # CHAIN_OPEN (oracle checks it before the broken-chain substitution).
        accounts = simple_accounts(4)
        L = TransferFlags.LINKED
        transfers = types.batch(
            [
                types.transfer(id=1, debit_account_id=1, credit_account_id=2,
                               amount=0, ledger=1, code=1, flags=L),  # fails
                types.transfer(id=2, debit_account_id=1, credit_account_id=2,
                               amount=10, ledger=1, code=1, flags=L),  # open tail
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [transfers])
        assert sm.stats["exact_batches"] == 1

    def test_multiple_chains_mixed(self):
        accounts = simple_accounts(6)
        L = TransferFlags.LINKED
        transfers = types.batch(
            [
                # chain 1: passes
                types.transfer(id=1, debit_account_id=1, credit_account_id=2,
                               amount=10, ledger=1, code=1, flags=L),
                types.transfer(id=2, debit_account_id=3, credit_account_id=4,
                               amount=10, ledger=1, code=1),
                # chain 2: fails in the middle
                types.transfer(id=3, debit_account_id=5, credit_account_id=6,
                               amount=10, ledger=1, code=1, flags=L),
                types.transfer(id=4, debit_account_id=5, credit_account_id=99,
                               amount=10, ledger=1, code=1, flags=L),  # no account
                types.transfer(id=5, debit_account_id=5, credit_account_id=6,
                               amount=10, ledger=1, code=1),
                # unlinked singleton after
                types.transfer(id=6, debit_account_id=1, credit_account_id=6,
                               amount=3, ledger=1, code=1),
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [transfers])
        assert sm.stats["exact_batches"] == 1
        assert 1 in orc.transfers and 6 in orc.transfers
        assert 4 not in orc.transfers and 5 not in orc.transfers

    def test_post_void_prior_batch_on_device(self):
        # Post/void of pendings created in EARLIER batches runs on-device.
        accounts = simple_accounts(2)
        P = TransferFlags.PENDING
        pendings = types.batch(
            [
                types.transfer(id=i, debit_account_id=1, credit_account_id=2,
                               amount=100 + i, ledger=1, code=1, flags=P)
                for i in range(1, 5)
            ],
            types.TRANSFER_DTYPE,
        )
        pv = types.batch(
            [
                types.transfer(id=10, pending_id=1, ledger=1, code=1,
                               flags=TransferFlags.POST_PENDING_TRANSFER),
                types.transfer(id=11, pending_id=2, amount=50, ledger=1, code=1,
                               flags=TransferFlags.POST_PENDING_TRANSFER),  # partial
                types.transfer(id=12, pending_id=3, ledger=1, code=1,
                               flags=TransferFlags.VOID_PENDING_TRANSFER),
                types.transfer(id=13, pending_id=1, ledger=1, code=1,
                               flags=TransferFlags.VOID_PENDING_TRANSFER),  # already posted
                types.transfer(id=14, pending_id=99, ledger=1, code=1,
                               flags=TransferFlags.POST_PENDING_TRANSFER),  # not found
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [pendings, pv])
        assert sm.stats["exact_batches"] >= 1
        assert sm.stats["serial_batches"] == 0
        assert orc.transfers[11].amount == 50

    def test_in_batch_fulfillment_race(self):
        # Two posts + one void of the SAME pending in one batch: first wins.
        accounts = simple_accounts(2)
        pendings = types.batch(
            [types.transfer(id=1, debit_account_id=1, credit_account_id=2,
                            amount=100, ledger=1, code=1,
                            flags=TransferFlags.PENDING)],
            types.TRANSFER_DTYPE,
        )
        pv = types.batch(
            [
                types.transfer(id=10, pending_id=1, ledger=1, code=1,
                               flags=TransferFlags.POST_PENDING_TRANSFER),
                types.transfer(id=11, pending_id=1, ledger=1, code=1,
                               flags=TransferFlags.POST_PENDING_TRANSFER),
                types.transfer(id=12, pending_id=1, ledger=1, code=1,
                               flags=TransferFlags.VOID_PENDING_TRANSFER),
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [pendings, pv])
        assert sm.stats["exact_batches"] >= 1

    def test_pv_mismatch_rungs(self):
        # Store-dependent rungs 25-30 computed host-side, merged exactly.
        accounts = simple_accounts(3)
        pendings = types.batch(
            [types.transfer(id=1, debit_account_id=1, credit_account_id=2,
                            amount=100, ledger=1, code=7,
                            flags=TransferFlags.PENDING),
             types.transfer(id=2, debit_account_id=1, credit_account_id=2,
                            amount=100, ledger=1, code=7)],  # NOT pending
            types.TRANSFER_DTYPE,
        )
        PP = TransferFlags.POST_PENDING_TRANSFER
        pv = types.batch(
            [
                types.transfer(id=10, pending_id=1, debit_account_id=3,
                               ledger=1, code=7, flags=PP),  # wrong dr
                types.transfer(id=11, pending_id=1, credit_account_id=3,
                               ledger=1, code=7, flags=PP),  # wrong cr
                types.transfer(id=12, pending_id=1, ledger=9, code=7, flags=PP),
                types.transfer(id=13, pending_id=1, ledger=1, code=9, flags=PP),
                types.transfer(id=14, pending_id=2, ledger=1, code=7, flags=PP),  # not pending
                types.transfer(id=15, pending_id=1, amount=500, ledger=1,
                               code=7, flags=PP),  # exceeds pending amount
                types.transfer(id=16, pending_id=1, amount=40, ledger=1, code=7,
                               flags=TransferFlags.VOID_PENDING_TRANSFER),  # diff amount
            ],
            types.TRANSFER_DTYPE,
        )
        sm, orc = run_both([accounts], [pendings, pv])
        assert sm.stats["exact_batches"] >= 1

    def test_pending_expiry_on_device(self):
        # timeout=1s pending expires once commit timestamps pass 1e9 ns.
        accounts = simple_accounts(2)
        pendings = types.batch(
            [types.transfer(id=1, debit_account_id=1, credit_account_id=2,
                            amount=10, timeout=1, ledger=1, code=1,
                            flags=TransferFlags.PENDING)],
            types.TRANSFER_DTYPE,
        )
        # Burn prepare_timestamp past the deadline with filler transfers.
        filler = types.batch(
            [types.transfer(id=1000 + i, debit_account_id=1, credit_account_id=2,
                            amount=1, ledger=1, code=1) for i in range(8)],
            types.TRANSFER_DTYPE,
        )
        pv = types.batch(
            [types.transfer(id=10, pending_id=1, ledger=1, code=1,
                            flags=TransferFlags.POST_PENDING_TRANSFER)],
            types.TRANSFER_DTYPE,
        )
        sm = StateMachine(CFG)
        orc = Oracle()
        ats = orc.prepare("create_accounts", len(accounts))
        orc.create_accounts([account_from_numpy(r) for r in accounts], ats)
        sm.create_accounts(accounts)
        for batch in [pendings, filler]:
            ts = orc.prepare("create_transfers", len(batch))
            expected = orc.create_transfers([transfer_from_numpy(r) for r in batch], ts)
            got = sm.create_transfers(batch)
            assert [(int(i), int(r)) for i, r in zip(got["index"], got["result"])] \
                == [(i, r) for i, r in expected]
        # Advance both clocks past the 1s deadline (prepare stamps are ns).
        orc.prepare_timestamp += 2 * 10**9
        sm.prepare_timestamp += 2 * 10**9
        ts = orc.prepare("create_transfers", len(pv))
        expected = orc.create_transfers([transfer_from_numpy(r) for r in pv], ts)
        got = sm.create_transfers(pv)
        assert [(int(i), int(r)) for i, r in zip(got["index"], got["result"])] \
            == [(i, r) for i, r in expected]
        assert expected[0][1] == int(TR.PENDING_TRANSFER_EXPIRED)
        check_equal(sm, orc)

    @pytest.mark.parametrize("seed", range(8))
    def test_random_config3_workload(self, seed):
        # BASELINE config-3-shaped workload: linked chains + pending +
        # post/void of prior-batch pendings. Done-bar (VERDICT r2 task 2):
        # ≥90% of batches take the exact kernel, byte-exact vs oracle.
        rng = np.random.default_rng(3000 + seed)
        n_accounts = 16
        accounts = simple_accounts(n_accounts)
        sm = StateMachine(CFG)
        orc = Oracle()
        ts = orc.prepare("create_accounts", n_accounts)
        orc.create_accounts([account_from_numpy(r) for r in accounts], ts)
        sm.create_accounts(accounts)

        next_id = 1
        prior_pendings = []  # ids of pendings LANDED in earlier batches
        n_batches = 6
        for _ in range(n_batches):
            batch = []
            new_pendings = []
            bn = int(rng.integers(8, 40))
            i = 0
            while i < bn:
                r = rng.random()
                if r < 0.25 and prior_pendings:
                    pid = int(rng.choice(prior_pendings))
                    batch.append(types.transfer(
                        id=next_id, pending_id=pid, ledger=1, code=1,
                        amount=int(rng.integers(0, 30)),
                        flags=int(TransferFlags.POST_PENDING_TRANSFER
                                  if rng.random() < 0.6
                                  else TransferFlags.VOID_PENDING_TRANSFER),
                    ))
                    next_id += 1
                    i += 1
                elif r < 0.45:
                    # linked chain of 2-4 events
                    clen = int(rng.integers(2, 5))
                    for j in range(clen):
                        flags = int(TransferFlags.LINKED) if j < clen - 1 else 0
                        if rng.random() < 0.25:
                            flags |= int(TransferFlags.PENDING)
                        batch.append(types.transfer(
                            id=next_id,
                            debit_account_id=int(rng.integers(1, n_accounts + 2)),
                            credit_account_id=int(rng.integers(1, n_accounts + 1)),
                            amount=int(rng.integers(0, 50)),
                            ledger=1, code=1, flags=flags,
                        ))
                        if flags & int(TransferFlags.PENDING):
                            new_pendings.append(next_id)
                        next_id += 1
                        i += 1
                else:
                    flags = int(TransferFlags.PENDING) if rng.random() < 0.35 else 0
                    batch.append(types.transfer(
                        id=next_id,
                        debit_account_id=int(rng.integers(1, n_accounts + 1)),
                        credit_account_id=int(rng.integers(1, n_accounts + 1)),
                        amount=int(rng.integers(1, 50)),
                        ledger=1, code=1, flags=flags,
                    ))
                    if flags:
                        new_pendings.append(next_id)
                    next_id += 1
                    i += 1
            arr = types.batch(batch, types.TRANSFER_DTYPE)
            ts = orc.prepare("create_transfers", len(arr))
            expected = orc.create_transfers([transfer_from_numpy(r) for r in arr], ts)
            got = sm.create_transfers(arr)
            assert [(int(i2), int(r2)) for i2, r2 in zip(got["index"], got["result"])] \
                == [(i2, r2) for i2, r2 in expected], f"seed {seed} diverged"
            # pendings only count as post targets once their batch landed
            prior_pendings += [p for p in new_pendings if p in orc.transfers]
        check_equal(sm, orc)
        assert sm.stats["exact_batches"] >= 0.9 * n_batches, sm.stats

    def test_exact_batch_8190(self):
        # Production-scale exact batch (VERDICT r2 weak #2): 8190 events of
        # mixed balancing/linked/pending/post-void through the sweep kernel.
        big_cfg = Config(name="big", accounts_max=1 << 12,
                         transfers_max=1 << 15, batch_max=8190)
        rng = np.random.default_rng(42)
        n_accounts = 64
        accounts = simple_accounts(n_accounts)
        sm = StateMachine(big_cfg)
        orc = Oracle()
        ts = orc.prepare("create_accounts", n_accounts)
        orc.create_accounts([account_from_numpy(r) for r in accounts], ts)
        sm.create_accounts(accounts)

        # Seed batch: simple + pending transfers (fast path).
        seed_batch = []
        for i in range(1, 1001):
            seed_batch.append(types.transfer(
                id=i, debit_account_id=int(rng.integers(1, n_accounts + 1)),
                credit_account_id=int(rng.integers(1, n_accounts + 1)),
                amount=int(rng.integers(1, 1000)), ledger=1, code=1,
                flags=int(TransferFlags.PENDING) if i % 3 == 0 else 0,
            ))
        arr = types.batch(seed_batch, types.TRANSFER_DTYPE)
        ts = orc.prepare("create_transfers", len(arr))
        expected = orc.create_transfers([transfer_from_numpy(r) for r in arr], ts)
        got = sm.create_transfers(arr)
        assert [(int(i), int(r)) for i, r in zip(got["index"], got["result"])] \
            == [(i, r) for i, r in expected]
        pending_ids = [i for i in range(3, 1001, 3) if i in orc.transfers]

        big = []
        next_id = 10_000
        while len(big) < 8190:
            r = rng.random()
            if r < 0.1 and pending_ids:
                big.append(types.transfer(
                    id=next_id, pending_id=int(rng.choice(pending_ids)),
                    ledger=1, code=1,
                    flags=int(TransferFlags.POST_PENDING_TRANSFER
                              if rng.random() < 0.5
                              else TransferFlags.VOID_PENDING_TRANSFER),
                ))
            elif r < 0.3:
                clen = min(int(rng.integers(2, 4)), 8190 - len(big))
                for j in range(clen):
                    big.append(types.transfer(
                        id=next_id + j,
                        debit_account_id=int(rng.integers(1, n_accounts + 1)),
                        credit_account_id=int(rng.integers(1, n_accounts + 1)),
                        amount=int(rng.integers(1, 100)),
                        ledger=1, code=1,
                        flags=int(TransferFlags.LINKED) if j < clen - 1 else 0,
                    ))
                next_id += clen - 1
            elif r < 0.5:
                big.append(types.transfer(
                    id=next_id,
                    debit_account_id=int(rng.integers(1, n_accounts + 1)),
                    credit_account_id=int(rng.integers(1, n_accounts + 1)),
                    amount=int(rng.integers(0, 100)), ledger=1, code=1,
                    flags=int(TransferFlags.BALANCING_DEBIT
                              if rng.random() < 0.5
                              else TransferFlags.BALANCING_CREDIT),
                ))
            else:
                big.append(types.transfer(
                    id=next_id,
                    debit_account_id=int(rng.integers(1, n_accounts + 1)),
                    credit_account_id=int(rng.integers(1, n_accounts + 1)),
                    amount=int(rng.integers(1, 100)), ledger=1, code=1,
                ))
            next_id += 1
        big = big[:8190]
        arr = types.batch(big, types.TRANSFER_DTYPE)
        ts = orc.prepare("create_transfers", len(arr))
        expected = orc.create_transfers([transfer_from_numpy(r) for r in arr], ts)
        got = sm.create_transfers(arr)
        assert [(int(i), int(r)) for i, r in zip(got["index"], got["result"])] \
            == [(i, r) for i, r in expected]
        assert sm.stats["exact_batches"] >= 1, sm.stats
        check_equal(sm, orc)


class TestDuplicateIdDeepInTheIdTree:
    """An id stored long ago, sent again once the id tree is several times
    over its decoded-mirror budget (lowered on the instance, as is the
    growth factor, so that a few thousand transfers reach the shape the
    production tree has past 2^23 rows). The duplicate-id confirm reaches
    it through the fences and one data block of a table two levels down,
    builds no mirror on the way, and the answers are the oracle's: `exists`
    for the stored id, `ok` for the fresh ids beside it, the global
    filter's false positives among them."""

    CFG = Config(
        name="deep", accounts_max=1 << 10, transfers_max=1 << 16,
        lsm_block_size=1 << 12, grid_block_count=1 << 13,
        grid_cache_blocks=64, index_memtable_rows=512,
    )
    BATCHES, N = 48, 512

    @staticmethod
    def _batch(ids, rng):
        ev = np.zeros(len(ids), dtype=types.TRANSFER_DTYPE)
        ev["id_lo"] = ids
        dr = rng.integers(1, 17, len(ids))
        ev["debit_account_id_lo"] = dr
        ev["credit_account_id_lo"] = 1 + (dr + rng.integers(0, 15, len(ids))) % 16
        ev["amount_lo"] = rng.integers(1, 1000, len(ids))
        ev["ledger"] = 1
        ev["code"] = 7
        return ev

    @staticmethod
    def _expected(orc, batch):
        ts = orc.prepare("create_transfers", len(batch))
        return orc.create_transfers([transfer_from_numpy(r) for r in batch], ts)

    @staticmethod
    def _pairs(got):
        return [(int(i), int(r)) for i, r in zip(got["index"], got["result"])]

    def _stored(self):
        sm, orc = StateMachine(self.CFG, backend="jax"), Oracle()
        tree = sm.transfer_index
        tree.growth, tree.DECODE_MIN_ROWS, tree.DECODE_BUDGET_ROWS = 3, 256, 8192
        accounts = simple_accounts(16)
        orc.create_accounts(
            [account_from_numpy(r) for r in accounts], orc.prepare("create_accounts", 16))
        assert len(sm.create_accounts(accounts)) == 0
        rng = np.random.default_rng(61)
        first = None
        for b in range(self.BATCHES):
            batch = self._batch(1000 + b * self.N + np.arange(self.N), rng)
            first = batch if first is None else first
            assert self._expected(orc, batch) == [] and len(sm.create_transfers(batch)) == 0
            sm.compact_beat()
        assert tree.count > 2 * tree.DECODE_BUDGET_ROWS and len(tree.levels) >= 3
        return sm, orc, first, rng

    @staticmethod
    def _level_of(tree, id_lo):
        for depth, level in enumerate(tree.levels):
            for t in level:
                for f in tree._table_fences(t):
                    keys, _ = tree._read_data_block(int(f["block"]), int(f["count"]))
                    if id_lo in keys["lo"]:
                        return depth
        return None

    def _fresh(self, sm, rng, n, linked):
        """n ids never stored, eight of them flagged by the global filter
        (its false positives: the confirm has to clear them); with
        `linked`, a chain of three at the front, which routes exact."""
        cand = 10_000_000 + np.arange(200_000, dtype=np.uint64)
        flagged = sm.transfer_seen.maybe(cand, np.zeros(len(cand), dtype=np.uint64))
        assert flagged.sum() >= 8
        ids = np.concatenate([cand[~flagged][:n - 8], cand[flagged][:8]])
        batch = self._batch(ids[rng.permutation(n)], rng)
        if linked:
            batch["flags"][:2] = int(TransferFlags.LINKED)
        return batch

    def _send(self, sm, batch, path):
        """Through the dispatch-ahead where the fast path has one (at its
        turn where that refuses), else single-phase."""
        if path == "exact":
            return sm.create_transfers(batch)
        ts = sm.prepare("create_transfers", len(batch))
        handle = sm.create_transfers_dispatch(batch, ts)
        if handle is None:
            return sm.create_transfers(batch, timestamp=ts)
        return sm.create_transfers_finish(handle)

    @pytest.mark.parametrize("path", ["fast", "exact"])
    def test_exists_two_levels_down_and_ok_beside_it(self, path):
        sm, orc, first, rng = self._stored()
        tree = sm.transfer_index
        deep = first[17]
        assert self._level_of(tree, int(deep["id_lo"])) >= 2
        mirrored = {t for lvl in tree.levels for t in lvl if t._decoded is not None}

        # Fresh ids alone: the confirm clears the filter's false positives
        # and the batch keeps its route.
        batch = self._fresh(sm, rng, 64, linked=path == "exact")
        before = dict(sm.stats)
        expected = self._expected(orc, batch)
        assert self._pairs(self._send(sm, batch, path)) == expected == []
        assert sm.stats[f"{path}_batches"] == before[f"{path}_batches"] + 1
        assert sm.stats["serial_batches"] == before["serial_batches"]
        sm.compact_beat()

        # The stored id among fresh ones: found, so the batch goes serial.
        batch = self._fresh(sm, rng, 64, linked=path == "exact")
        batch["id_lo"] += 1_000_000
        batch[40] = deep
        before = dict(sm.stats)
        expected = self._expected(orc, batch)
        assert expected == [(40, int(TR.EXISTS))]
        assert self._pairs(self._send(sm, batch, path)) == expected
        assert sm.stats["serial_batches"] == before["serial_batches"] + 1
        sm.compact_beat()

        # Neither confirm built a mirror of a table below level 0.
        assert {t for lvl in tree.levels[1:] for t in lvl
                if t._decoded is not None} <= mirrored
        check_equal(sm, orc)
