"""Sharded commit over the virtual 8-device CPU mesh vs single-chip kernel.

Byte-equality: the sharded step must produce the same codes and the same
balances as the single-device fast path (which is itself oracle-exact).
"""

import jax
import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.ops import commit as commit_ops
from tigerbeetle_tpu.parallel import sharding

A = 1 << 10  # accounts capacity (divisible by shard axis)
N = 256  # batch size


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return sharding.make_mesh(8)


def _setup(mesh, rng):
    n_accounts = 100
    state_1 = commit_ops.init_state(A)
    slots = np.arange(n_accounts, dtype=np.int32)
    ledger = np.ones(n_accounts, dtype=np.uint32)
    flags = np.zeros(n_accounts, dtype=np.uint32)
    mask = np.ones(n_accounts, dtype=bool)
    state_1 = commit_ops.register_accounts(state_1, slots, ledger, flags, mask)

    state_n = sharding.init_sharded_state(A, mesh)
    state_n = sharding.register_accounts_sharded(mesh, state_n, slots, ledger, flags, mask)

    b = commit_ops.TransferBatch(
        id=types.u64_pair_to_limbs(
            np.arange(1, N + 1, dtype=np.uint64), np.zeros(N, dtype=np.uint64)
        ),
        dr_slot=rng.integers(0, n_accounts, N).astype(np.int32),
        cr_slot=rng.integers(0, n_accounts, N).astype(np.int32),
        amount=types.u64_pair_to_limbs(
            rng.integers(1, 10_000, N).astype(np.uint64), np.zeros(N, dtype=np.uint64)
        ),
        pending_id=np.zeros((N, 4), dtype=np.uint32),
        timeout=np.zeros(N, dtype=np.uint32),
        ledger=np.ones(N, dtype=np.uint32),
        code=np.full(N, 7, dtype=np.uint32),
        flags=(rng.random(N) < 0.3).astype(np.uint32) * commit_ops.F_PENDING,
        timestamp=types.u64_to_limbs(np.arange(1, N + 1, dtype=np.uint64)),
    )
    # Make some events invalid to exercise code paths: dr == cr handled via
    # host_code; a few zero amounts.
    amt = np.array(b.amount)
    amt[::17] = 0
    b = b._replace(amount=amt)
    host_code = np.zeros(N, dtype=np.uint32)
    host_code[::23] = 12  # accounts_must_be_different, say
    return state_1, state_n, b, host_code


def test_sharded_matches_single(mesh):
    rng = np.random.default_rng(42)
    state_1, state_n, b, host_code = _setup(mesh, rng)

    new_1, codes_1, bail_1 = commit_ops.create_transfers_fast(state_1, b, host_code)
    step = sharding.make_sharded_commit(mesh, A)
    new_n, codes_n, bail_n = step(state_n, b, host_code)

    assert not bool(bail_1) and not bool(bail_n)
    np.testing.assert_array_equal(np.asarray(codes_1), np.asarray(codes_n))
    for f in ("debits_pending", "debits_posted", "credits_pending", "credits_posted"):
        np.testing.assert_array_equal(
            np.asarray(getattr(new_1, f)), np.asarray(getattr(new_n, f)), err_msg=f
        )


def test_sharded_exact_matches_single(mesh):
    """The exact sweep kernel (balancing/limits/chains/post-void) over
    sharded state must be byte-identical to single-chip (r3 task 7)."""
    from tigerbeetle_tpu.ops import commit_exact

    rng = np.random.default_rng(77)
    state_1, state_n, b, host_code = _setup(mesh, rng)
    # Rewrite the batch into an exact-kernel shape: balancing flags, a
    # linked chain, and limit accounts.
    flags = np.zeros(N, dtype=np.uint32)
    bal = rng.random(N) < 0.4
    flags[bal] = np.where(
        rng.random(int(bal.sum())) < 0.5,
        np.uint32(commit_ops.F_BAL_DR), np.uint32(commit_ops.F_BAL_CR),
    )
    flags[10] = np.uint32(commit_ops.F_LINKED)
    chain_id = np.arange(N, dtype=np.int32)
    chain_id[11] = 10
    b = b._replace(flags=flags)
    host_code = np.zeros(N, dtype=np.uint32)

    # Seed balances so clamps have room (same on both states).
    slots = np.arange(100, dtype=np.int32)
    seed_bal = np.zeros((100, 4), dtype=np.uint32)
    seed_bal[:, 0] = 1_000_000
    state_1 = commit_ops.write_balances(
        state_1, slots, seed_bal, seed_bal, seed_bal, seed_bal
    )
    from tigerbeetle_tpu.parallel.sharding import _place
    dense = commit_ops.LedgerState(*[np.asarray(x) for x in state_1])
    state_n = _place(dense, mesh)

    pinfo = commit_exact.PendingInfo(
        found=np.zeros(N, dtype=bool),
        amount=np.zeros((N, 4), dtype=np.uint32),
        dr_slot=np.full(N, -1, dtype=np.int32),
        cr_slot=np.full(N, -1, dtype=np.int32),
        timestamp=np.zeros((N, 2), dtype=np.uint32),
        timeout=np.zeros(N, dtype=np.uint32),
        base_fulfillment=np.full(N, commit_exact.FULFILL_NONE, dtype=np.int32),
        group=np.full(N, N, dtype=np.int32),
    )

    new_1, codes_1, amounts_1, _, _, bail_1, sweeps_1 = commit_exact.create_transfers_exact(
        state_1, b, host_code, pinfo, chain_id
    )
    step = sharding.make_sharded_commit_exact(mesh, A)
    new_n, codes_n, amounts_n, _, _, bail_n, sweeps_n = step(state_n, b, host_code, pinfo, chain_id)

    assert not bool(bail_1) and not bool(bail_n)
    assert int(sweeps_1) == int(sweeps_n) >= 1
    np.testing.assert_array_equal(np.asarray(codes_1), np.asarray(codes_n))
    np.testing.assert_array_equal(np.asarray(amounts_1), np.asarray(amounts_n))
    assert int((np.asarray(codes_1) == 0).sum()) > 0
    for f in ("debits_pending", "debits_posted", "credits_pending", "credits_posted"):
        np.testing.assert_array_equal(
            np.asarray(getattr(new_1, f)), np.asarray(getattr(new_n, f)), err_msg=f
        )


def test_sharded_state_placement(mesh):
    state = sharding.init_sharded_state(A, mesh)
    shard_axis = {d for d in state.debits_posted.sharding.spec}
    assert "shard" in shard_axis
    # metadata replicated
    assert state.ledger.sharding.is_fully_replicated


def test_state_machine_on_mesh_oracle_parity(mesh):
    """The FULL StateMachine (host prefetch + routing + all three commit
    paths) over slot-sharded mesh state, byte-checked against the serial
    oracle — multi-chip as a product path, not a kernel demo."""
    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.constants import Config
    from tigerbeetle_tpu.flags import AccountFlags, TransferFlags

    from tests.test_state_machine import check_equal

    cfg = Config(name="mesh", accounts_max=A, transfers_max=1 << 14, batch_max=64)

    from tigerbeetle_tpu.models.oracle import (
        Oracle,
        account_from_numpy,
        transfer_from_numpy,
    )
    from tigerbeetle_tpu.models.state_machine import StateMachine

    rng = np.random.default_rng(99)
    n_accounts = 24
    accounts = types.batch(
        [
            types.account(
                id=1 + i, ledger=1, code=10,
                flags=int(AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS)
                if i % 6 == 0 else 0,
            )
            for i in range(n_accounts)
        ],
        types.ACCOUNT_DTYPE,
    )
    sm = StateMachine(cfg, backend="jax", mesh=mesh)
    orc = Oracle()
    ts = orc.prepare("create_accounts", n_accounts)
    orc.create_accounts([account_from_numpy(r) for r in accounts], ts)
    sm.create_accounts(accounts)

    next_id = 1
    prior_pendings = []
    for _ in range(4):
        batch = []
        new_p = []
        for _ in range(int(rng.integers(8, 40))):
            r = rng.random()
            if r < 0.15 and prior_pendings:
                batch.append(types.transfer(
                    id=next_id, pending_id=int(rng.choice(prior_pendings)),
                    ledger=1, code=10, amount=int(rng.integers(0, 30)),
                    flags=int(TransferFlags.POST_PENDING_TRANSFER
                              if rng.random() < 0.6
                              else TransferFlags.VOID_PENDING_TRANSFER)))
            elif r < 0.35:
                batch.append(types.transfer(
                    id=next_id,
                    debit_account_id=int(rng.integers(1, n_accounts + 1)),
                    credit_account_id=int(rng.integers(1, n_accounts + 1)),
                    amount=int(rng.integers(0, 60)), ledger=1, code=10,
                    flags=int(TransferFlags.BALANCING_DEBIT
                              if rng.random() < 0.5
                              else TransferFlags.BALANCING_CREDIT)))
            else:
                flags = int(TransferFlags.PENDING) if rng.random() < 0.3 else 0
                batch.append(types.transfer(
                    id=next_id,
                    debit_account_id=int(rng.integers(1, n_accounts + 1)),
                    credit_account_id=int(rng.integers(1, n_accounts + 1)),
                    amount=int(rng.integers(1, 50)), ledger=1, code=10,
                    flags=flags))
                if flags:
                    new_p.append(next_id)
            next_id += 1
        arr = types.batch(batch, types.TRANSFER_DTYPE)
        ts = orc.prepare("create_transfers", len(arr))
        expected = orc.create_transfers([transfer_from_numpy(r) for r in arr], ts)
        got = sm.create_transfers(arr)
        assert [(int(i), int(r)) for i, r in zip(got["index"], got["result"])] \
            == [(i, r) for i, r in expected]
        prior_pendings += [p for p in new_p if p in orc.transfers]
    check_equal(sm, orc)
    assert sm.stats["exact_batches"] + sm.stats["fast_batches"] >= 3, sm.stats
    # The mesh is real: balance tables stay sharded after all that traffic.
    assert "shard" in {d for d in sm.state.debits_posted.sharding.spec}


def test_mesh_shapes():
    m = sharding.make_mesh(8)
    assert m.shape["dp"] * m.shape["shard"] == 8
