"""The exact kernel's post phase writes the rows a batch touches
(`commit_exact._apply`: one segment total and one row write per touched
slot, from the sort plan). Held here, bit for bit, to the dense post it
replaced: `u128.scatter_add` / `scatter_sub` over the whole table and
the overflow checks on every row. The dense post is kept below as a
test-only reference and run through the kernel's own `balance_apply`
hook, so both sides share everything but the post.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tigerbeetle_tpu import types
from tigerbeetle_tpu.ops import commit as commit_ops
from tigerbeetle_tpu.ops import commit_exact, u128
from tigerbeetle_tpu.results import CreateTransferResult as TR

A = 4096  # account slots: a small table
N = 1024  # events a batch (2n = 2048 postings: room for 819 on one slot)
FIELDS = commit_exact.BAL_FIELDS
F_LINKED, F_PENDING = commit_ops.F_LINKED, commit_ops.F_PENDING
F_POST, F_VOID = commit_ops.F_POST, commit_ops.F_VOID


def _dense_apply(st, eff_dr, eff_cr, amounts, p_amount, add_pend, add_post, sub_pend):
    """The post this PR took out of the kernel: six scatter passes over
    all A rows, adds before subs, then the overflow checks on every row."""
    new_dp, o1 = u128.scatter_add(st.debits_pending, eff_dr, amounts, add_pend)
    new_cp, o2 = u128.scatter_add(st.credits_pending, eff_cr, amounts, add_pend)
    new_dpo, o3 = u128.scatter_add(st.debits_posted, eff_dr, amounts, add_post)
    new_cpo, o4 = u128.scatter_add(st.credits_posted, eff_cr, amounts, add_post)
    new_dp, u1 = u128.scatter_sub(new_dp, eff_dr, p_amount, sub_pend)
    new_cp, u2 = u128.scatter_sub(new_cp, eff_cr, p_amount, sub_pend)
    _, o5 = u128.add(new_dp, new_dpo)
    _, o6 = u128.add(new_cp, new_cpo)
    over = (
        jnp.any(o1) | jnp.any(o2) | jnp.any(o3) | jnp.any(o4)
        | jnp.any(o5) | jnp.any(o6) | jnp.any(u1) | jnp.any(u2)
    )
    return st._replace(
        debits_pending=new_dp, debits_posted=new_dpo,
        credits_pending=new_cp, credits_posted=new_cpo,
    ), over


rowwise = commit_exact.create_transfers_exact
dense = jax.jit(
    functools.partial(commit_exact.create_transfers_exact_impl, balance_apply=_dense_apply),
    static_argnames=("max_sweeps", "has_pv", "has_chains"),
)


def _limbs(values):
    """Python ints -> (k, 4) u32 limbs."""
    return np.array([types.int_to_limbs(int(v)) for v in values], np.uint32).reshape(-1, 4)


def _state(rows=None):
    """A ledger-1 table; `rows` {slot: (dp, dpo, cp, cpo)} as Python ints."""
    cols = {f: np.zeros((A, 4), np.uint32) for f in FIELDS}
    for slot, vals in (rows or {}).items():
        for f, v in zip(FIELDS, vals):
            cols[f][slot] = _limbs([v])[0]
    return commit_ops.LedgerState(
        **cols, ledger=np.ones(A, np.uint32), flags=np.zeros(A, np.uint32)
    )


class Batch:
    """Events by hand: `add` appends one, `build` pads to N the way
    `_device_batch` does (slot -1, a host code, zeros) and derives the
    chains from the LINKED flags as the state machine does."""

    def __init__(self):
        self.rows = []

    def add(self, dr, cr, amount, flags=0, host_code=0, pending=None):
        """pending: (amount, dr_slot, cr_slot, group) of the referenced
        pending transfer, for a post or void."""
        self.rows.append((dr, cr, amount, flags, host_code, pending))
        return self

    def build(self):
        n = len(self.rows)
        assert n <= N
        pad = N - n
        dr, cr, amount, flags, host_code, pend = zip(*self.rows)
        flags = np.array(flags + (0,) * pad, np.uint32)
        b = commit_ops.TransferBatch(
            id=_limbs(list(range(1, n + 1)) + [0] * pad),
            dr_slot=np.array(dr + (-1,) * pad, np.int32),
            cr_slot=np.array(cr + (-1,) * pad, np.int32),
            amount=_limbs(amount + (0,) * pad),
            pending_id=_limbs([0 if p is None else 10**6 + p[3] for p in pend] + [0] * pad),
            timeout=np.zeros(N, np.uint32),
            ledger=np.array([1] * n + [0] * pad, np.uint32),
            code=np.array([1] * n + [0] * pad, np.uint32),
            flags=flags,
            timestamp=types.u64_to_limbs(np.arange(1000, 1000 + N, dtype=np.uint64)),
        )
        host = np.array(
            host_code + (int(TR.ID_MUST_NOT_BE_ZERO),) * pad, np.uint32
        )
        has = np.array([p is not None for p in pend] + [False] * pad)
        pick = lambda i, fill: np.array(
            [fill if p is None else p[i] for p in pend] + [fill] * pad
        )
        pinfo = commit_exact.PendingInfo(
            found=has,
            amount=_limbs(list(pick(0, 0))),
            dr_slot=pick(1, -1).astype(np.int32),
            cr_slot=pick(2, -1).astype(np.int32),
            timestamp=np.zeros((N, 2), np.uint32),
            timeout=np.zeros(N, np.uint32),
            base_fulfillment=np.full(N, commit_exact.FULFILL_NONE, np.int32),
            group=pick(3, N).astype(np.int32),
        )
        linked = (flags & F_LINKED) != 0
        new_chain = np.ones(N, bool)
        new_chain[1:] = ~linked[:-1]
        chain_id = np.maximum.accumulate(
            np.where(new_chain, np.arange(N), 0)
        ).astype(np.int32)
        return b, host, pinfo, chain_id


def _plan(b, pinfo, chain_id):
    return commit_exact.build_sort_plan(
        np.asarray(b.flags), np.asarray(b.dr_slot), np.asarray(b.cr_slot),
        pinfo.dr_slot, pinfo.cr_slot, chain_id, pinfo.group, A,
    )


def _both(state, built, has_pv=True, has_chains=True, with_plan=True):
    """Run the kernel and the dense reference; hold all seven outputs
    equal; return the kernel's (new_state, codes, amounts, bail)."""
    b, host, pinfo, chain_id = built
    plan = _plan(b, pinfo, chain_id) if with_plan else None
    kw = dict(has_pv=has_pv, has_chains=has_chains)
    got = rowwise(state, b, host, pinfo, chain_id, plan, **kw)
    want = dense(state, b, host, pinfo, chain_id, plan, **kw)
    jax.tree.map(np.testing.assert_array_equal, got, want)
    new_state, codes, amounts, _, _, bail, _ = got
    return new_state, np.asarray(codes), np.asarray(amounts), bool(bail)


def _row(state, slot):
    return tuple(u128.to_ints(np.asarray(getattr(state, f))[slot]) for f in FIELDS)


def _random_case(rng, has_pv, has_chains):
    """A batch over 48 hot slots and the whole table: simple, pending,
    linked, post and void events, some failing on the host's rungs, some
    without an account, and padding behind them."""
    hot = rng.choice(np.arange(1, A - 1), 48, replace=False)
    rows = {int(s): (1 << 70, int(rng.integers(1 << 40)), 1 << 70, int(rng.integers(1 << 40)))
            for s in hot}
    # Slot 0 and the last slot hold balances: a posting without an
    # account, clipped or wrapped into the table, would show there.
    rows[0] = rows[A - 1] = (7, 8, 9, 10)
    batch = Batch()
    n = int(rng.integers(N - 200, N - 8))
    for i in range(n):
        pool = hot if rng.random() < 0.7 else np.arange(1, A - 1)
        dr, cr = (int(x) for x in rng.choice(pool, 2, replace=False))
        amount = int(rng.integers(1, 1 << 62)) << int(rng.integers(0, 40))
        flags, host_code, pending = 0, 0, None
        kind = rng.random()
        if kind < 0.15:
            flags = F_PENDING
        elif has_pv and kind < 0.35:
            p_amount = int(rng.integers(1, 1 << 40))
            post = rng.random() < 0.5
            flags = F_POST if post else F_VOID
            amount = int(rng.integers(0, p_amount + 1)) if post else 0
            p_dr, p_cr = (int(x) for x in rng.choice(hot, 2, replace=False))
            # Some pendings are referenced twice: the second sees
            # ALREADY_POSTED / ALREADY_VOIDED.
            group = i if rng.random() < 0.8 else max(0, i - 3)
            pending = (p_amount, p_dr, p_cr, group)
        elif kind < 0.40:
            dr = -1  # debit account not found
        if rng.random() < 0.03:
            host_code = int(TR.EXISTS)
        if has_chains and rng.random() < 0.3 and i < n - 1:
            flags |= F_LINKED
        batch.add(dr, cr, amount, flags, host_code, pending)
    return _state(rows), batch.build()


@pytest.mark.parametrize("with_plan", [True, False], ids=["host_plan", "device_sort"])
@pytest.mark.parametrize("has_pv,has_chains", [
    (True, True), (False, False), (False, True), (True, False),
], ids=["pv+chains", "plain", "chains", "pv"])
def test_rowwise_post_equals_dense_on_random_batches(has_pv, has_chains, with_plan):
    for seed in (11, 12, 13):
        rng = np.random.default_rng([seed, has_pv, has_chains])
        state, built = _random_case(rng, has_pv, has_chains)
        new_state, codes, _, bail = _both(state, built, has_pv, has_chains, with_plan)
        assert not bail
        assert 100 < np.count_nonzero(codes == 0) < N - 100  # both outcomes occur
        assert _row(new_state, 0) == _row(new_state, A - 1) == (7, 8, 9, 10)
        moved = sum(
            np.count_nonzero((np.asarray(getattr(new_state, f)) != getattr(state, f)).any(1))
            for f in FIELDS
        )
        assert moved > 100


def test_a_slot_hit_by_819_postings():
    """One cash account on the credit side of 819 transfers whose amounts
    carry through every limb: the segment total is exact."""
    rng = np.random.default_rng(819)
    amounts = [int(rng.integers(1, 1 << 63)) << int(rng.integers(0, 60)) for _ in range(819)]
    batch = Batch()
    for i, amount in enumerate(amounts):
        batch.add(100 + i, 7, amount)
    new_state, codes, _, bail = _both(_state({7: (0, 0, 0, 5)}), batch.build())
    assert not bail and (codes[:819] == 0).all()
    assert _row(new_state, 7) == (0, 0, 0, 5 + sum(amounts))
    assert _row(new_state, 100) == (0, amounts[0], 0, 0)


def test_a_post_and_a_void_of_one_account_in_one_batch():
    """Adds, then subs, on one row: account 5 takes a new pending (+30),
    a post of 40 of a pending 100 and the void of a pending 60."""
    batch = (
        Batch()
        .add(5, 6, 30, F_PENDING)
        .add(0, 0, 40, F_POST, pending=(100, 5, 6, 0))
        .add(0, 0, 0, F_VOID, pending=(60, 5, 6, 1))
    )
    state = _state({5: (160, 1, 0, 0), 6: (0, 0, 160, 2)})
    new_state, codes, amounts, bail = _both(state, batch.build())
    assert not bail and (codes[:3] == 0).all()
    assert u128.to_ints(amounts[:3]) == [30, 40, 60]
    assert _row(new_state, 5) == (160 + 30 - 100 - 60, 1 + 40, 0, 0)
    assert _row(new_state, 6) == (0, 0, 160 + 30 - 100 - 60, 2 + 40)


def test_postings_without_a_slot_and_padding_are_dropped():
    batch = (
        Batch()
        .add(-1, 9, 5)  # debit account not found
        .add(9, -1, 5)
        .add(0, 0, 0, F_VOID)  # a void of no pending: no account pair at all
        .add(9, 10, 5)
    )
    edge = {0: (1, 2, 3, 4), A - 1: (5, 6, 7, 8)}
    new_state, codes, _, bail = _both(_state(edge), batch.build())
    assert not bail
    assert codes[:4].tolist() == [
        int(TR.DEBIT_ACCOUNT_NOT_FOUND), int(TR.CREDIT_ACCOUNT_NOT_FOUND),
        int(TR.PENDING_ID_MUST_NOT_BE_ZERO), 0,
    ]
    assert _row(new_state, 0) == edge[0] and _row(new_state, A - 1) == edge[A - 1]
    assert _row(new_state, 9) == (0, 5, 0, 0) and _row(new_state, 10) == (0, 0, 0, 5)


def test_an_overflow_in_the_sum_of_a_touched_row_bails():
    """debits_pending and debits_posted each fit u128 after the void and
    only their sum does not: the third of the post's checks."""
    half = 1 << 127
    batch = Batch().add(0, 0, 0, F_VOID, pending=(5, 20, 21, 0))
    state = _state({20: (half + 5, half, 0, 0), 21: (0, 0, 5, 0)})
    new_state, codes, _, bail = _both(state, batch.build())
    assert codes[0] == 0 and bail
    assert _row(new_state, 20) == (half, half, 0, 0)


def test_an_underflowing_void_bails():
    batch = Batch().add(0, 0, 0, F_VOID, pending=(50, 20, 21, 0))
    _, codes, _, bail = _both(_state({20: (10, 0, 0, 0), 21: (0, 0, 50, 0)}), batch.build())
    assert codes[0] == 0 and bail


def test_an_overflowing_add_of_a_touched_row_bails():
    """Two posts of one pending's account whose amounts the pv ladder
    does not weigh against u128: the per-field overflow of the adds."""
    top = (1 << 128) - 1
    batch = Batch().add(0, 0, 9, F_POST, pending=(9, 20, 21, 0))
    state = _state({20: (9, top - 3, 0, 0), 21: (0, 0, 9, 0)})
    _, codes, _, bail = _both(state, batch.build())
    assert codes[0] == 0 and bail


def test_an_all_failed_batch_leaves_the_state_unchanged():
    batch = Batch()
    for i in range(600):
        batch.add(30 + i % 7, 50 + i % 5, 1 + i, host_code=int(TR.EXISTS))
    state = _state({30: (1, 2, 3, 4), 50: (5, 6, 7, 8)})
    new_state, codes, amounts, bail = _both(state, batch.build())
    assert not bail and (codes != 0).all() and not amounts.any()
    for f in FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(new_state, f)), getattr(state, f))
