"""Device-resident ledger state and the batched create_transfers commit kernel.

This is the TPU re-expression of the reference's hot loop
(/root/reference/src/state_machine.zig:1002-1368): instead of a serial
per-transfer loop over an LSM, the balances of all accounts live on-device as
uint32 limb arrays, validation is a vectorized ladder over the whole
8190-event batch, and balance posting is an exact wide-integer scatter-add
(u16 half-limb accumulation, see ops/u128.scatter_add).

Exactness contract: this kernel is byte-identical to the serial oracle
(models/oracle.py) for batches that satisfy the *fast-path preconditions*
checked by the host dispatcher (models/state_machine.py):
  - no event carries linked/post_pending/void_pending/balancing flags
    (pending-create IS handled — it is order-independent),
  - no duplicate transfer ids within the batch and none already exist,
  - no touched account has debits/credits_must_not_exceed or history flags.
Under those preconditions every check in the reference's validation ladder is
independent of event order except u128 overflow; overflow is monotone in the
per-account prefix sums, so "no overflow at the batch total" implies "no
overflow at any prefix". The kernel therefore computes batch totals, and
raises a `bail` flag if any total overflows — the host then discards the
result and re-runs the batch through the exact serial path. Overflow needs
amounts within 2^115 of the u128 limit, so bail never fires in practice.

State layout: structure-of-arrays over account slots (host assigns slots and
maps id → slot; the device never hashes). u128 → (A, 4) uint32 limbs.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from tigerbeetle_tpu.ops import u128
from tigerbeetle_tpu.results import CreateTransferResult as TR

U32 = jnp.uint32

# TransferFlags bits (flags.py; reference tigerbeetle.zig:107-120).
F_LINKED = 1 << 0
F_PENDING = 1 << 1
F_POST = 1 << 2
F_VOID = 1 << 3
F_BAL_DR = 1 << 4
F_BAL_CR = 1 << 5
F_PADDING = 0xFFFF & ~0x3F

# AccountFlags bits.
AF_DEBITS_MUST_NOT_EXCEED_CREDITS = 1 << 1
AF_CREDITS_MUST_NOT_EXCEED_DEBITS = 1 << 2
AF_HISTORY = 1 << 3

NS_PER_S = 1_000_000_000

# Slot sentinel for "account not found" (host uses -1; any negative works).
NOT_FOUND = -1


class LedgerState(NamedTuple):
    """Device-resident mutable account state, SoA over slots.

    Immutable per-account metadata (id, user_data, code, timestamp) stays in
    host mirrors; the device holds what the commit ladder reads or writes.
    """

    debits_pending: jnp.ndarray  # (A, 4) u32
    debits_posted: jnp.ndarray  # (A, 4) u32
    credits_pending: jnp.ndarray  # (A, 4) u32
    credits_posted: jnp.ndarray  # (A, 4) u32
    ledger: jnp.ndarray  # (A,) u32
    flags: jnp.ndarray  # (A,) u32


def init_state(accounts_max: int) -> LedgerState:
    a = accounts_max
    z = lambda: jnp.zeros((a, 4), dtype=U32)
    return LedgerState(
        debits_pending=z(),
        debits_posted=z(),
        credits_pending=z(),
        credits_posted=z(),
        ledger=jnp.zeros((a,), dtype=U32),
        flags=jnp.zeros((a,), dtype=U32),
    )


class TransferBatch(NamedTuple):
    """One create_transfers batch in device SoA form (host-prefetched slots)."""

    id: jnp.ndarray  # (n, 4) u32
    dr_slot: jnp.ndarray  # (n,) i32, NOT_FOUND if absent
    cr_slot: jnp.ndarray  # (n,) i32
    amount: jnp.ndarray  # (n, 4) u32
    pending_id: jnp.ndarray  # (n, 4) u32
    timeout: jnp.ndarray  # (n,) u32
    ledger: jnp.ndarray  # (n,) u32
    code: jnp.ndarray  # (n,) u32
    flags: jnp.ndarray  # (n,) u32
    timestamp: jnp.ndarray  # (n, 2) u32 — assigned event timestamps


def merge_codes(code: jnp.ndarray, host_code: jnp.ndarray) -> jnp.ndarray:
    """Merge device- and host-computed failure codes exactly.

    CreateTransferResult values are ordered by precedence (results.py), and
    both ladders emit the first-failing rung — so the exact merged result is
    the nonzero minimum.
    """
    big = jnp.uint32(0xFFFFFFFF)
    merged = jnp.minimum(
        jnp.where(code == 0, big, code), jnp.where(host_code == 0, big, host_code)
    )
    return jnp.where(merged == big, jnp.uint32(0), merged)


def _ladder(code, cond, result):  # tidy: static=result — precedence constant (a TR enum member), never a traced value
    """One rung: where no earlier rung fired and cond holds, set `result`.

    Encodes the reference's precedence order (first failing check wins,
    state_machine.zig:1239-1368) as a chain of selects.
    """
    return jnp.where((code == 0) & cond, jnp.uint32(int(result)), code)


def validate_simple(state: LedgerState, b: TransferBatch):
    """Vectorized validation ladder for fast-path batches.

    Returns (codes (n,) u32, unsupported (n,) bool). `unsupported` marks
    events the fast path must not handle (linked/post/void/balancing flags) —
    the host dispatcher checks this before trusting the result; it is also
    re-derived here so the kernel is safe to call blind.
    """
    n = b.flags.shape[0]
    flags = b.flags

    id_zero = u128.is_zero(b.id)
    id_max = u128.is_max(b.id)
    pend = (flags & F_PENDING) != 0

    code = jnp.zeros((n,), dtype=U32)
    code = _ladder(code, (flags & F_PADDING) != 0, TR.RESERVED_FLAG)
    code = _ladder(code, id_zero, TR.ID_MUST_NOT_BE_ZERO)
    code = _ladder(code, id_max, TR.ID_MUST_NOT_BE_INT_MAX)

    # Post/void events branch to a different ladder (state_machine.zig:1255);
    # the fast path treats them as unsupported.
    unsupported = (flags & (F_LINKED | F_POST | F_VOID | F_BAL_DR | F_BAL_CR)) != 0

    # dr/cr id checks are done host-side against the raw u128 ids; the device
    # only sees resolved slots, so the host encodes id_zero/id_max/equal
    # failures into the slot sentinels and per-event precomputed codes. Here
    # we rely on dr_slot/cr_slot: NOT_FOUND means "no such account" — but
    # zero/max/equal id errors precede not_found in the ladder, so the host
    # passes those through `host_code` merged by the dispatcher. To keep the
    # kernel self-contained for the graft entry, the id-shape checks that CAN
    # be derived on device are: pending_id / timeout / amount / ledger / code.
    code = _ladder(code, ~u128.is_zero(b.pending_id), TR.PENDING_ID_MUST_BE_ZERO)
    code = _ladder(code, ~pend & (b.timeout != 0), TR.TIMEOUT_RESERVED_FOR_PENDING_TRANSFER)
    code = _ladder(code, u128.is_zero(b.amount), TR.AMOUNT_MUST_NOT_BE_ZERO)
    code = _ladder(code, b.ledger == 0, TR.LEDGER_MUST_NOT_BE_ZERO)
    code = _ladder(code, b.code == 0, TR.CODE_MUST_NOT_BE_ZERO)

    dr_found = b.dr_slot >= 0
    cr_found = b.cr_slot >= 0
    code = _ladder(code, ~dr_found, TR.DEBIT_ACCOUNT_NOT_FOUND)
    code = _ladder(code, ~cr_found, TR.CREDIT_ACCOUNT_NOT_FOUND)

    dr_ix = jnp.clip(b.dr_slot, 0, state.ledger.shape[0] - 1)
    cr_ix = jnp.clip(b.cr_slot, 0, state.ledger.shape[0] - 1)
    dr_ledger = state.ledger[dr_ix]
    cr_ledger = state.ledger[cr_ix]
    code = _ladder(code, dr_ledger != cr_ledger, TR.ACCOUNTS_MUST_HAVE_THE_SAME_LEDGER)
    code = _ladder(
        code, b.ledger != dr_ledger, TR.TRANSFER_MUST_HAVE_THE_SAME_LEDGER_AS_ACCOUNTS
    )

    # overflows_timeout: timestamp + timeout * 1e9 > maxInt(u64)
    # (state_machine.zig:1326). timeout * 1e9 fits u64 exactly via mul_u32.
    timeout_ns = u128.mul_u32(b.timeout, jnp.uint32(NS_PER_S % (1 << 32)))
    # NS_PER_S < 2^32 so the single-limb multiply is exact... except 1e9 <
    # 2^30, so no wrap: assert statically.
    assert NS_PER_S < (1 << 32)
    _, ts_over = u128.add(b.timestamp, timeout_ns)
    code = _ladder(code, ts_over, TR.OVERFLOWS_TIMEOUT)

    return code, unsupported


def create_transfers_fast_impl(state: LedgerState, b: TransferBatch, host_code: jnp.ndarray):
    """Fast-path commit: validate + post the whole batch in parallel.

    host_code (n,) u32: failure codes precomputed by the host for checks the
    device cannot do (raw-id shape checks, exists checks); 0 = pass. Host
    codes are merged at their exact precedence position by the host choosing
    codes only for checks that precede everything computed here or by
    guaranteeing disjointness (see models/state_machine.py dispatch).

    Returns (new_state, codes, bail) — bail True means a u128 overflow was
    possible and the host must redo the batch serially (never in practice).
    """
    # The named scopes are metadata only: they name the kernel's phases in
    # a profiler trace (docs/OBSERVABILITY.md) and change no operation.
    with jax.named_scope("validate"):
        code, unsupported = validate_simple(state, b)
        code = merge_codes(code, host_code)

    ok = (code == 0) & ~unsupported
    pend = (b.flags & F_PENDING) != 0

    with jax.named_scope("post"):
        new_state, overflow = apply_posting_streamed(
            state, b.dr_slot, b.cr_slot, b.amount,
            dr_pend=ok & pend, dr_post=ok & ~pend,
            cr_pend=ok & pend, cr_post=ok & ~pend,
        )
        bail = overflow | jnp.any(unsupported)
    return new_state, code, bail


def apply_posting_streamed(
    state: LedgerState, dr_slot, cr_slot, amount, *, dr_pend, dr_post, cr_pend, cr_post
):
    """Post amounts via full-table streamed scatter-add (u128.scatter_add).

    Work is O(A) per batch: four table-sized accumulators and six passes
    over the table, whatever the batch touches. The fast kernel has no
    sort plan, so it has no cheaper way to a slot's total than the
    on-device sort/unique of `apply_posting_compact` below; which of the
    two costs less at which A has not been measured on a v5e (the exact
    kernel, which holds a plan, posts row by row: commit_exact._apply).
    Per-side masks let the sharded path apply only the sides its shard
    owns. Overflow semantics: per-slot u128 overflow plus the combined
    pending+posted check (state_machine.zig:1308-1324), monotone in batch
    totals.
    """
    new_dp, o1 = u128.scatter_add(state.debits_pending, dr_slot, amount, dr_pend)
    new_cp, o2 = u128.scatter_add(state.credits_pending, cr_slot, amount, cr_pend)
    new_dpo, o3 = u128.scatter_add(state.debits_posted, dr_slot, amount, dr_post)
    new_cpo, o4 = u128.scatter_add(state.credits_posted, cr_slot, amount, cr_post)
    _, o5 = u128.add(new_dp, new_dpo)
    _, o6 = u128.add(new_cp, new_cpo)
    over = (
        jnp.any(o1) | jnp.any(o2) | jnp.any(o3) | jnp.any(o4)
        | jnp.any(o5) | jnp.any(o6)
    )
    new_state = state._replace(
        debits_pending=new_dp,
        debits_posted=new_dpo,
        credits_pending=new_cp,
        credits_posted=new_cpo,
    )
    return new_state, over


def apply_posting_compact(
    state: LedgerState, dr_slot, cr_slot, amount, pend_mask, post_mask
):
    """Post amounts touching only batch rows (sort/unique + row updates).

    Work scales with the batch, not the table, at the price of an
    on-device sort/unique. No served path calls it (tests/test_u128.py
    holds it equal to the streamed post): it is the fast kernel's way to
    posting that follows the rows touched, and neither its cost nor the
    table size from which it would pay has been measured on a v5e.
    """
    a = state.debits_pending.shape[0]
    n = dr_slot.shape[0]
    assert n < (1 << 15), "posting exactness requires 2n < 2^16"
    t = 2 * n
    sentinel = jnp.int32(a)

    dr_active = pend_mask | post_mask
    cr_active = dr_active
    dr_s = jnp.where(dr_active, dr_slot, sentinel)
    cr_s = jnp.where(cr_active, cr_slot, sentinel)
    all_slots = jnp.concatenate([dr_s, cr_s])
    uniq = jnp.unique(all_slots, size=t, fill_value=sentinel)
    ix_dr = jnp.searchsorted(uniq, dr_s).astype(jnp.int32)
    ix_cr = jnp.searchsorted(uniq, cr_s).astype(jnp.int32)

    halves = u128.split_u16(amount)  # (n, 8)
    zeros8 = jnp.zeros_like(halves)

    def accum(ix, mask):
        vals = jnp.where(mask[:, None], halves, zeros8)
        return jnp.zeros((t, 8), dtype=jnp.uint32).at[ix].add(vals, mode="drop")

    d_dp, over_dp = u128.combine_u16(accum(ix_dr, pend_mask))
    d_dpo, over_dpo = u128.combine_u16(accum(ix_dr, post_mask))
    d_cp, over_cp = u128.combine_u16(accum(ix_cr, pend_mask))
    d_cpo, over_cpo = u128.combine_u16(accum(ix_cr, post_mask))

    rows = jnp.clip(uniq, 0, a - 1)
    row_valid = uniq < a

    new_rows = {}
    over = over_dp | over_dpo | over_cp | over_cpo
    for name, delta in (
        ("debits_pending", d_dp), ("debits_posted", d_dpo),
        ("credits_pending", d_cp), ("credits_posted", d_cpo),
    ):
        cur = getattr(state, name)[rows]
        nxt, o = u128.add(cur, delta)
        over = over | o
        new_rows[name] = nxt

    # Combined debits/credits overflow (OVERFLOWS_DEBITS / OVERFLOWS_CREDITS,
    # state_machine.zig:1318-1324): monotone, so batch-final totals suffice.
    _, o5 = u128.add(new_rows["debits_pending"], new_rows["debits_posted"])
    _, o6 = u128.add(new_rows["credits_pending"], new_rows["credits_posted"])
    over = over | o5 | o6

    scatter_rows = jnp.where(row_valid, rows, jnp.int32(a))
    new_state = state._replace(**{
        name: getattr(state, name).at[scatter_rows].set(new_rows[name], mode="drop")
        for name in new_rows
    })
    return new_state, jnp.any(over & row_valid)


create_transfers_fast = jax.jit(create_transfers_fast_impl)


@jax.jit
def register_accounts(
    state: LedgerState,
    slots: jnp.ndarray,  # (n,) i32 — host-assigned slots for NEW accounts
    ledger: jnp.ndarray,  # (n,) u32
    flags: jnp.ndarray,  # (n,) u32
    mask: jnp.ndarray,  # (n,) bool — which events actually create
) -> LedgerState:
    """Install freshly created accounts' immutable fields (balances are
    already zero — create_account requires zero balances,
    state_machine.zig:1210-1217)."""
    safe = jnp.where(mask, slots, state.ledger.shape[0]).astype(jnp.int32)
    return state._replace(
        ledger=state.ledger.at[safe].set(ledger, mode="drop"),
        flags=state.flags.at[safe].set(flags, mode="drop"),
    )


@jax.jit
def write_balances(
    state: LedgerState,
    slots: jnp.ndarray,  # (k,) i32
    debits_pending: jnp.ndarray,  # (k, 4) u32
    debits_posted: jnp.ndarray,
    credits_pending: jnp.ndarray,
    credits_posted: jnp.ndarray,
) -> LedgerState:
    """Scatter exact balances for `slots` (serial-fallback writeback path)."""
    s = slots.astype(jnp.int32)
    return state._replace(
        debits_pending=state.debits_pending.at[s].set(debits_pending, mode="drop"),
        debits_posted=state.debits_posted.at[s].set(debits_posted, mode="drop"),
        credits_pending=state.credits_pending.at[s].set(credits_pending, mode="drop"),
        credits_posted=state.credits_posted.at[s].set(credits_posted, mode="drop"),
    )


@jax.jit
def read_balances(state: LedgerState, slots: jnp.ndarray):
    """Gather balances for `slots` (prefetch / lookup / serial-fallback)."""
    s = jnp.clip(slots.astype(jnp.int32), 0, state.ledger.shape[0] - 1)
    return (
        state.debits_pending[s],
        state.debits_posted[s],
        state.credits_pending[s],
        state.credits_posted[s],
    )


def create_transfers_exact(
    state, b, host_code, pending, chain_id, plan=None, has_pv=True, has_chains=True
):
    """Facade re-export so every ops backend (this module, ShardedOps)
    exposes the same surface and the dispatcher never falls back silently.
    Lazy import: commit_exact imports from this module."""
    from tigerbeetle_tpu.ops import commit_exact

    return commit_exact.create_transfers_exact(
        state, b, host_code, pending, chain_id, plan,
        has_pv=has_pv, has_chains=has_chains,
    )
