"""The plain reference: TigerBeetle's ledger semantics, as far as the
benchmark's traffic uses them, in numpy. Imports nothing of the program.

It decides `correct`: the same operations on the same data must give the
same result codes, the same stored transfers and the same balances as
the served system gave. It follows the published state machine
(tigerbeetle `src/state_machine.zig`, `create_transfer`,
`post_or_void_pending_transfer`, the linked-chain loop of `execute`) for
the events the benchmark's mixes send: simple and pending transfers,
post and void of a pending transfer created by an EARLIER request,
linked chains; on accounts without flags, ids and amounts below 2^63.

What makes it plain is that it may apply whole batches at once: under
the preconditions below no answer depends on the order of the events in
a batch, nor on the order of batches from different sessions. It checks
every precondition itself and raises `Unsupported` where a batch falls
outside them, so it can never quietly judge what it does not model:

- no balancing flags, no time-outs, no account with limit or history
  flags (then no balance check can fail, and sums commute);
- every transfer id is new (never sent before, not twice in a batch);
- a post/void names a pending transfer that is not in the same batch,
  and no two events of one batch name the same one;
- the batch does not end inside a linked chain.

The wire layouts are the protocol's (128-byte Account and Transfer,
8-byte result pairs), written out here again on purpose.
"""

from __future__ import annotations

import numpy as np

ACCOUNT = np.dtype([
    ("id_lo", "<u8"), ("id_hi", "<u8"),
    ("debits_pending_lo", "<u8"), ("debits_pending_hi", "<u8"),
    ("debits_posted_lo", "<u8"), ("debits_posted_hi", "<u8"),
    ("credits_pending_lo", "<u8"), ("credits_pending_hi", "<u8"),
    ("credits_posted_lo", "<u8"), ("credits_posted_hi", "<u8"),
    ("user_data_128_lo", "<u8"), ("user_data_128_hi", "<u8"),
    ("user_data_64", "<u8"), ("user_data_32", "<u4"), ("reserved", "<u4"),
    ("ledger", "<u4"), ("code", "<u2"), ("flags", "<u2"), ("timestamp", "<u8"),
])
TRANSFER = np.dtype([
    ("id_lo", "<u8"), ("id_hi", "<u8"),
    ("debit_account_id_lo", "<u8"), ("debit_account_id_hi", "<u8"),
    ("credit_account_id_lo", "<u8"), ("credit_account_id_hi", "<u8"),
    ("amount_lo", "<u8"), ("amount_hi", "<u8"),
    ("pending_id_lo", "<u8"), ("pending_id_hi", "<u8"),
    ("user_data_128_lo", "<u8"), ("user_data_128_hi", "<u8"),
    ("user_data_64", "<u8"), ("user_data_32", "<u4"), ("timeout", "<u4"),
    ("ledger", "<u4"), ("code", "<u2"), ("flags", "<u2"), ("timestamp", "<u8"),
])
RESULT = np.dtype([("index", "<u4"), ("result", "<u4")])
assert ACCOUNT.itemsize == TRANSFER.itemsize == 128

LINKED, PENDING, POST, VOID = 1, 2, 4, 8

# CreateTransferResult, by the protocol's numbering.
LINKED_EVENT_FAILED = 1
DEBIT_ACCOUNT_ID_MUST_NOT_BE_ZERO = 8
CREDIT_ACCOUNT_ID_MUST_NOT_BE_ZERO = 10
ACCOUNTS_MUST_BE_DIFFERENT = 12
PENDING_ID_MUST_BE_ZERO = 13
PENDING_ID_MUST_NOT_BE_ZERO = 14
PENDING_ID_MUST_BE_DIFFERENT = 16
AMOUNT_MUST_NOT_BE_ZERO = 18
LEDGER_MUST_NOT_BE_ZERO = 19
CODE_MUST_NOT_BE_ZERO = 20
DEBIT_ACCOUNT_NOT_FOUND = 21
CREDIT_ACCOUNT_NOT_FOUND = 22
ACCOUNTS_MUST_HAVE_THE_SAME_LEDGER = 23
TRANSFER_MUST_HAVE_THE_SAME_LEDGER_AS_ACCOUNTS = 24
PENDING_TRANSFER_NOT_FOUND = 25
PENDING_TRANSFER_NOT_PENDING = 26
PENDING_TRANSFER_HAS_DIFFERENT_DEBIT_ACCOUNT_ID = 27
PENDING_TRANSFER_HAS_DIFFERENT_CREDIT_ACCOUNT_ID = 28
PENDING_TRANSFER_HAS_DIFFERENT_LEDGER = 29
PENDING_TRANSFER_HAS_DIFFERENT_CODE = 30
EXCEEDS_PENDING_TRANSFER_AMOUNT = 31
PENDING_TRANSFER_HAS_DIFFERENT_AMOUNT = 32
PENDING_TRANSFER_ALREADY_POSTED = 33
PENDING_TRANSFER_ALREADY_VOIDED = 34

# What the ledger remembers of a transfer id.
ABSENT, STORED, OPEN, POSTED, VOIDED = 0, 1, 2, 3, 4

BALANCES = ("debits_pending", "debits_posted", "credits_pending", "credits_posted")
SMALL = np.uint64(1 << 40)  # ids and amounts stay far below 2^63: sums cannot wrap


class Unsupported(Exception):
    """The batch is outside what this reference models."""


def _need(ok: bool, what: str) -> None:
    if not ok:
        raise Unsupported(what)


class Ledger:
    def __init__(self, accounts_max: int) -> None:
        self.accounts = np.zeros(accounts_max + 1, dtype=ACCOUNT)  # by id
        self.exists = np.zeros(accounts_max + 1, dtype=bool)
        self.balance = {b: np.zeros(accounts_max + 1, dtype=np.uint64)
                        for b in BALANCES}
        self.kind = np.zeros(1 << 16, dtype=np.uint8)  # by transfer id
        self.sent = np.zeros(1 << 16, dtype=bool)
        self.row = np.full(1 << 16, -1, dtype=np.int64)  # into self.pending
        self.pending = np.zeros(1 << 12, dtype=TRANSFER)
        self.pending_count = 0

    # accounts ------------------------------------------------------------

    def create_accounts(self, events: np.ndarray) -> np.ndarray:
        ids = events["id_lo"]
        _need(bool(np.all(events["id_hi"] == 0) and np.all(ids > 0)
                   and np.all(ids < len(self.exists))), "account ids out of range")
        _need(len(np.unique(ids)) == len(ids) and not self.exists[ids].any(),
              "an account id twice")
        _need(not events["flags"].any() and not events["timestamp"].any()
              and not events["reserved"].any(), "account flags")
        _need(bool(np.all(events["ledger"] > 0) and np.all(events["code"] > 0)),
              "account ledger or code zero")
        for b in BALANCES:
            _need(not events[b + "_lo"].any() and not events[b + "_hi"].any(),
                  "account created with a balance")
        self.accounts[ids] = events
        self.exists[ids] = True
        return np.zeros(0, dtype=RESULT)

    def lookup_accounts(self, ids: np.ndarray) -> np.ndarray:
        """The stored accounts among `ids`, in that order, timestamps 0."""
        ids = np.asarray(ids, dtype=np.uint64)
        found = ids[(ids < len(self.exists)) & self.exists[
            np.minimum(ids, len(self.exists) - 1)]]
        out = self.accounts[found].copy()
        for b in BALANCES:
            out[b + "_lo"] = self.balance[b][found]
        return out

    # transfers -----------------------------------------------------------

    def _grow(self, top: int) -> None:
        if top < len(self.kind):
            return
        size = len(self.kind)
        while size <= top:
            size *= 2
        for name, fill in (("kind", 0), ("sent", False), ("row", -1)):
            old = getattr(self, name)
            new = np.full(size, fill, dtype=old.dtype)
            new[: len(old)] = old
            setattr(self, name, new)

    def _account(self, ids: np.ndarray):
        """(found, id clipped into the table) for account ids."""
        inside = ids < len(self.exists)
        safe = np.where(inside, ids, 0)
        return inside & self.exists[safe], safe

    def create_transfers(self, events: np.ndarray):
        """Returns (results, stored): the (index, result) pairs of the
        events that failed, and the transfers that were stored, as the
        ledger would return them with the timestamp zeroed."""
        ev = events
        n = len(ev)
        idx = np.arange(n)
        flags = ev["flags"].astype(np.int64)
        ids = ev["id_lo"]
        dr, cr = ev["debit_account_id_lo"], ev["credit_account_id_lo"]
        amount, pid = ev["amount_lo"], ev["pending_id_lo"]

        _need(not (flags & ~(LINKED | PENDING | POST | VOID)).any(), "flags")
        _need(not ev["timeout"].any() and not ev["timestamp"].any(),
              "time-outs or timestamps")
        for hi in ("id_hi", "debit_account_id_hi", "credit_account_id_hi",
                   "amount_hi", "pending_id_hi"):
            _need(not ev[hi].any(), hi)
        _need(bool(np.all(ids > 0) and np.all(ids < SMALL) and np.all(amount < SMALL)),
              "ids or amounts out of range")
        self._grow(int(ids.max()))
        _need(len(np.unique(ids)) == n and not self.sent[ids].any(),
              "a transfer id sent twice")
        _need(n == 0 or not flags[-1] & LINKED, "batch ends inside a chain")
        is_post, is_void = (flags & POST) != 0, (flags & VOID) != 0
        pv = is_post | is_void
        reg = ~pv
        _need(not (is_post & is_void).any() and not (pv & ((flags & PENDING) != 0)).any(),
              "mutually exclusive flags")

        code = np.zeros(n, dtype=np.uint32)

        def ladder(cond, result):
            np.copyto(code, np.uint32(result), where=(code == 0) & cond)

        # create_transfer's ladder, the rungs the traffic can reach, in order.
        ladder(reg & (dr == 0), DEBIT_ACCOUNT_ID_MUST_NOT_BE_ZERO)
        ladder(reg & (cr == 0), CREDIT_ACCOUNT_ID_MUST_NOT_BE_ZERO)
        ladder(reg & (dr == cr), ACCOUNTS_MUST_BE_DIFFERENT)
        ladder(reg & (pid != 0), PENDING_ID_MUST_BE_ZERO)
        ladder(reg & (amount == 0), AMOUNT_MUST_NOT_BE_ZERO)
        ladder(reg & (ev["ledger"] == 0), LEDGER_MUST_NOT_BE_ZERO)
        ladder(reg & (ev["code"] == 0), CODE_MUST_NOT_BE_ZERO)
        dr_found, dr_at = self._account(dr)
        cr_found, cr_at = self._account(cr)
        ladder(reg & ~dr_found, DEBIT_ACCOUNT_NOT_FOUND)
        ladder(reg & ~cr_found, CREDIT_ACCOUNT_NOT_FOUND)
        dr_ledger = self.accounts["ledger"][dr_at]
        ladder(reg & (dr_ledger != self.accounts["ledger"][cr_at]),
               ACCOUNTS_MUST_HAVE_THE_SAME_LEDGER)
        ladder(reg & (ev["ledger"] != dr_ledger),
               TRANSFER_MUST_HAVE_THE_SAME_LEDGER_AS_ACCOUNTS)

        # post_or_void_pending_transfer's ladder.
        ladder(pv & (pid == 0), PENDING_ID_MUST_NOT_BE_ZERO)
        ladder(pv & (pid == ids), PENDING_ID_MUST_BE_DIFFERENT)
        _need(not np.isin(pid[pv], ids).any(),
              "post/void of a transfer of the same batch")
        inside = pid < len(self.kind)
        p_kind = np.where(inside, self.kind[np.where(inside, pid, 0)], ABSENT)
        ladder(pv & (p_kind == ABSENT), PENDING_TRANSFER_NOT_FOUND)
        ladder(pv & (p_kind == STORED), PENDING_TRANSFER_NOT_PENDING)
        has_p = pv & (p_kind >= OPEN)
        p = self.pending[np.where(has_p, self.row[np.where(inside, pid, 0)], 0)]
        ladder(has_p & (dr > 0) & (dr != p["debit_account_id_lo"]),
               PENDING_TRANSFER_HAS_DIFFERENT_DEBIT_ACCOUNT_ID)
        ladder(has_p & (cr > 0) & (cr != p["credit_account_id_lo"]),
               PENDING_TRANSFER_HAS_DIFFERENT_CREDIT_ACCOUNT_ID)
        ladder(has_p & (ev["ledger"] > 0) & (ev["ledger"] != p["ledger"]),
               PENDING_TRANSFER_HAS_DIFFERENT_LEDGER)
        ladder(has_p & (ev["code"] > 0) & (ev["code"] != p["code"]),
               PENDING_TRANSFER_HAS_DIFFERENT_CODE)
        settle = np.where(amount > 0, amount, p["amount_lo"])
        ladder(has_p & (settle > p["amount_lo"]), EXCEEDS_PENDING_TRANSFER_AMOUNT)
        ladder(has_p & is_void & (settle < p["amount_lo"]),
               PENDING_TRANSFER_HAS_DIFFERENT_AMOUNT)
        ladder(has_p & (p_kind == POSTED), PENDING_TRANSFER_ALREADY_POSTED)
        ladder(has_p & (p_kind == VOIDED), PENDING_TRANSFER_ALREADY_VOIDED)
        settles = has_p & (code == 0)
        _need(len(np.unique(pid[settles])) == int(settles.sum()),
              "two events of one batch settle the same pending transfer")

        # Linked chains: the first member to fail keeps its code, every
        # other member of that chain reads linked_event_failed, and
        # nothing of the chain is applied.
        if n:
            linked = (flags & LINKED) != 0
            head = np.ones(n, dtype=bool)
            head[1:] = ~linked[:-1]
            chain = np.cumsum(head) - 1
            first_bad = np.minimum.reduceat(np.where(code != 0, idx, n),
                                            np.nonzero(head)[0])[chain]
            code = np.where((first_bad < n) & (idx != first_bad),
                            np.uint32(LINKED_EVENT_FAILED), code)

        ok = code == 0
        touched = np.concatenate([dr_at[ok & reg], cr_at[ok & reg]])
        _need(not self.accounts["flags"][touched].any(), "an account with flags")

        add, sub = np.add.at, np.subtract.at
        is_pending = (flags & PENDING) != 0
        m = ok & reg & ~is_pending
        add(self.balance["debits_posted"], dr_at[m], amount[m])
        add(self.balance["credits_posted"], cr_at[m], amount[m])
        m = ok & reg & is_pending
        add(self.balance["debits_pending"], dr_at[m], amount[m])
        add(self.balance["credits_pending"], cr_at[m], amount[m])
        self._remember_pending(ev[m])
        m = ok & pv
        sub(self.balance["debits_pending"], p["debit_account_id_lo"][m], p["amount_lo"][m])
        sub(self.balance["credits_pending"], p["credit_account_id_lo"][m], p["amount_lo"][m])
        m_post = ok & is_post
        add(self.balance["debits_posted"], p["debit_account_id_lo"][m_post], settle[m_post])
        add(self.balance["credits_posted"], p["credit_account_id_lo"][m_post], settle[m_post])
        self.kind[pid[m_post]] = POSTED
        self.kind[pid[ok & is_void]] = VOIDED
        self.kind[ids[ok & ~(reg & is_pending)]] = STORED
        self.sent[ids] = True

        stored = ev.copy()
        for name in ("debit_account_id_lo", "credit_account_id_lo", "ledger", "code"):
            stored[name] = np.where(pv, p[name], ev[name])
        stored["amount_lo"] = np.where(pv, settle, amount)
        own_128 = (ev["user_data_128_lo"] != 0) | (ev["user_data_128_hi"] != 0)
        for name, own in (("user_data_128_lo", own_128), ("user_data_128_hi", own_128),
                          ("user_data_64", ev["user_data_64"] != 0),
                          ("user_data_32", ev["user_data_32"] != 0)):
            stored[name] = np.where(pv & ~own, p[name], ev[name])
        bad = np.nonzero(~ok)[0]
        results = np.zeros(len(bad), dtype=RESULT)
        results["index"], results["result"] = bad, code[bad]
        return results, stored[ok]

    def _remember_pending(self, created: np.ndarray) -> None:
        k = len(created)
        while self.pending_count + k > len(self.pending):
            self.pending = np.concatenate([self.pending, np.zeros_like(self.pending)])
        at = np.arange(self.pending_count, self.pending_count + k)
        self.pending[at] = created
        self.row[created["id_lo"]] = at
        self.kind[created["id_lo"]] = OPEN
        self.pending_count += k
