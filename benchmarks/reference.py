"""The plain reference: TigerBeetle's ledger semantics, as far as the
benchmark's traffic uses them, in numpy. Imports nothing of the program.

It decides `correct`: the same operations on the same data must give the
same result codes, the same stored transfers and the same balances as
the served system gave. It follows the published state machine
(tigerbeetle `src/state_machine.zig`, `create_transfer`,
`post_or_void_pending_transfer`, the linked-chain loop of `execute`) for
the events the benchmark's mixes send: simple and pending transfers,
post and void of a pending transfer created by an EARLIER request,
linked chains, `balancing_debit` / `balancing_credit` transfers; on
accounts that carry no flag or one of the two limit flags
(`debits_must_not_exceed_credits`, `credits_must_not_exceed_debits`);
ids and amounts below 2^40.

It is plain in one of two ways, batch by batch:

- A batch with no balancing flag that touches no account with a limit
  flag holds no balance check that can fail, and sums commute: no answer
  depends on the order of its events, nor on the order of batches from
  different sessions. It is applied whole, in numpy.
- Any other batch is applied event by event, in the order of the batch,
  in a plain loop over Python integers (`_in_order`): the balancing clamp
  against `credits_posted - (debits_posted + debits_pending)` and its
  mirror (amount 0 stands for "as much as there is"; nothing left reads
  `exceeds_credits` / `exceeds_debits`), then the limit predicates of
  `tigerbeetle.zig` (`debits_pending + debits_posted + amount >
  credits_posted` on a debit account that must not exceed its credits,
  and the mirror), a pending amount counting against the limit, the
  stored transfer carrying its POST-CLAMP amount, and the open linked
  chain walked back, event by event, when one of its links is refused. Such answers depend on the order in which the batches were
  committed: the caller replays them in that order (run.py: the `op` of
  each reply).

The rungs that read no balance are the same in both, computed for the
whole batch first. It checks every precondition itself and raises
`Unsupported` where a batch falls outside them, so it can never quietly
judge what it does not model:

- no time-outs; no account flag but the two limit flags, and not both
  on one account (no history, `closing_*` or `imported`); no transfer
  flag but linked, pending, post, void and the two balancing flags;
- every transfer id is new (never sent before, not twice in a batch);
- a post/void names a pending transfer that is not in the same batch,
  and no two events of one batch name the same one;
- the batch does not end inside a linked chain.

A read (`lookup_accounts`) changes nothing and answers from the balances
as the transfers applied before it left them: the caller asks at the
read's own place in the commit order (run.py), and that place is what is
judged.

The wire layouts are the protocol's (128-byte Account and Transfer,
8-byte result pairs, 16-byte ids), written out here again on purpose.
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
ID = np.dtype([("lo", "<u8"), ("hi", "<u8")])  # an event of lookup_accounts: one id
assert ACCOUNT.itemsize == TRANSFER.itemsize == 128 and ID.itemsize == 16

LINKED, PENDING, POST, VOID, BALANCING_DEBIT, BALANCING_CREDIT = 1, 2, 4, 8, 16, 32
BALANCING = BALANCING_DEBIT | BALANCING_CREDIT
# AccountFlags: the two limits. (1 is linked, 8 history: not modelled.)
DEBITS_MUST_NOT_EXCEED_CREDITS, CREDITS_MUST_NOT_EXCEED_DEBITS = 2, 4
LIMITS = DEBITS_MUST_NOT_EXCEED_CREDITS | CREDITS_MUST_NOT_EXCEED_DEBITS

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
EXCEEDS_CREDITS = 54
EXCEEDS_DEBITS = 55

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
        flags = events["flags"].astype(np.int64)
        _need(not (flags & ~LIMITS).any() and not (flags == LIMITS).any(),
              "account flags other than one of the two limits")
        _need(not events["timestamp"].any() and not events["reserved"].any(),
              "account timestamp or reserved")
        _need(bool(np.all(events["ledger"] > 0) and np.all(events["code"] > 0)),
              "account ledger or code zero")
        for b in BALANCES:
            _need(not events[b + "_lo"].any() and not events[b + "_hi"].any(),
                  "account created with a balance")
        self.accounts[ids] = events
        self.exists[ids] = True
        return np.zeros(0, dtype=RESULT)

    def lookup_accounts(self, ids: np.ndarray) -> np.ndarray:
        """The stored accounts among `ids` (plain integers, or the `ID`
        events of a request's body), in the request's order, timestamps 0:
        an id named twice is answered twice, an id that names no account is
        passed over (tigerbeetle `src/state_machine.zig`, `execute_lookup_accounts`)."""
        if getattr(ids, "dtype", None) == ID:
            _need(not ids["hi"].any(), "id_hi")
            ids = ids["lo"]
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

        _need(not (flags & ~(LINKED | PENDING | POST | VOID | BALANCING)).any(), "flags")
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
        _need(not (is_post & is_void).any()
              and not (pv & ((flags & (PENDING | BALANCING)) != 0)).any(),
              "mutually exclusive flags")
        balancing = (flags & BALANCING) != 0

        code = np.zeros(n, dtype=np.uint32)

        def ladder(cond, result):
            np.copyto(code, np.uint32(result), where=(code == 0) & cond)

        # create_transfer's ladder, the rungs the traffic can reach, in order.
        ladder(reg & (dr == 0), DEBIT_ACCOUNT_ID_MUST_NOT_BE_ZERO)
        ladder(reg & (cr == 0), CREDIT_ACCOUNT_ID_MUST_NOT_BE_ZERO)
        ladder(reg & (dr == cr), ACCOUNTS_MUST_BE_DIFFERENT)
        ladder(reg & (pid != 0), PENDING_ID_MUST_BE_ZERO)
        ladder(reg & ~balancing & (amount == 0), AMOUNT_MUST_NOT_BE_ZERO)
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

        # The accounts an event that passed those rungs would move: its own
        # pair, or the pair of the pending transfer it settles.
        live = code == 0
        at_dr = np.where(pv, p["debit_account_id_lo"], dr_at)
        at_cr = np.where(pv, p["credit_account_id_lo"], cr_at)
        in_order = bool(balancing.any() or self.accounts["flags"][
            np.concatenate([at_dr[live], at_cr[live]])].any())
        if in_order:
            # A balance check can fail: event by event, in the batch's order.
            code, amount = self._in_order(code, flags, amount, at_dr, at_cr, pv,
                                          settle, p["amount_lo"])
        elif n:
            # Linked chains: the first member to fail keeps its code, every
            # other member of that chain reads linked_event_failed, and
            # nothing of the chain is applied.
            linked = (flags & LINKED) != 0
            head = np.ones(n, dtype=bool)
            head[1:] = ~linked[:-1]
            chain = np.cumsum(head) - 1
            first_bad = np.minimum.reduceat(np.where(code != 0, idx, n),
                                            np.nonzero(head)[0])[chain]
            code = np.where((first_bad < n) & (idx != first_bad),
                            np.uint32(LINKED_EVENT_FAILED), code)

        ok = code == 0
        is_pending = (flags & PENDING) != 0
        m_post = ok & is_post
        if not in_order:  # no check reads a balance: sums, in any order
            add, sub = np.add.at, np.subtract.at
            m = ok & reg & ~is_pending
            add(self.balance["debits_posted"], dr_at[m], amount[m])
            add(self.balance["credits_posted"], cr_at[m], amount[m])
            m = ok & reg & is_pending
            add(self.balance["debits_pending"], dr_at[m], amount[m])
            add(self.balance["credits_pending"], cr_at[m], amount[m])
            m = ok & pv
            sub(self.balance["debits_pending"], p["debit_account_id_lo"][m], p["amount_lo"][m])
            sub(self.balance["credits_pending"], p["credit_account_id_lo"][m], p["amount_lo"][m])
            add(self.balance["debits_posted"], p["debit_account_id_lo"][m_post], settle[m_post])
            add(self.balance["credits_posted"], p["credit_account_id_lo"][m_post], settle[m_post])
        created = ev[ok & reg & is_pending]
        created["amount_lo"] = amount[ok & reg & is_pending]  # what a balancing clamp left of it
        self._remember_pending(created)
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

    def _in_order(self, code, flags, amount, at_dr, at_cr, pv, settle, p_amount):
        """The batch event by event, as the published `execute` loop runs it:
        `code` holds what the rungs that read no balance said. Returns the
        final codes and, for the created transfers, the amounts stored.
        Plain Python integers on the balances of the accounts the batch
        names; written back at the end."""
        n = len(code)
        accounts, local = np.unique(np.concatenate([at_dr, at_cr]), return_inverse=True)
        dp, dpo, cp, cpo = (self.balance[b][accounts].tolist() for b in BALANCES)
        limit = self.accounts["flags"][accounts]
        debits_limited = ((limit & DEBITS_MUST_NOT_EXCEED_CREDITS) != 0).tolist()
        credits_limited = ((limit & CREDITS_MUST_NOT_EXCEED_DEBITS) != 0).tolist()
        dr_of, cr_of = local[:n].tolist(), local[n:].tolist()
        code, flags, amount = code.tolist(), flags.tolist(), amount.tolist()
        pv, settle, p_amount = pv.tolist(), settle.tolist(), p_amount.tolist()

        def move(i, d, c, sign):
            """Event i's effect on its two accounts (sign -1: taken back)."""
            if pv[i]:
                dp[d] -= sign * p_amount[i]
                cp[c] -= sign * p_amount[i]
                if flags[i] & POST:
                    dpo[d] += sign * settle[i]
                    cpo[c] += sign * settle[i]
            elif flags[i] & PENDING:
                dp[d] += sign * amount[i]
                cp[c] += sign * amount[i]
            else:
                dpo[d] += sign * amount[i]
                cpo[c] += sign * amount[i]

        first = -1  # the open chain's first event, or -1
        failed = False  # a link of the open chain was refused
        for i in range(n):
            f, d, c = flags[i], dr_of[i], cr_of[i]
            if first < 0 and f & LINKED:
                first, failed = i, False
            if failed:
                code[i] = LINKED_EVENT_FAILED
            else:
                result = code[i]
                if result == 0 and not pv[i]:
                    a = amount[i]
                    if f & BALANCING:
                        a = a or (1 << 64) - 1  # 0: as much as there is
                        if f & BALANCING_DEBIT:
                            a = min(a, max(0, cpo[d] - dpo[d] - dp[d]))
                            if a == 0:
                                result = EXCEEDS_CREDITS
                        if result == 0 and f & BALANCING_CREDIT:
                            a = min(a, max(0, dpo[c] - cpo[c] - cp[c]))
                            if a == 0:
                                result = EXCEEDS_DEBITS
                        amount[i] = a
                    if result == 0:
                        if debits_limited[d] and dp[d] + dpo[d] + a > cpo[d]:
                            result = EXCEEDS_CREDITS
                        elif credits_limited[c] and cp[c] + cpo[c] + a > dpo[c]:
                            result = EXCEEDS_DEBITS
                if result == 0:
                    move(i, d, c, 1)
                else:
                    code[i] = result
                    if first >= 0:  # the chain rolls back whole
                        failed = True
                        for j in range(first, i):
                            move(j, dr_of[j], cr_of[j], -1)
                            code[j] = LINKED_EVENT_FAILED
            if not f & LINKED:
                first = -1
                failed = False
        for b, values in zip(BALANCES, (dp, dpo, cp, cpo)):
            self.balance[b][accounts] = np.array(values, dtype=np.uint64)
        return np.array(code, dtype=np.uint32), np.array(amount, dtype=np.uint64)

    def _remember_pending(self, created: np.ndarray) -> None:
        k = len(created)
        while self.pending_count + k > len(self.pending):
            self.pending = np.concatenate([self.pending, np.zeros_like(self.pending)])
        at = np.arange(self.pending_count, self.pending_count + k)
        self.pending[at] = created
        self.row[created["id_lo"]] = at
        self.kind[created["id_lo"]] = OPEN
        self.pending_count += k
