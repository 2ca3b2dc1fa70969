"""SmallBank's transactions as double-entry transfers, from a seed.

SmallBank (Alomari, Cahill, Fekete, Roehm, "The Cost of Serializability on
Platforms That Use Snapshot Isolation", ICDE 2008; H-Store's and
OLTP-Bench's `smallbank`, which add SendPayment): every customer has a
savings and a checking balance; a transaction names one or two customers,
drawn from a hotspot of `hotspot_customers` with probability
`hotspot_share` and from the other customers else. Double entry has no
one-sided update, so money enters and leaves through the bank's own
accounts, and a rule "roll back if the balance would go negative" is the
ledger's own: the customers' accounts carry
`debits_must_not_exceed_credits`, and the ledger refuses the transfer
with `exceeds_credits`. The transactions (`code` of the stored transfer):

  deposit_checking  1  bank -> checking(c), `amounts.deposit_checking`
  transact_savings  2  savings(c) -> bank, `amounts.transact_savings`:
                       refused where the savings do not cover it
  write_check       3  checking(c) -> bank, `amounts.write_check`: refused
                       where the checking balance does not cover it
                       (SmallBank overdraws with a penalty instead: a
                       ledger account that may not be overdrawn cannot)
  send_payment      4  checking(c1) -> checking(c2), `amounts.send_payment`:
                       refused where c1's checking does not cover it
  amalgamate      5,6  two events, not linked: savings(c1) -> checking(c2)
                       and checking(c1) -> checking(c2), both
                       `balancing_debit` with `amounts.amalgamate_max` as
                       the upper bound: each moves what is there and is
                       stored with the amount that moved, or is refused
                       where nothing is (which moves nothing, as SmallBank's
                       would)
  balance              a read: savings(c) and checking(c) by one
                       `lookup_accounts`; sent where the traffic file's
                       `weights` hold `balance`, else left out (the file
                       then says so: `weights_left_out`)

A batch holds the same count of each kind in every seed: the traffic
file's `weights`, scaled to the batch's events (an amalgamate is two;
`balance` takes no part in this), what is left over going to
send_payment; the transactions are shuffled through the batch as whole
units.

With `weights.balance` a session alternates: request 2k is write batch k,
to the byte the batch a file without it gives, and request 2k + 1 is one
`lookup_accounts` that holds the Balance transactions that go with that
batch: floor(transactions of a batch x balance / the other five weights)
customers, drawn by the hotspot rule from (seed, session, k), each as its
savings id then its checking id. A hot customer is named several times in
one read; a customer whose load has not been committed yet reads zeros.
Without it request k is write batch k (`request` says which).

The accounts, by id: the bank's accounts 1..B, one per
`customers_per_bank_account` customers (at least one), then the savings
accounts, then the checking accounts, customer by customer; the hotspot is
the first `hotspot_customers` customers (at most a tenth of them, so that
a rehearsal at 1,000 accounts has cold customers too). customers =
(accounts - B) // 2; an account left over carries no flag and is never
named.

SmallBank's loader gives every savings and every checking account a
balance between `initial_balance`'s two ends. A ledger's accounts begin at
zero, so the load is traffic: the run's first ceil(2 x customers / batch)
batches (batch (session, seq) is number seq x sessions + session of the
run) deposit it, bank -> account, account by account; the last of them is
shorter than a batch. A session that is ahead of the others may send
transactions on customers whose load has not been committed yet: they are
refused, as the commit order has it.

As in `ledger_mix`: batch (session, seq), and so request (session, seq),
is a pure function of (seed, session, seq) and of the parameters; it never
reads a reply. The answers depend on the order of the events in a batch
and on the order in which the server commits the sessions' requests, reads
among them (run.py replays in that order).
"""

from __future__ import annotations

import numpy as np

from benchmarks.generators import ledger_mix
from benchmarks.reference import BALANCING_DEBIT, DEBITS_MUST_NOT_EXCEED_CREDITS, ID, TRANSFER

KINDS = ("deposit_checking", "transact_savings", "write_check", "send_payment", "amalgamate")
DEPOSIT, SAVINGS, CHECK, PAYMENT, AMALGAMATE = range(5)
EVENTS = (1, 1, 1, 1, 2)  # of a transaction, by kind
LOAD_CODE = 7


class Generator(ledger_mix.Generator):
    """`ids` and `rng` are ledger_mix's."""

    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        super().__init__(config, traffic, seed)
        per_bank = int(config["customers_per_bank_account"])
        self.banks = max(1, self.accounts // (2 * per_bank + 1))
        self.customers = (self.accounts - self.banks) // 2
        if self.customers < 2:
            raise ValueError(f"{self.accounts} accounts leave fewer than two customers")
        self.first_savings = self.banks + 1
        self.first_checking = self.first_savings + self.customers
        self.initial_balance = [int(v) for v in config["initial_balance"]]
        self.load_batches = -(-2 * self.customers // self.n)
        self.hot = min(int(traffic["hotspot_customers"]), self.customers // 10)
        self.hot_share = float(traffic["hotspot_share"]) if self.hot else 0.0
        self.amount = {k: int(v) for k, v in traffic["amounts"].items()}
        weights = [float(traffic["weights"][k]) for k in KINDS]
        unit = self.n / sum(w * e for w, e in zip(weights, EVENTS))
        self.count = [int(w * unit) for w in weights]
        self.count[PAYMENT] += self.n - sum(c * e for c, e in zip(self.count, EVENTS))
        # Balance transactions to a batch: its weight against the five that write.
        self.balances = int(sum(self.count) * float(traffic["weights"].get("balance", 0))
                            / sum(weights))

    # accounts ------------------------------------------------------------

    def account_batches(self):
        last = self.first_checking + self.customers - 1
        for acc in super().account_batches():
            customer = (acc["id_lo"] >= self.first_savings) & (acc["id_lo"] <= last)
            acc["flags"] = np.where(customer, DEBITS_MUST_NOT_EXCEED_CREDITS, 0)
            yield acc

    # transfers -----------------------------------------------------------

    def _load(self, t: np.ndarray, number: int) -> np.ndarray:
        """Load batch `number`: the initial balances of accounts
        number x batch .. (savings first, then checking)."""
        rng = self.rng(2, number)
        first = number * self.n
        t = t[: min(self.n, 2 * self.customers - first)]
        t["debit_account_id_lo"] = rng.integers(1, self.banks + 1, len(t), dtype=np.uint64)
        t["credit_account_id_lo"] = self.first_savings + first + np.arange(len(t))
        t["amount_lo"] = rng.integers(self.initial_balance[0], self.initial_balance[1] + 1, len(t))
        t["code"] = LOAD_CODE
        return t

    def _customers(self, rng, k: int) -> np.ndarray:
        cold = rng.integers(self.hot, self.customers, k)
        if not self.hot:
            return cold
        return np.where(rng.random(k) < self.hot_share, rng.integers(0, self.hot, k), cold)

    def request(self, session: int, seq: int) -> tuple:
        """Request `seq` of a session as (operation, body)."""
        if not self.balances:
            return "create_transfers", self.batch(session, seq)
        k, read = divmod(seq, 2)
        if read:
            return "lookup_accounts", self.balance(session, k)
        return "create_transfers", self.batch(session, k)

    def balance(self, session: int, k: int) -> np.ndarray:
        """The ids of the Balance transactions that go with batch (session, k)."""
        c = self._customers(self.rng(3, session, k), self.balances)
        ids = np.zeros(2 * self.balances, dtype=ID)
        ids["lo"][0::2] = self.first_savings + c
        ids["lo"][1::2] = self.first_checking + c
        return ids

    def batch(self, session: int, seq: int) -> np.ndarray:
        n = self.n
        t = np.zeros(n, dtype=TRANSFER)
        t["id_lo"] = np.array(self.ids(session, seq), dtype=np.uint64)
        t["ledger"] = 1
        t["user_data_128_hi"] = seq + 1
        t["user_data_32"] = session + 1
        number = seq * self.sessions + session
        if number < self.load_batches:
            return self._load(t, number)

        rng = self.rng(1, session, seq)
        kinds = np.repeat(np.arange(len(KINDS)), self.count)[rng.permutation(sum(self.count))]
        length = np.take(EVENTS, kinds)
        at = np.cumsum(length) - length  # a transaction's first event
        c1 = self._customers(rng, len(kinds))
        c2 = self._customers(rng, len(kinds))
        c2 = np.where(c2 == c1, (c1 + 1) % self.customers, c2)
        bank = rng.integers(1, self.banks + 1, len(kinds))
        savings1, checking1 = self.first_savings + c1, self.first_checking + c1
        checking2 = self.first_checking + c2

        debit = np.choose(kinds, [bank, savings1, checking1, checking1, savings1])
        credit = np.choose(kinds, [checking1, bank, bank, checking2, checking2])
        amount = np.take([self.amount[k] for k in KINDS[:PAYMENT + 1]]
                         + [self.amount["amalgamate_max"]], kinds)
        t["debit_account_id_lo"][at] = debit
        t["credit_account_id_lo"][at] = credit
        t["amount_lo"][at] = amount
        t["code"][at] = kinds + 1
        t["user_data_64"][at] = c1 + 1
        t["user_data_128_lo"][at] = np.where(kinds >= PAYMENT, c2 + 1, 0)
        both = at[kinds == AMALGAMATE]
        t["flags"][both] = BALANCING_DEBIT
        t[both + 1] = t[both]  # the second event: c1's checking
        t["id_lo"][both + 1] = t["id_lo"][both] + np.uint64(1)
        t["debit_account_id_lo"][both + 1] = checking1[kinds == AMALGAMATE]
        t["code"][both + 1] = AMALGAMATE + 2
        return t
