"""TPC-B's debit-credit transaction as double-entry transfers, from a seed.

TPC Benchmark B (rev 2.0; `pgbench`'s built-in "TPC-B (sort of)"): per
unit of `scale` one branch, `tellers_per_branch` tellers and a block of
customer accounts; a transaction draws a teller uniformly, an account of
the teller's own branch (or, with probability `remote_share`, of another
branch) and a Delta, moves the Account, Teller and Branch balances by
Delta, all or nothing, and appends a History row.

Double entry has no one-sided update: each of the three is one transfer
between that row's account and the cash account of the TELLER's branch,
amount |Delta| (Delta > 0 debits cash and credits the row, Delta < 0 the
reverse), and the three are one linked chain in the order account,
teller, branch. The stored transfers are the History row: each carries
the account id (`user_data_128_lo`), the teller id (`user_data_64`) and
the branch number from 1 (`user_data_32`); `code` is the leg, 1 to 3.

The accounts, by id: customers 1..scale*per_branch (the branch of account
a is (a-1) // per_branch), then the tellers (tellers_per_branch
consecutive ids a branch), the branches, the cash accounts. per_branch is
derived from the configuration's `accounts`, `scale` and
`tellers_per_branch`, so a test can run the same shape at a small size.

Parameters of the traffic file: `sessions`, `remote_share`,
`chain_fail_one_in` (one transaction in this many carries a link with
amount zero, at a position that varies, so the whole chain rolls back).
Where `batch` is no multiple of 3 the last one or two events are simple
transfers between two customer accounts (`code` 4).

As in `ledger_mix`: batch (session, seq) is a pure function of (seed,
session, seq), and no answer depends on the order in which the server
commits the sessions' batches (no limits, balancing, time-outs or flags).
"""

from __future__ import annotations

import numpy as np

from benchmarks.generators import ledger_mix
from benchmarks.reference import LINKED, TRANSFER

DELTA_MAX = 999_999  # |Delta| is uniform in 1..DELTA_MAX, its sign even
LEGS = 3  # account, teller, branch


class Generator(ledger_mix.Generator):
    """`account_batches`, `ids` and `rng` are ledger_mix's."""

    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        super().__init__(config, traffic, seed)
        self.scale = int(config["scale"])
        self.tellers_per_branch = int(config["tellers_per_branch"])
        fixed = self.scale * (self.tellers_per_branch + 2)
        self.per_branch = (self.accounts - fixed) // self.scale
        if self.per_branch < 1 or self.scale * self.per_branch + fixed != self.accounts:
            raise ValueError(f"{self.accounts} accounts do not divide into {self.scale} branches")
        self.customers = self.scale * self.per_branch
        self.tellers = self.scale * self.tellers_per_branch
        self.first_teller = self.customers + 1
        self.first_branch = self.first_teller + self.tellers
        self.first_cash = self.first_branch + self.scale
        self.remote_share = float(traffic.get("remote_share", 0.0)) if self.scale > 1 else 0.0

    def batch(self, session: int, seq: int) -> np.ndarray:
        n, k = self.n, self.n // LEGS
        rng = self.rng(1, session, seq)
        t = np.zeros(n, dtype=TRANSFER)
        t["id_lo"] = np.array(self.ids(session, seq), dtype=np.uint64)
        t["ledger"] = 1
        t["user_data_128_hi"] = seq + 1

        teller = rng.integers(0, self.tellers, k)
        branch = teller // self.tellers_per_branch
        remote = rng.random(k) < self.remote_share
        other = rng.integers(0, max(self.scale - 1, 1), k)
        other += other >= branch  # uniform over the branches that are not the teller's
        account = (np.where(remote, other, branch) * self.per_branch
                   + rng.integers(0, self.per_branch, k) + 1)
        amount = rng.integers(1, DELTA_MAX + 1, k)
        deposit = rng.integers(0, 2, k).astype(bool)  # Delta > 0
        if self.chain_fail_one_in:
            broken = rng.permutation(k)[: ledger_mix._quota(seq, k / self.chain_fail_one_in)]
        else:
            broken = np.zeros(0, dtype=np.int64)

        rows = np.stack([account, self.first_teller + teller, self.first_branch + branch], axis=1)
        cash = np.repeat(self.first_cash + branch, LEGS)
        rows, leg_deposit = rows.reshape(-1), np.repeat(deposit, LEGS)
        legs = t[: k * LEGS]
        legs["debit_account_id_lo"] = np.where(leg_deposit, cash, rows)
        legs["credit_account_id_lo"] = np.where(leg_deposit, rows, cash)
        legs["amount_lo"] = np.repeat(amount, LEGS)
        legs["amount_lo"][broken * LEGS + broken % LEGS] = 0
        legs["code"] = np.tile(np.arange(1, LEGS + 1), k)
        legs["flags"] = np.tile([LINKED] * (LEGS - 1) + [0], k)
        legs["user_data_128_lo"] = np.repeat(account, LEGS)
        legs["user_data_64"] = np.repeat(self.first_teller + teller, LEGS)
        legs["user_data_32"] = np.repeat(branch + 1, LEGS)

        rest = t[k * LEGS:]
        if len(rest):
            dr = rng.integers(1, self.customers + 1, len(rest), dtype=np.uint64)
            cr = rng.integers(1, self.customers + 1, len(rest), dtype=np.uint64)
            rest["debit_account_id_lo"] = dr
            rest["credit_account_id_lo"] = np.where(
                cr == dr, dr % np.uint64(self.customers) + np.uint64(1), cr)
            rest["amount_lo"] = rng.integers(1, DELTA_MAX + 1, len(rest))
            rest["code"] = LEGS + 1
        return t
