"""The one traffic generator: ledger transfers in full batches, from a seed.

A traffic file under `benchmarks/traffic/` names this module and gives
its parameters; a new mix is a new data file. Batch (session, seq) is a
pure function of (seed, session, seq) and of the parameters, so the
sender and the plain reference's replay build the same bytes apart.

Parameters (all optional but `sessions`):

  zipf_s             0: account pairs drawn uniformly (upstream's
                     benchmark_load.zig); > 0: Zipf(s) over the accounts
  fail_share         share of the simple events made to fail on EACH of
                     four order-free rungs (same account twice, no such
                     debit account, wrong ledger, amount zero)
  shares.linked      share of a batch's events inside linked chains of 2-4
  shares.pending     share created pending (no time-out)
  shares.post_void   share that posts (half, every second one partially)
                     or voids (half) a pending transfer this session's
                     previous batch created
  chain_fail_one_in  one chain in this many carries a link with amount
                     zero, so the whole chain rolls back
  settle_fail_share  share of the post/void events aimed, in equal parts,
                     at an id that does not exist, at a transfer that was
                     never pending, and at one already posted or voided

No event's answer depends on the order in which the server commits the
sessions' batches: no limits, no balancing, no time-outs, and a session
settles only what its own earlier, already answered batches created.
"""

from __future__ import annotations

import numpy as np

from benchmarks.reference import ACCOUNT, LINKED, PENDING, POST, TRANSFER, VOID

SIMPLE, CHAIN, PEND, SETTLE = 0, 1, 2, 3


def _quota(seq: int, per_batch: float) -> int:
    """How many of a kind batch `seq` gets, so that the share holds over
    the batches and no seed gets more or fewer than another."""
    return int((seq + 1) * per_batch) - int(seq * per_batch)


class Generator:
    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        self.seed = seed
        self.accounts = int(config["accounts"])
        self.n = int(config["batch"])
        self.sessions = int(traffic["sessions"])
        shares = traffic.get("shares", {})
        self.share = {k: float(shares.get(k, 0.0))
                      for k in ("linked", "pending", "post_void")}
        self.fail_share = float(traffic.get("fail_share", 0.0))
        self.chain_fail_one_in = int(traffic.get("chain_fail_one_in", 0))
        self.settle_fail_share = float(traffic.get("settle_fail_share", 0.0))
        self.cdf = None
        s = float(traffic.get("zipf_s", 0.0))
        if s > 0.0:
            w = np.arange(1, self.accounts + 1, dtype=np.float64) ** -s
            self.cdf = np.cumsum(w) / w.sum()
        self.meta = {}  # (session, seq) -> what later batches settle

    def rng(self, *stream: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, *stream])

    # accounts ------------------------------------------------------------

    def account_batches(self):
        for start in range(1, self.accounts + 1, self.n):
            ids = np.arange(start, min(start + self.n, self.accounts + 1),
                            dtype=np.uint64)
            acc = np.zeros(len(ids), dtype=ACCOUNT)
            acc["id_lo"] = ids
            acc["user_data_128_lo"] = ids * np.uint64(0x9E3779B97F4A7C15)
            acc["user_data_128_hi"] = ids
            acc["user_data_64"] = ids % np.uint64(97)
            acc["user_data_32"] = ids % np.uint64(65521)
            acc["ledger"] = 1
            acc["code"] = 1 + ids % np.uint64(5)
            yield acc

    # transfers -----------------------------------------------------------

    def _draw(self, rng, k: int) -> np.ndarray:
        if self.cdf is None:
            return rng.integers(1, self.accounts + 1, k, dtype=np.uint64)
        return (np.searchsorted(self.cdf, rng.random(k)) + 1).clip(
            1, self.accounts).astype(np.uint64)

    def _roles(self, rng, seq: int):
        """(role, position in its chain, length of its chain) per event:
        chains and single events shuffled as whole units."""
        n = self.n
        lengths = []  # 2, 3, 4, 2, ...: every seed gets the same chains
        budget = int(round(self.share["linked"] * n))
        while budget >= 2:
            lengths.append(min(2 + len(lengths) % 3, budget))
            budget -= lengths[-1]
        n_chain = sum(lengths)
        n_pend = int(round(self.share["pending"] * n))
        n_settle = int(round(self.share["post_void"] * n)) if seq else 0
        n_single = n - n_chain
        unit_len = np.array(lengths + [1] * n_single, dtype=np.int64)
        unit_role = np.full(len(unit_len), SIMPLE)
        unit_role[: len(lengths)] = CHAIN
        unit_role[len(lengths): len(lengths) + n_pend] = PEND
        unit_role[len(lengths) + n_pend: len(lengths) + n_pend + n_settle] = SETTLE
        order = rng.permutation(len(unit_len))
        unit_len, unit_role = unit_len[order], unit_role[order]
        unit = np.repeat(np.arange(len(unit_len)), unit_len)
        pos = np.arange(n) - (np.cumsum(unit_len) - unit_len)[unit]
        return unit_role[unit], pos, unit_len[unit], unit

    def ids(self, session: int, seq: int) -> list:
        """The transfer ids of batch (session, seq): what the read-back asks for."""
        first = 1 + (seq * self.sessions + session) * self.n
        return list(range(first, first + self.n))

    def batch(self, session: int, seq: int) -> np.ndarray:
        n = self.n
        rng = self.rng(1, session, seq)
        t = np.zeros(n, dtype=TRANSFER)
        t["id_lo"] = np.array(self.ids(session, seq), dtype=np.uint64)
        dr, cr = self._draw(rng, n), self._draw(rng, n)
        t["debit_account_id_lo"] = dr
        t["credit_account_id_lo"] = np.where(
            cr == dr, dr % np.uint64(self.accounts) + np.uint64(1), cr)
        t["amount_lo"] = rng.integers(1, 1000, n)
        t["ledger"] = 1
        t["code"] = rng.integers(1, 5, n)
        t["user_data_64"] = rng.integers(1, 17, n)
        t["user_data_32"] = rng.integers(1, 17, n)
        t["user_data_128_lo"] = rng.integers(1, 1 << 62, n)
        t["user_data_128_hi"] = seq + 1
        role, pos, length, unit = self._roles(rng, seq)
        good = np.ones(n, dtype=bool)  # built to succeed

        # simple events that must fail, on rungs no commit order can move
        simple = role == SIMPLE
        order = rng.permutation(np.nonzero(simple)[0])
        q = _quota(seq, self.fail_share * len(order))
        same, nowhere, ledger2, zero = (order[i * q:(i + 1) * q] for i in range(4))
        t["credit_account_id_lo"][same] = t["debit_account_id_lo"][same]
        t["debit_account_id_lo"][nowhere] += np.uint64(self.accounts + 7)
        t["ledger"][ledger2] = 2
        t["amount_lo"][zero] = 0
        good[order[: 4 * q]] = False

        # linked chains, now and then with a link that fails
        in_chain = role == CHAIN
        t["flags"][in_chain & (pos < length - 1)] = LINKED
        if self.chain_fail_one_in:
            chains = rng.permutation(np.unique(unit[in_chain]))
            broken = chains[: _quota(seq, len(chains) / self.chain_fail_one_in)]
            breaks = in_chain & np.isin(unit, broken)
            t["amount_lo"][breaks & (pos == (unit % length))] = 0
            good &= ~breaks

        t["flags"][role == PEND] = PENDING

        # post or void what this session's previous batch left pending
        at = np.nonzero(role == SETTLE)[0]
        settled = np.zeros(0, dtype=np.uint64)
        if len(at):
            prev = self._meta(session, seq - 1)
            k = min(len(at), len(prev["pending_ids"]))
            at = at[:k]  # more slots than pendings: the rest stay simple
            j = np.arange(k)
            t["flags"][at] = np.where(j % 2 == 0, POST, VOID)
            t["pending_id_lo"][at] = prev["pending_ids"][:k]
            partial = j % 4 == 0
            t["amount_lo"][at] = np.where(
                partial, 1 + rng.integers(0, 1 << 30, k) % prev["pending_amounts"][:k], 0)
            for name in ("debit_account_id_lo", "credit_account_id_lo",
                         "ledger", "code", "user_data_32"):
                t[name][at] = 0
            q = _quota(seq, self.settle_fail_share * k / 3)
            miss = rng.permutation(at)
            nothing = miss[:q]
            never = miss[q: q + min(q, len(prev["plain_ids"]))]
            again = miss[2 * q: 2 * q + min(q, len(prev["settled_ids"]))]
            t["pending_id_lo"][nothing] = np.uint64(1 << 41) + t["id_lo"][nothing]
            t["pending_id_lo"][never] = prev["plain_ids"][: len(never)]
            t["pending_id_lo"][again] = prev["settled_ids"][: len(again)]
            t["amount_lo"][again] = 0
            failed = np.zeros(n, dtype=bool)
            failed[nothing] = failed[never] = failed[again] = True
            good &= ~failed
            settled = t["pending_id_lo"][at[~failed[at]]]

        pend_ok = (role == PEND) & good
        plain_ok = simple & good
        self.meta[(session, seq)] = {
            "pending_ids": t["id_lo"][pend_ok],
            "pending_amounts": t["amount_lo"][pend_ok],
            "plain_ids": t["id_lo"][plain_ok][:256],
            "settled_ids": settled,
        }
        self.meta.pop((session, seq - 3), None)
        return t

    def _meta(self, session: int, seq: int) -> dict:
        if (session, seq) not in self.meta:
            self.batch(session, seq)
        return self.meta[(session, seq)]
