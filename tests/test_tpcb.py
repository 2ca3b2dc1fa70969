"""TPC-B's debit-credit deployment (`tpcb_1m`, benchmarks/generators/tpcb.py)
at test_min size on the CPU.

The generator against the rules it states (one branch : ten tellers : a
block of accounts; the teller's own branch in 85% of the transactions;
every transaction one chain of three), and its traffic through the plain
reference, the serial oracle and the served state machine on the jax
backend (the route the chip takes: the C staging pass, then the exact
kernel on every batch): the same result codes, stored transfers and
balances from all three, TPC-B's consistency condition on the balances,
and the counters the deployment added against counts taken from the
batches themselves.
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.generators.tpcb import DELTA_MAX, Generator  # noqa: E402
from benchmarks.reference import LINKED, RESULT, Ledger  # noqa: E402

TINY = {"accounts": 1000, "batch": 64, "scale": 10, "tellers_per_branch": 10}
SESSIONS = 3
BATCHES = 30  # of the agreement run: under test_min's transfers_max


def load(*parts: str) -> dict:
    with open(os.path.join(REPO, "benchmarks", *parts)) as f:
        return json.load(f)


def traffic(**over) -> dict:
    return {**load("traffic", "debit_credit_sat.json"), "sessions": SESSIONS, **over}


def classes(gen: Generator, ids: np.ndarray) -> np.ndarray:
    """0 customer, 1 teller, 2 branch, 3 cash."""
    return np.searchsorted([gen.first_teller, gen.first_branch, gen.first_cash], ids, "right")


# --- the generator against the rules ------------------------------------------------


@pytest.mark.parametrize("config", [TINY, load("configs", "tpcb_1m.json")],
                         ids=["test_min", "tpcb_1m"])
def test_accounts_follow_the_scaling_rule(config):
    """Clause 4.2: per unit of scale one branch, ten tellers, a block of
    accounts; here also one cash account a branch."""
    gen = Generator(config, traffic(), 5)
    ids = np.concatenate([acc["id_lo"] for acc in gen.account_batches()])
    assert ids.tolist() == list(range(1, int(config["accounts"]) + 1))
    scale = int(config["scale"])
    assert np.bincount(classes(gen, ids)).tolist() == [
        scale * gen.per_branch, scale * int(config["tellers_per_branch"]), scale, scale]
    assert gen.per_branch == config.get("accounts_per_branch", 88)
    assert int(config["accounts"]) <= config.get("accounts_max", 1 << 10)


def test_accounts_that_do_not_divide_are_refused():
    with pytest.raises(ValueError):
        Generator({**TINY, "accounts": 1001}, traffic(), 5)


@pytest.mark.parametrize("n", [64, 63, 8190])
def test_every_transaction_is_one_chain_of_three(n):
    """Clause 1.2 as transfers: account, teller and branch each against the
    cash account of the TELLER's branch, |Delta| on all three, linked,
    linked, closed; what is left of a batch is simple transfers between
    customers."""
    gen = Generator({**TINY, "batch": n}, traffic(chain_fail_one_in=0), 7)
    t = gen.batch(1, 2)
    k = n // 3
    legs, rest = t[: 3 * k].reshape(k, 3), t[3 * k:]
    assert len(t) == n and len(rest) == n % 3
    assert (legs["flags"] == [LINKED, LINKED, 0]).all() and not rest["flags"].any()
    assert (legs["code"] == [1, 2, 3]).all() and (rest["code"] == 4).all()
    deposit = classes(gen, legs["debit_account_id_lo"][:, 0]) == 3
    row = np.where(deposit[:, None], legs["credit_account_id_lo"], legs["debit_account_id_lo"])
    cash = np.where(deposit[:, None], legs["debit_account_id_lo"], legs["credit_account_id_lo"])
    assert (classes(gen, row) == [0, 1, 2]).all() and (classes(gen, cash) == 3).all()
    branch = (row[:, 1] - gen.first_teller) // gen.tellers_per_branch
    assert (row[:, 2] == gen.first_branch + branch).all()
    assert (cash == (gen.first_cash + branch)[:, None]).all()
    assert (legs["amount_lo"] == legs["amount_lo"][:, :1]).all()
    assert legs["amount_lo"].min() >= 1 and legs["amount_lo"].max() <= DELTA_MAX
    # the History row's keys on every stored leg
    assert (legs["user_data_128_lo"] == row[:, :1]).all()
    assert (legs["user_data_64"] == row[:, 1:2]).all()
    assert (legs["user_data_32"] == (branch + 1)[:, None]).all()
    assert (classes(gen, rest["debit_account_id_lo"]) == 0).all()
    assert (classes(gen, rest["credit_account_id_lo"]) == 0).all()
    assert (rest["debit_account_id_lo"] != rest["credit_account_id_lo"]).all()
    assert t["id_lo"].tolist() == gen.ids(1, 2) and (t["ledger"] == 1).all()


@pytest.fixture(scope="module")
def drawn():
    """54,600 transactions of the deployment's own size: (account's branch,
    teller, deposit?, amount zero?) of each."""
    config = load("configs", "tpcb_1m.json")
    gen = Generator(config, traffic(sessions=16), 3_000_000_043)
    legs = np.concatenate([gen.batch(s, q) for s in range(4) for q in range(5)]).reshape(-1, 3)
    deposit = classes(gen, legs["debit_account_id_lo"][:, 0]) == 3
    account = np.where(deposit, legs["credit_account_id_lo"][:, 0],
                       legs["debit_account_id_lo"][:, 0])
    return {"gen": gen, "legs": legs, "deposit": deposit,
            "home": ((account - 1) // gen.per_branch).astype(np.int64),
            "teller": (legs["user_data_64"][:, 0] - gen.first_teller).astype(np.int64)}


def test_the_account_is_of_the_tellers_branch_in_85_percent(drawn):
    """Clause 5.3. Tolerance: 54,600 draws at p = 0.85 have a standard
    deviation of 0.15 points; one point is six of them."""
    gen = drawn["gen"]
    branch = drawn["teller"] // gen.tellers_per_branch
    at_home = drawn["home"] == branch
    assert abs(at_home.mean() - 0.85) < 0.01
    # the remote 15% fall evenly on the OTHER nine branches (1/9 each, +-2 points)
    away = (drawn["home"][~at_home] - branch[~at_home]) % gen.scale
    assert away.min() >= 1
    assert np.abs(np.bincount(away, minlength=gen.scale)[1:] / len(away) - 1 / 9).max() < 0.02


def test_tellers_and_signs_are_uniform(drawn):
    gen = drawn["gen"]
    per_teller = np.bincount(drawn["teller"], minlength=gen.tellers)
    assert len(per_teller) == 100 and per_teller.min() > 0.8 * len(drawn["teller"]) / 100
    assert abs(drawn["deposit"].mean() - 0.5) < 0.01
    amounts = drawn["legs"]["amount_lo"][:, 0]
    assert amounts.max() > 0.99 * DELTA_MAX and abs(amounts.mean() / DELTA_MAX - 0.5) < 0.01


def test_one_chain_in_200_is_built_to_roll_back(drawn):
    """2,730 / 200 = 13.65 a batch, so 68 in a session's first five, the
    same number for every seed; the zero amount at each of the three
    positions in turn."""
    zero = drawn["legs"]["amount_lo"] == 0
    assert (zero.sum(axis=1) <= 1).all()
    assert zero.sum() == 4 * int(5 * 2730 / 200)
    assert zero.any(axis=0).all()


def test_same_seed_same_bytes():
    a, b, c = (Generator(TINY, traffic(), seed) for seed in (7, 7, 8))
    for seq in range(3):
        x, y, z = a.batch(1, seq), b.batch(1, seq), c.batch(1, seq)
        assert x.tobytes() == y.tobytes() and x.tobytes() != z.tobytes()
        assert x.tobytes() != a.batch(2, seq).tobytes()
        assert sorted(x["flags"].tolist()) == sorted(z["flags"].tolist())
        assert (x["amount_lo"] == 0).sum() == (z["amount_lo"] == 0).sum()


# --- reference, oracle and the served state machine on the same traffic -------------


def no_timestamp(recs):
    out = np.array(recs)
    out["timestamp"] = 0
    return out


@pytest.fixture(scope="module", params=[11, 3_000_000_019], ids=lambda s: f"seed{s}")
def replayed(request):
    """BATCHES batches of three sessions, interleaved in a seeded order,
    through all three; the tracer on around the served state machine."""
    from tigerbeetle_tpu import tracer, types
    from tigerbeetle_tpu.constants import TEST_MIN
    from tigerbeetle_tpu.models import oracle as om
    from tigerbeetle_tpu.models.state_machine import StateMachine

    seed = request.param
    gen = Generator(TINY, traffic(chain_fail_one_in=8), seed)
    ledger, o = Ledger(TINY["accounts"]), om.Oracle()
    was = tracer.enabled()
    tracer.enable()
    tracer.reset()
    try:
        sm = StateMachine(TEST_MIN, backend="jax")
        for acc in gen.account_batches():
            ts = o.prepare("create_accounts", len(acc))
            assert o.create_accounts([om.account_from_numpy(r) for r in acc], ts) == []
            assert len(ledger.create_accounts(acc)) == 0
            assert len(sm.create_accounts(acc.view(types.ACCOUNT_DTYPE))) == 0
        rng = np.random.default_rng(seed)
        next_seq = [0] * SESSIONS
        out = {"gen": gen, "batches": [], "codes": {"reference": [], "oracle": [], "served": []},
               "stored": {"reference": [], "oracle": [], "served": []}}
        zeros = lambda ids: np.zeros(len(ids), np.uint64)  # noqa: E731
        for _ in range(BATCHES):
            s = int(rng.integers(0, SESSIONS))
            events = gen.batch(s, next_seq[s])
            next_seq[s] += 1
            out["batches"].append(events)
            ts = o.prepare("create_transfers", len(events))
            out["codes"]["oracle"].append(np.array(o.create_transfers(
                [om.transfer_from_numpy(r) for r in events], ts), dtype=RESULT).reshape(-1))
            got, stored = ledger.create_transfers(events)
            out["codes"]["reference"].append(got)
            out["stored"]["reference"].append(stored)
            out["codes"]["served"].append(sm.create_transfers(events.view(types.TRANSFER_DTYPE)))
            found = o.lookup_transfers([int(v) for v in events["id_lo"]])
            out["stored"]["oracle"].append(no_timestamp(types.batch(
                [om.transfer_to_numpy(t) for t in found], types.TRANSFER_DTYPE)))
            out["stored"]["served"].append(no_timestamp(
                sm.lookup_transfers(events["id_lo"], zeros(events))))
        ids = np.arange(1, TINY["accounts"] + 1, dtype=np.uint64)
        out["accounts"] = {
            "reference": ledger.lookup_accounts(ids),
            "oracle": no_timestamp(types.batch(
                [om.account_to_numpy(a) for a in o.lookup_accounts(ids.tolist())],
                types.ACCOUNT_DTYPE)),
            "served": no_timestamp(sm.lookup_accounts(ids, zeros(ids))),
        }
        out["stats"] = dict(sm.stats)
        out["snapshot"] = tracer.snapshot()
        out["trace_events"] = tracer.trace_events()
        return out
    finally:
        tracer.reset()
        if not was:
            tracer.disable()


@pytest.mark.parametrize("who", ["oracle", "served"])
@pytest.mark.parametrize("what", ["codes", "stored", "accounts"])
def test_same_answers_as_the_reference(replayed, what, who):
    want, got = replayed[what]["reference"], replayed[what][who]
    if what == "accounts":
        want, got = [want], [got]
    assert len(want) == len(got) == (1 if what == "accounts" else BATCHES)
    for w, g in zip(want, got):
        assert g.tobytes() == w.tobytes()


def test_a_chain_rolled_back_among_them(replayed):
    """The comparison had something to compare: a link with amount zero
    (18) and the other two of its chain (1), and nothing of it stored."""
    codes = np.concatenate(replayed["codes"]["served"])
    assert set(codes["result"].tolist()) == {1, 18}
    assert (codes["result"] == 1).sum() == 2 * (codes["result"] == 18).sum() > 0
    stored = sum(len(s) for s in replayed["stored"]["served"])
    assert stored == BATCHES * TINY["batch"] - len(codes)


def test_every_batch_took_the_exact_kernel(replayed):
    stats = replayed["stats"]
    assert stats["exact_batches"] == BATCHES
    assert not stats.get("fast_batches") and not stats.get("serial_batches")
    assert not stats.get("bail_batches")
    routes = {k: v["count"] for k, v in replayed["snapshot"].items()
              if k.startswith("sm.route.")}
    assert routes == {"sm.route.exact_batches": BATCHES}


def net(accounts: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Credits less debits, posted, of the accounts with these ids."""
    rows = accounts[ids - 1]
    return rows["credits_posted_lo"].astype(np.int64) - rows["debits_posted_lo"].astype(np.int64)


def test_the_consistency_condition_holds(replayed):
    """Clause 2.3: per branch, the branch's balance = the sum of its
    tellers' balances = the sum of what its tellers moved on accounts
    (here: minus a third of its cash account's balance, which took the
    other side of all three)."""
    gen, accounts = replayed["gen"], replayed["accounts"]["served"]
    assert (accounts["id_lo"] == np.arange(1, TINY["accounts"] + 1)).all()
    branches = np.arange(gen.scale)
    branch = net(accounts, gen.first_branch + branches)
    tellers = net(accounts, gen.first_teller + np.arange(gen.tellers)).reshape(
        gen.scale, gen.tellers_per_branch).sum(axis=1)
    cash = net(accounts, gen.first_cash + branches)
    assert branch.any() and (branch == tellers).all() and (3 * branch == -cash).all()
    # and the whole ledger balances: the customers hold what the cash accounts lack
    assert net(accounts, np.arange(1, gen.first_cash + gen.scale)).sum() == 0
    assert not accounts["debits_pending_lo"].any() and not accounts["credits_pending_lo"].any()


def test_the_counters_count_what_the_batches_hold(replayed):
    """chains, rolled-back chains, distinct slots and the longest slot
    segment, each summed over the batches, from the batches themselves."""
    chains = rolled = touched = hottest = 0
    for t in replayed["batches"]:
        linked = (t["flags"] & LINKED) != 0
        head = np.ones(len(t), dtype=bool)
        head[1:] = ~linked[:-1]
        chain = np.cumsum(head) - 1
        chains += int((head & linked).sum())
        rolled += len(np.unique(chain[(t["amount_lo"] == 0) & (linked | ~head)]))
        postings = np.concatenate([t["debit_account_id_lo"], t["credit_account_id_lo"]])
        per_account = np.unique(postings, return_counts=True)[1]
        touched += len(per_account)
        hottest += int(per_account.max())
    count = {k: v["count"] for k, v in replayed["snapshot"].items()}
    assert chains == BATCHES * (TINY["batch"] // 3) and rolled > 0
    assert count["sm.exact.chains"] == chains
    assert count["sm.exact.chains_rolled_back"] == rolled
    assert count["sm.exact.slots_touched"] == touched
    assert count["sm.exact.slot_postings_max"] == hottest
    assert count["sm.exact.sweeps"] >= BATCHES


def test_the_plan_span_nests_in_the_stage_span(replayed):
    """`sm.ct.plan` is a part of `sm.ct.stage`, once a batch: a metric over
    the stage keeps measuring what it measured."""
    events = replayed["trace_events"]
    plans = [(tid, t0, t1) for ev, _, tid, t0, t1 in events if ev == "sm.ct.plan"]
    stages = [(tid, t0, t1) for ev, _, tid, t0, t1 in events if ev == "sm.ct.stage"]
    assert len(plans) == BATCHES == replayed["snapshot"]["sm.ct.plan"]["count"]
    for tid, t0, t1 in plans:
        assert any(s_tid == tid and s0 <= t0 and t1 <= s1 for s_tid, s0, s1 in stages)


def test_the_counters_cost_nothing_with_the_tracer_off(monkeypatch):
    """Tracer off: the segment statistics are not computed at all."""
    from tigerbeetle_tpu import tracer, types
    from tigerbeetle_tpu.constants import TEST_MIN
    from tigerbeetle_tpu.models.state_machine import StateMachine
    from tigerbeetle_tpu.ops import commit_exact

    assert not tracer.enabled()
    gen = Generator(TINY, traffic(), 13)
    sm = StateMachine(TEST_MIN, backend="jax")
    for acc in gen.account_batches():
        assert len(sm.create_accounts(acc.view(types.ACCOUNT_DTYPE))) == 0
    calls, before = [], tracer.snapshot()
    monkeypatch.setattr(commit_exact, "plan_slot_segments", lambda *a: calls.append(a))
    sm.create_transfers(gen.batch(0, 0).view(types.TRANSFER_DTYPE))
    assert sm.stats["exact_batches"] == 1 and calls == [] and tracer.snapshot() == before


@pytest.mark.parametrize("slots,want", [
    ([[5, 5, 7], [9, 5, 5]], (3, 4)),  # slot 5 four times, 7 and 9 once
    ([[3, -1, 3], [4, 4, -1]], (2, 2)),  # postings without a slot are no segment
    ([[-1, -1], [-1, -1]], (0, 0)),
])
def test_plan_slot_segments(slots, want):
    from tigerbeetle_tpu.ops import commit_exact

    dr, cr = (np.array(s, dtype=np.int32) for s in slots)
    n = len(dr)
    none = np.full(n, -1, dtype=np.int32)
    plan = commit_exact.build_sort_plan(
        np.zeros(n, np.uint32), dr, cr, none, none, np.arange(n, dtype=np.int32),
        np.full(n, n, dtype=np.int32), 16)
    posted = int((dr >= 0).sum() + (cr >= 0).sum())
    assert commit_exact.plan_slot_segments(plan, posted) == want
