"""Long lines of dependent events on one account, on the CPU: the exact
kernel's line walk (ops/commit_exact.py `LinePlan`) against the serial
oracle (models/oracle.py).

SmallBank's own generator at full 8190-event batches and high contention
(a hotspot of 100 customers, SmallBank's HOTSPOT_FIXED_SIZE, and of 10:
lines of ~120 and ~1,000 dependent events a batch, more than the sweeps'
64 can settle) commits every batch on the device with one sweep and no
bail, byte for byte the oracle. A line on one account of any length
from 64 on takes one sweep as well. The batches the sweeps are sure to
settle keep the variant without the walk: lines shallower than their 64
(the accepted hotspot of 1,000), and the other accepted deployments'
linked chains and post/void, where a line past the budget still bails.
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.generators.smallbank import Generator  # noqa: E402
from benchmarks.reference import RESULT  # noqa: E402

SEED = 3_000_004_101
ACCOUNTS = 40_041  # 20 bank accounts, 20,010 customers: five load batches
MIX = {100: 150, 10: 20}  # mix batches replayed, by hotspot
CHECKED = {100: 40, 10: 20}  # of them, the first held against the oracle


def load(*parts: str) -> dict:
    with open(os.path.join(REPO, "benchmarks", *parts)) as f:
        return json.load(f)


def no_timestamp(recs):
    out = np.array(recs)
    out["timestamp"] = 0
    return out


def _counts(snap: dict, name: str) -> int:
    return snap.get(name, {"count": 0})["count"]


def _oracle_batch(o, om, events):
    ts = o.prepare("create_transfers", len(events))
    return np.array(o.create_transfers(
        [om.transfer_from_numpy(r) for r in events], ts), dtype=RESULT).reshape(-1)


def _stored(sm, o, om, types, events):
    ids = events["id_lo"]
    served = no_timestamp(sm.lookup_transfers(ids, np.zeros(len(ids), np.uint64)))
    oracle = no_timestamp(types.batch(
        [om.transfer_to_numpy(t) for t in o.lookup_transfers([int(v) for v in ids])],
        types.TRANSFER_DTYPE))
    return served, oracle


def _accounts(sm, o, om, types, count):
    ids = np.arange(1, count + 1, dtype=np.uint64)
    served = no_timestamp(sm.lookup_accounts(ids, np.zeros(count, np.uint64)))
    oracle = no_timestamp(types.batch(
        [om.account_to_numpy(a) for a in o.lookup_accounts(ids.tolist())],
        types.ACCOUNT_DTYPE))
    return served, oracle


def test_the_high_contention_deployment_states_its_traffics_skew():
    """`smallbank_1m_hot100` is `smallbank_1m` at a hotspot of 100: every
    number the harness reads is the accepted deployment's, and the skew it
    states is the one its traffic file gives the generator."""
    config, accepted = load("configs", "smallbank_1m_hot100.json"), load(
        "configs", "smallbank_1m.json")
    traffic, base = load("traffic", "hotspot100_sat.json"), load("traffic", "hotspot_sat.json")
    skew = ("hotspot_customers", "hotspot_share")
    assert {k: config[k] for k in skew} == {k: traffic[k] for k in skew}
    assert config["hotspot_customers"] == 100
    prose = {"source", "source_notes", "deployment", "assumed", *skew}
    assert {k: v for k, v in config.items() if k not in prose} == {
        k: v for k, v in accepted.items() if k not in prose}
    assert {k: v for k, v in traffic.items() if not k.startswith("why")} == {
        **{k: v for k, v in base.items() if not k.startswith("why")}, "hotspot_customers": 100}


@pytest.fixture(scope="module", params=[100, 10], ids=lambda h: f"hotspot{h}")
def replayed(request):
    """The cell's generator and traffic with `hotspot_customers` changed:
    the load, then MIX[hot] mix batches of 16 sessions in turn through
    the served state machine on the jax backend, the first CHECKED[hot]
    of them through the oracle too; the tracer on."""
    from tigerbeetle_tpu import tracer, types
    from tigerbeetle_tpu.constants import DEVELOPMENT
    from tigerbeetle_tpu.models import oracle as om
    from tigerbeetle_tpu.models.state_machine import StateMachine

    hot = request.param
    if hot == 100:
        config, traffic = load("configs", "smallbank_1m_hot100.json"), load(
            "traffic", "hotspot100_sat.json")
    else:
        config = load("configs", "smallbank_1m.json")
        traffic = {**load("traffic", "hotspot_sat.json"), "hotspot_customers": hot}
    gen = Generator({**config, "accounts": ACCOUNTS}, traffic, SEED)
    sessions = int(traffic["sessions"])
    was = tracer.enabled()
    tracer.enable()
    tracer.reset()
    try:
        sm, o = StateMachine(DEVELOPMENT, backend="jax"), om.Oracle()
        for acc in gen.account_batches():
            assert len(sm.create_accounts(acc.view(types.ACCOUNT_DTYPE))) == 0
            ts = o.prepare("create_accounts", len(acc))
            assert o.create_accounts([om.account_from_numpy(r) for r in acc], ts) == []
        out = {"codes": ([], []), "stored": ([], []), "sweeps": [], "line_steps": [],
               "bails": []}
        total = gen.load_batches + MIX[hot]
        checked = gen.load_batches + CHECKED[hot]
        for k in range(total):
            events = gen.batch(k % sessions, k // sessions)
            before = tracer.snapshot()
            served = sm.create_transfers(events.view(types.TRANSFER_DTYPE))
            after = tracer.snapshot()
            if k >= gen.load_batches:
                for name in ("sweeps", "line_steps"):
                    out[name].append(_counts(after, f"sm.exact.{name}")
                                     - _counts(before, f"sm.exact.{name}"))
                out["bails"].append(_counts(after, "sm.route.bail_batches")
                                    - _counts(before, "sm.route.bail_batches"))
            if k < checked:
                out["codes"][0].append(np.asarray(served))
                out["codes"][1].append(_oracle_batch(o, om, events))
                for side, recs in zip(out["stored"], _stored(sm, o, om, types, events)):
                    side.append(recs)
            if k == checked - 1:
                out["accounts"] = _accounts(sm, o, om, types, ACCOUNTS)
        out["stats"] = dict(sm.stats)
        out["total"] = total
        return out
    finally:
        tracer.reset()
        if not was:
            tracer.disable()


def test_every_batch_commits_on_the_device_in_one_sweep(replayed):
    """No bail (the sweeps alone bail at 64, on every batch at a hotspot of
    10), and one full-batch pass a batch: the walk settled the lines."""
    assert not any(replayed["bails"]) and not replayed["stats"].get("bail_batches")
    assert not replayed["stats"].get("serial_batches")
    assert replayed["stats"]["exact_batches"] + replayed["stats"].get("fast_batches", 0) \
        == replayed["total"]
    assert set(replayed["sweeps"]) == {1}


def test_the_walk_is_as_long_as_the_deepest_chain(replayed):
    """Every mix batch walked; a walk is at least as long as the longest
    line of dependent events (~120 a batch at 100 hot customers)."""
    steps = np.array(replayed["line_steps"])
    assert (steps > 100).all(), steps


@pytest.mark.parametrize("what", ["codes", "stored", "accounts"])
def test_same_answers_as_the_oracle(replayed, what):
    served, oracle = replayed[what]
    if what == "accounts":
        served, oracle = [served], [oracle]
    assert len(served) == len(oracle) > 0
    for s, o in zip(served, oracle):
        assert s.tobytes() == o.tobytes()


def test_the_accepted_hotspot_keeps_the_sweeps():
    """At the accepted cells' hotspot of 1,000 customers the walk is some
    35 levels deep, inside the sweeps' budget: no batch is walked, and the
    sweeps settle each (9-10 of them on the chip) with no bail, as they did
    before the walk existed."""
    from tigerbeetle_tpu import tracer, types
    from tigerbeetle_tpu.constants import DEVELOPMENT
    from tigerbeetle_tpu.models.state_machine import StateMachine

    traffic = load("traffic", "hotspot_sat.json")
    assert traffic["hotspot_customers"] == 1000
    gen = Generator({**load("configs", "smallbank_1m.json"), "accounts": ACCOUNTS}, traffic, SEED)
    sessions = int(traffic["sessions"])
    was = tracer.enabled()
    tracer.enable()
    tracer.reset()
    try:
        sm = StateMachine(DEVELOPMENT, backend="jax")
        for acc in gen.account_batches():
            assert len(sm.create_accounts(acc.view(types.ACCOUNT_DTYPE))) == 0
        for k in range(gen.load_batches):
            sm.create_transfers(gen.batch(k % sessions, k // sessions).view(types.TRANSFER_DTYPE))
        before = tracer.snapshot()
        mix = 16
        for k in range(gen.load_batches, gen.load_batches + mix):
            sm.create_transfers(gen.batch(k % sessions, k // sessions).view(types.TRANSFER_DTYPE))
        after = tracer.snapshot()
    finally:
        tracer.reset()
        if not was:
            tracer.disable()

    def moved(name):
        return _counts(after, name) - _counts(before, name)

    assert moved("sm.route.exact_batches") == mix and moved("sm.route.bail_batches") == 0
    assert moved("sm.exact.line_steps") == 0
    assert mix < moved("sm.exact.sweeps") <= 64 * mix


# --- one account, one line of any length --------------------------------------


def _one_account_line(length: int, linked_pair: bool = False):
    """Five accounts, of which account 1 may not be overdrawn, and a batch
    whose line on account 1 is `length` dependent events: debits refused
    where it does not hold them and balancing debits clamped to what it
    holds, with credits from accounts 2-4 between them (funded outside the
    line, no limit: they read nothing), two for every three debits. With
    `linked_pair`, a linked pair from account 2 to 3 and back leads."""
    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.flags import AccountFlags, TransferFlags

    acc = np.zeros(5, dtype=types.ACCOUNT_DTYPE)
    acc["id_lo"] = np.arange(1, 6)
    acc["ledger"] = 1
    acc["code"] = 10
    acc["flags"][0] = int(AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS)
    rng = np.random.default_rng(length)
    credits = 2 * length // 3
    # debit, balancing debit, credit
    kind = rng.permutation(np.concatenate([
        rng.choice(2, length, p=[2 / 3, 1 / 3]), np.full(credits, 2),
    ]))
    total = length + credits
    t = np.zeros(total, dtype=types.TRANSFER_DTYPE)
    t["id_lo"] = 1000 + np.arange(total)
    t["ledger"] = 1
    t["code"] = 7
    t["amount_lo"] = rng.integers(1, 100, total)
    t["debit_account_id_lo"] = np.where(kind == 2, rng.integers(2, 5, total), 1)
    t["credit_account_id_lo"] = np.where(kind == 2, 1, 5)
    t["flags"] = np.where(kind == 1, int(TransferFlags.BALANCING_DEBIT), 0)
    t["amount_lo"][kind == 1] *= 2
    if linked_pair:
        pair = np.zeros(2, dtype=types.TRANSFER_DTYPE)
        pair["id_lo"] = [998, 999]
        pair["ledger"] = 1
        pair["code"] = 7
        pair["amount_lo"] = 5
        pair["debit_account_id_lo"] = [2, 3]
        pair["credit_account_id_lo"] = [3, 2]
        pair["flags"] = [int(TransferFlags.LINKED), 0]
        t = np.concatenate([pair, t])
    return acc, t, kind


def _replay_one(acc, t):
    """The batch through the served state machine (jax backend, tracer on)
    and the oracle: (served, oracle's codes, state machine, oracle, tracer
    snapshot)."""
    from tigerbeetle_tpu import tracer
    from tigerbeetle_tpu.constants import DEVELOPMENT
    from tigerbeetle_tpu.models import oracle as om
    from tigerbeetle_tpu.models.state_machine import StateMachine

    was = tracer.enabled()
    tracer.enable()
    tracer.reset()
    try:
        sm, o = StateMachine(DEVELOPMENT, backend="jax"), om.Oracle()
        assert len(sm.create_accounts(acc)) == 0
        assert o.create_accounts([om.account_from_numpy(r) for r in acc],
                                 o.prepare("create_accounts", len(acc))) == []
        served = sm.create_transfers(t)
        snap = tracer.snapshot()
    finally:
        tracer.reset()
        if not was:
            tracer.disable()
    return served, _oracle_batch(o, om, t), sm, o, snap


def _same_as_the_oracle(served, oracle, sm, o, t):
    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.models import oracle as om

    assert np.asarray(served).tobytes() == oracle.tobytes()
    s_stored, o_stored = _stored(sm, o, om, types, t)
    assert s_stored.tobytes() == o_stored.tobytes()
    s_acc, o_acc = _accounts(sm, o, om, types, 5)
    assert s_acc.tobytes() == o_acc.tobytes()
    return s_stored


@pytest.mark.parametrize("length", [64, 256, 1024, 4096])
def test_a_line_on_one_account_takes_one_sweep(length):
    """A line of `length` dependent events on account 1: byte for byte the
    oracle, in one full-batch pass whatever the length (64, the sweeps'
    own budget, is the shortest line the walk takes)."""
    from tigerbeetle_tpu.ops import commit_exact

    acc, t, kind = _one_account_line(length)
    served, oracle, sm, o, snap = _replay_one(acc, t)
    s_stored = _same_as_the_oracle(served, oracle, sm, o, t)
    # the line did refuse, clamp and credit
    assert (oracle["result"] == 54).any() and (kind == 2).any()
    asked = dict(zip(t["id_lo"].tolist(), t["amount_lo"].tolist()))
    assert any(r["amount_lo"] < asked[int(r["id_lo"])] for r in s_stored)  # a clamp
    assert sm.stats["exact_batches"] == 1 and not sm.stats.get("bail_batches")
    assert _counts(snap, "sm.exact.sweeps") == 1
    # a step per debit (the events that read the line): they share the one
    # line, so each is a level of its own; the credits read no line
    assert _counts(snap, "sm.exact.line_steps") == length >= commit_exact.MAX_SWEEPS


def test_a_line_shallower_than_the_sweeps_budget_is_not_walked():
    """A line of 63 dependent events: the sweeps settle at least a level
    each, so they are sure to settle it within their 64, and the walk is
    not taken. Byte for byte the oracle, no bail."""
    acc, t, _ = _one_account_line(63)
    served, oracle, sm, o, snap = _replay_one(acc, t)
    _same_as_the_oracle(served, oracle, sm, o, t)
    assert sm.stats["exact_batches"] == 1 and not sm.stats.get("bail_batches")
    assert _counts(snap, "sm.exact.line_steps") == 0
    assert 1 < _counts(snap, "sm.exact.sweeps") <= 64


def test_a_long_line_behind_a_linked_pair_bails_and_is_answered_serially():
    """What the walk does not take yet: a batch with a linked chain (or a
    post/void, or on the sharded kernels) settles its lines by the sweeps
    alone. A line of 4,096 dependent events outruns their 64: the kernel
    bails and the serial path answers, byte for byte the oracle."""
    acc, t, _ = _one_account_line(4096, linked_pair=True)
    served, oracle, sm, o, snap = _replay_one(acc, t)
    _same_as_the_oracle(served, oracle, sm, o, t)
    assert sm.stats.get("bail_batches") == 1
    assert _counts(snap, "sm.route.bail_batches") == 1
    assert _counts(snap, "sm.exact.line_steps") == 0


# --- the batches that keep the variant without the walk ------------------------


def _mix_batches(generator, config, traffic, count):
    gen = generator(config, traffic, SEED)
    sessions = int(traffic["sessions"])
    return gen, [gen.batch(k % sessions, k // sessions) for k in range(count)]


@pytest.mark.parametrize("cell", ["settlement", "tpcb"])
def test_chains_and_post_void_keep_the_sweeps(cell, monkeypatch):
    """Settlement's two-phase mix (post/void, chains) and TPC-B's chains of
    three take the variant without the walk: their kernel is called with
    no line plan, the walk counts no step, and each batch takes the one
    sweep it took before the walk existed (`exact_sweeps_per_batch` 1.0
    in both cells on the chip)."""
    from tigerbeetle_tpu import tracer, types
    from tigerbeetle_tpu.constants import TEST_MIN
    from tigerbeetle_tpu.models.state_machine import StateMachine
    from tigerbeetle_tpu.ops import commit as commit_ops

    if cell == "settlement":
        from benchmarks.generators.ledger_mix import Generator as Mix

        config = {"accounts": 1000, "batch": 64}
        traffic = {**load("traffic", "two_phase_sat.json"), "sessions": 2}
    else:
        from benchmarks.generators.tpcb import Generator as Mix

        config = {"accounts": 1000, "batch": 64, "scale": 10, "tellers_per_branch": 10}
        traffic = {**load("traffic", "debit_credit_sat.json"), "sessions": 2,
                   "chain_fail_one_in": 8}
    gen, batches = _mix_batches(Mix, config, traffic, 8)
    given, sweeps = [], []
    real = commit_ops.create_transfers_exact

    def spy(*args, lines=None, **kw):
        out = real(*args, lines=lines, **kw)
        given.append(lines)
        sweeps.append(int(out[-1]))
        return out

    monkeypatch.setattr(commit_ops, "create_transfers_exact", spy)
    was = tracer.enabled()
    tracer.enable()
    tracer.reset()
    try:
        sm = StateMachine(TEST_MIN, backend="jax")
        for acc in gen.account_batches():
            assert len(sm.create_accounts(acc.view(types.ACCOUNT_DTYPE))) == 0
        for events in batches:
            sm.create_transfers(events.view(types.TRANSFER_DTYPE))
        snap = tracer.snapshot()
    finally:
        tracer.reset()
        if not was:
            tracer.disable()
    assert given and all(x is None for x in given)
    assert sweeps == [1] * len(given)
    assert _counts(snap, "sm.exact.line_steps") == 0
    assert _counts(snap, "sm.route.exact_batches") == len(given)


# --- the walk's plan ------------------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_line_walk_native_and_python_agree(seed, monkeypatch):
    """The C walk and the Python loop give the same order, places,
    predecessors and level sizes; each walked event is one level above the
    last walked event on either of its lines, and follows it."""
    from tigerbeetle_tpu import native
    from tigerbeetle_tpu.ops import commit_exact

    rng = np.random.default_rng(seed)
    n, nseg = 3000, 200
    seg = rng.integers(-1, nseg, 2 * n).astype(np.int32)
    twice = rng.integers(0, n, 20)
    seg[twice] = seg[n + twice]  # events on one account twice (refused, they move nothing)
    reads = rng.random(2 * n) < 0.4
    none = 2 * (n + 512)
    got = commit_exact._line_walk(reads, seg, nseg, none)
    monkeypatch.setattr(native, "hostops", lambda: None)
    slow = commit_exact._line_walk(reads, seg, nseg, none)
    assert got[0] == slow[0] and all(np.array_equal(x, y) for x, y in zip(got[1:], slow[1:]))
    depth, order, at, pred, sizes = got
    walked = reads[:n] | reads[n:]
    assert (at >= 0).tolist() == walked.tolist() and sizes.sum() == walked.sum()
    read = {g for r, g in zip(reads, seg) if r and g >= 0}
    level, last = {}, {}
    for e in np.flatnonzero(walked):
        on = [s for s in (0, 1) if seg[e + s * n] in read]
        level[e] = 1 + max([level[last[seg[e + s * n]][0]] for s in on
                            if seg[e + s * n] in last], default=0)
        for s in (0, 1):
            want = -1 if s not in on else (
                none if seg[e + s * n] not in last
                else 2 * at[last[seg[e + s * n]][0]] + last[seg[e + s * n]][1])
            assert pred[2 * at[e] + s] == want
        for s in on:
            last[seg[e + s * n]] = (e, s)
    assert order[: walked.sum()].tolist() == sorted(level, key=lambda e: (level[e], e))
    assert depth == max(level.values())
