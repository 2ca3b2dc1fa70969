"""Reads beside writes (PR 37): SmallBank's Balance as `lookup_accounts`
traffic (`traffic/hotspot_balance_sat.json`), the reference answering a
read at its place in the commit order, and `run.compare` judging it
there. On the CPU:

- the generator's requests are a pure function of (seed, session, seq),
  and `hotspot_sat`'s batches are to the byte what the parent commit's
  generator gave (`data/hotspot_sat_golden.json`, written by running this
  file as a script in the parent's tree);
- the reference's `lookup_accounts` against `models/oracle.py` in a replay
  with reads between the writes: ids named twice, ids that name no
  account, customers read before their load;
- the same requests replayed in SESSION order give other rows, so the
  order is what is judged; `run.compare` reads 0 on what a sound server
  would have answered and counts a row altered, a read answered as of one
  write too early, and a request never answered;
- the cell through the whole of `run.run_cell` at test_min size (its
  control `stale_reads` is test_faults.py's).

    python -m pytest benchmarks/tests/test_reads.py -q -p no:cacheprovider
    python3 benchmarks/tests/test_reads.py <root of a tree> > data/hotspot_sat_golden.json
"""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
GOLDEN = os.path.join(HERE, "data", "hotspot_sat_golden.json")
sys.path.insert(0, REPO)

from test_smallbank import SESSIONS, in_commit_order  # noqa: E402

CELL = "smallbank_1m.hotspot_balance_sat"
SEEDS = (11, 3_000_000_019)
# (session, batch) of the digests: the load's first and last batches, the mix's first, later ones
PLACES = {"tiny": [(0, 0), (2, 5), (1, 6), (0, 40)], "full": [(0, 0), (4, 15), (5, 15), (3, 40)]}


def load(*parts: str) -> dict:
    with open(os.path.join(REPO, "benchmarks", *parts)) as f:
        return json.load(f)


def sizes(root: str = REPO) -> dict:
    with open(os.path.join(root, "benchmarks", "configs", "smallbank_1m.json")) as f:
        full = json.load(f)
    return {"tiny": {**full, "accounts": 1000, "batch": 64}, "full": full}


def digests(root: str) -> dict:
    """{"<size>.<seed>": sha256 of `hotspot_sat`'s batches at PLACES} by the
    generator of the tree at `root`."""
    sys.path.insert(0, root)
    from benchmarks.generators.smallbank import Generator

    with open(os.path.join(root, "benchmarks", "traffic", "hotspot_sat.json")) as f:
        traffic = json.load(f)
    out = {}
    for size, config in sizes(root).items():
        for seed in SEEDS:
            gen = Generator(config, traffic, seed)
            h = hashlib.sha256()
            for s, k in PLACES[size]:
                h.update(gen.batch(s, k).tobytes())
            out[f"{size}.{seed}"] = h.hexdigest()
    return out


def generators(size: str, seed: int, **over):
    """(with Balance, without): the two traffic files over one configuration."""
    from benchmarks.generators.smallbank import Generator

    config = sizes()[size]
    return (Generator(config, {**load("traffic", "hotspot_balance_sat.json"), **over}, seed),
            Generator(config, {**load("traffic", "hotspot_sat.json"), **over}, seed))


def test_hotspot_sat_sends_the_bytes_it_sent_before():
    with open(GOLDEN) as f:
        golden = json.load(f)
    assert digests(REPO) == golden and len(golden) == 4


def test_the_two_traffic_files_differ_by_the_balance_alone():
    with_balance, without = (load("traffic", n + ".json")
                             for n in ("hotspot_balance_sat", "hotspot_sat"))
    words = lambda t: {k: v for k, v in t.items() if not k.startswith("why")}  # noqa: E731
    weights = {**without["weights"], "balance": without["weights_left_out"]["balance"]}
    assert words(with_balance) == {**{k: v for k, v in words(without).items()
                                      if k != "weights_left_out"}, "weights": weights}
    assert sum(weights.values()) == 100 and weights["balance"] == 15


@pytest.mark.parametrize("size", ["tiny", "full"])
def test_a_session_alternates_the_siblings_batches_with_one_read(size):
    reads, plain = generators(size, 7)
    again, other = generators(size, 7)[0], generators(size, 8)[0]
    for s, k in PLACES[size]:
        operation, body = reads.request(s, 2 * k)
        assert operation == "create_transfers"
        assert body.tobytes() == plain.batch(s, k).tobytes()  # same seed, same bytes
        assert plain.request(s, k)[1].tobytes() == body.tobytes()  # without Balance: as it was
        assert plain.request(s, k)[0] == "create_transfers"
        operation, ids = reads.request(s, 2 * k + 1)
        assert operation == "lookup_accounts" and ids.dtype.itemsize == 16
        assert ids.tobytes() == again.request(s, 2 * k + 1)[1].tobytes()  # a pure function
        assert ids.tobytes() != other.request(s, 2 * k + 1)[1].tobytes()
        assert len(ids) == len(other.request(s, 2 * k + 1)[1])  # every seed the same sizes
        # customer by customer: savings id, then checking id
        assert not ids["hi"].any()
        assert (ids["lo"][1::2] - ids["lo"][0::2] == reads.customers).all()
        assert ids["lo"][0::2].min() >= reads.first_savings
        assert ids["lo"][0::2].max() < reads.first_checking
    if size == "full":
        # floor(6,962 transactions x 15 / 85) customers; a 39 KB body, a 314 KB reply
        assert sum(reads.count) == 6962 and reads.balances == 1228
        assert len(ids) == 2456 and ids.nbytes == 39296 and len(ids) * 128 == 314368
        hot = (ids["lo"][0::2] - reads.first_savings < reads.hot).mean()
        assert 0.86 < hot < 0.94  # the same hotspot rule
        assert len(np.unique(ids["lo"])) < len(ids)  # a hot customer is named more than once


def fresh_ledger(gen):
    from benchmarks.reference import Ledger

    ledger = Ledger(gen.accounts)
    for acc in gen.account_batches():
        assert len(ledger.create_accounts(acc)) == 0
    return ledger


def no_timestamp(recs):
    out = np.array(recs)
    out["timestamp"] = 0
    return out


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_reads_what_the_oracle_reads_between_the_writes(seed):
    from benchmarks.reference import ID, RESULT
    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.models import oracle as om

    gen, _ = generators("tiny", seed, sessions=SESSIONS)
    ledger, o = fresh_ledger(gen), om.Oracle()
    for acc in gen.account_batches():
        ts = o.prepare("create_accounts", len(acc))
        assert o.create_accounts([om.account_from_numpy(r) for r in acc], ts) == []
    rows = early = twice = 0
    for n, (s, seq) in enumerate(in_commit_order(seed, 90)):
        operation, body = gen.request(s, seq)
        if operation == "create_transfers":
            ts = o.prepare("create_transfers", len(body))
            want = np.array(o.create_transfers([om.transfer_from_numpy(r) for r in body], ts),
                            dtype=RESULT).reshape(-1)
            assert ledger.create_transfers(body)[0].tobytes() == want.tobytes()
            continue
        if n % 3 == 0:  # ids that name no account, in the middle of the request: passed over
            body = np.concatenate([body[:5], np.zeros(2, dtype=ID), body[5:]])
            body["lo"][5:7] = (gen.accounts + 9, 1 << 40)
        found = o.lookup_accounts([int(v) for v in body["lo"]])
        want = no_timestamp(types.batch([om.account_to_numpy(a) for a in found],
                                        types.ACCOUNT_DTYPE))
        got = ledger.lookup_accounts(body)
        assert got.tobytes() == want.tobytes()
        assert got["id_lo"].tolist() == [v for v in body["lo"].tolist() if v <= gen.accounts]
        rows += len(got)
        twice += len(got) - len(np.unique(got["id_lo"]))
        early += int((got["credits_posted_lo"] == 0).sum())
    # the comparison had something to compare: rows, an id twice in one read, a customer read
    # before the load reached it (zeros, which the oracle says too)
    assert rows > 500 and twice > 20 and early > 20
    with pytest.raises(Exception):
        bad = np.zeros(1, dtype=ID)
        bad["hi"] = 1
        ledger.lookup_accounts(bad)  # outside what the reference models: refused, not judged


def served(gen, order):
    """{(session, seq): reply bytes} of a sound server that commits `order`."""
    ledger, replies = fresh_ledger(gen), {}
    for s, seq in order:
        operation, body = gen.request(s, seq)
        replies[(s, seq)] = (ledger.lookup_accounts(body).tobytes()
                             if operation == "lookup_accounts"
                             else ledger.create_transfers(body)[0].tobytes())
    return replies


def test_session_order_gives_other_rows_than_commit_order():
    gen, _ = generators("tiny", 3_000_000_019, sessions=SESSIONS)
    committed = in_commit_order(7, 120)
    a, b = served(gen, committed), served(gen, sorted(committed))
    reads = [key for key in committed if key[1] % 2]
    assert len(reads) > 40 and sum(a[k] != b[k] for k in reads) > 10
    assert served(gen, committed) == a


def records_of(gen, order, replies):
    """What `sessions.Load` would hold of that run: one record a request,
    answered in commit order, one after the other on the clock."""
    from benchmarks.sessions import Record

    records = []
    for op, (s, seq) in enumerate(order):
        operation, body = gen.request(s, seq)
        records.append(Record(s, seq, operation, len(body), sent=op + 0.25, done=op + 0.75,
                              reply=replies[(s, seq)], op=100 + op))
    return records


def compare(gen, records):
    from benchmarks import run
    from benchmarks.reference import Ledger

    return run.compare(gen, Ledger(gen.accounts), records, set(), {}, [])


def test_compare_judges_a_read_at_its_place_in_the_commit_order():
    from benchmarks.reference import ACCOUNT

    gen, _ = generators("tiny", 11, sessions=SESSIONS)
    order = in_commit_order(5, 120)
    replies = served(gen, order)
    verdict = compare(gen, records_of(gen, order, replies))
    assert verdict["read_mismatches"] == verdict["code_mismatches"] == 0
    assert verdict["read_rows_compared"] == 18 * sum(seq % 2 for _s, seq in order) > 500
    assert verdict["realtime_order_violations"] == verdict["session_order_violations"] == 0

    # one row of one read altered where it is produced
    key = next(k for k in order[60:] if k[1] % 2)
    rows = np.frombuffer(replies[key], dtype=ACCOUNT).copy()
    rows["credits_posted_lo"][3] += 1
    verdict = compare(gen, records_of(gen, order, {**replies, key: rows.tobytes()}))
    assert verdict["read_mismatches"] == 1 and verdict["mismatched_requests"] == {key: 1}
    assert verdict["code_mismatches"] == 0

    # a read that stands behind a write in the commit order, answered as of before it
    at = next(i for i in range(60, len(order) - 1)
              if not order[i][1] % 2 and order[i + 1][1] % 2)
    overtaken = order[:at] + [order[at + 1], order[at]] + order[at + 2:]
    stale = {**replies, order[at + 1]: served(gen, overtaken)[order[at + 1]]}
    verdict = compare(gen, records_of(gen, order, stale))
    assert verdict["read_mismatches"] > 0 and verdict["code_mismatches"] == 0
    assert set(verdict["mismatched_requests"]) == {order[at + 1]}

    # a read without a reply is no row compared: the caller counts it as never answered
    records = records_of(gen, order, replies)
    records[-1].reply = None
    assert compare(gen, records)["read_mismatches"] == 0


def test_a_generator_without_request_is_driven_as_before():
    from benchmarks import run
    from benchmarks.generators.ledger_mix import Generator

    gen = Generator({"accounts": 300, "batch": 64}, {**load("traffic", "transfers_sat.json"),
                                                     "sessions": SESSIONS}, 5)
    request, transfer_ids = run.requests_of(gen)
    operation, body = request(1, 2)
    assert operation == "create_transfers" and body.tobytes() == gen.batch(1, 2).tobytes()
    assert transfer_ids(1, 2) == gen.ids(1, 2) == body["id_lo"].tolist()
    reads, _ = generators("tiny", 5, sessions=SESSIONS)
    request, transfer_ids = run.requests_of(reads)
    assert request(1, 4)[1].tobytes() == reads.batch(1, 2).tobytes()
    assert transfer_ids(1, 4) == reads.ids(1, 2)  # request 4 of a session is its write batch 2


# --- the cell, through the whole of run.run_cell (rehearse.py; about 20 s a case) --------


def test_the_cell_runs_correct_and_reports_its_reads():
    from test_faults import rehearse

    result = rehearse(CELL)
    compared = result["compared"]
    assert result["correct"] is True and result["failed"] == 0 < result["attempted"]
    assert compared["read_mismatches"] == [0, 0] and compared["read_rows_compared"][0] > 0
    assert compared["code_events_compared"][0] > 0 and compared["accounts_compared"][0] == 1000
    assert set(result["metrics"]) == {"tx_per_s", "write_p50_ms", "setup_s", "read_p50_ms"}
    # attempted counts transfers and looked-up ids
    assert result["attempted"] > compared["read_rows_compared"][0] // 2


def test_the_traced_run_reads_the_device_gather():
    from test_faults import rehearse

    result = rehearse(CELL, "", "--trace", "1")
    metrics = result["metrics"]
    assert result["correct"] is True and result["compared"]["read_rows_compared"][0] > 0
    assert metrics["read_balances_ms_per_read"]["value"] > 0.0
    assert {"exact_sweeps_per_batch", "checkpoint_s_in_window", "execute_ms_per_batch",
            "slots_touched_per_batch"} <= set(metrics)


def test_a_cell_without_reads_reports_no_read_metric():
    from test_faults import rehearse

    result = rehearse("smallbank_1m.hotspot_sat")
    assert result["correct"] is True and "read_p50_ms" not in result["metrics"]
    assert result["compared"]["read_mismatches"] == [0, 0]
    assert result["compared"]["read_rows_compared"] == [0, None]
    assert "read_balances_ms_per_read" not in rehearse(
        "smallbank_1m.hotspot_sat", "", "--trace", "1")["metrics"]


if __name__ == "__main__":
    print(json.dumps(digests(sys.argv[1]), indent=1))
