"""The plain reference and the generator against models/oracle.py, on the
CPU at test_min size: the same traffic through both gives the same result
codes, the same stored transfers and the same balances. (On the chip the
reference judges the served system; here the repo's serial oracle judges
the reference.)

    python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.generators.ledger_mix import Generator  # noqa: E402
from benchmarks.reference import RESULT, Ledger, Unsupported  # noqa: E402

CONFIG = {"accounts": 300, "batch": 64}


def traffic(name: str) -> dict:
    with open(os.path.join(REPO, "benchmarks", "traffic", name + ".json")) as f:
        out = {**json.load(f), "sessions": 3}
    if "shares" in out:  # at 64 events a batch the rare faults must be common to occur
        out.update(chain_fail_one_in=4, settle_fail_share=0.6)
    return out


def no_timestamp(recs):
    out = np.array(recs)
    out["timestamp"] = 0
    return out


@pytest.mark.parametrize("seed", [11, 3_000_000_019])
@pytest.mark.parametrize("mix", ["transfers_sat", "two_phase_sat"])
def test_reference_agrees_with_the_oracle(mix, seed):
    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.models import oracle as om

    gen = Generator(CONFIG, traffic(mix), seed)
    ledger, o = Ledger(CONFIG["accounts"]), om.Oracle()
    for acc in gen.account_batches():
        ts = o.prepare("create_accounts", len(acc))
        assert o.create_accounts([om.account_from_numpy(r) for r in acc], ts) == []
        assert len(ledger.create_accounts(acc)) == 0
    rng = np.random.default_rng(seed)
    next_seq = [0, 0, 0]
    failures = stored_rows = 0
    codes = set()
    for _ in range(36):  # sessions interleaved in a seeded order, each in its own
        s = int(rng.integers(0, 3))
        events = gen.batch(s, next_seq[s])
        next_seq[s] += 1
        ts = o.prepare("create_transfers", len(events))
        want = np.array(o.create_transfers(
            [om.transfer_from_numpy(r) for r in events], ts), dtype=RESULT).reshape(-1)
        got, stored = ledger.create_transfers(events)
        assert got.tobytes() == want.tobytes()
        failures += len(got)
        codes |= set(got["result"].tolist())
        found = o.lookup_transfers([int(v) for v in events["id_lo"]])
        want_stored = types.batch([om.transfer_to_numpy(t) for t in found],
                                  types.TRANSFER_DTYPE)
        assert no_timestamp(want_stored).tobytes() == stored.tobytes()
        stored_rows += len(stored)
    ids = list(range(1, CONFIG["accounts"] + 1))
    want_acc = types.batch([om.account_to_numpy(a) for a in o.lookup_accounts(ids)],
                           types.ACCOUNT_DTYPE)
    assert no_timestamp(want_acc).tobytes() == ledger.lookup_accounts(ids).tobytes()
    assert failures > 0 and stored_rows > 0  # the comparison had something to compare
    if mix == "two_phase_sat":
        # a chain rolled back; a post found nothing, found a plain transfer,
        # found one already settled
        assert {1, 25, 26} <= codes and codes & {33, 34}


def test_reference_refuses_what_it_does_not_model():
    gen = Generator(CONFIG, traffic("transfers_sat"), 5)
    ledger = Ledger(CONFIG["accounts"])
    for acc in gen.account_batches():
        ledger.create_accounts(acc)
    events = gen.batch(0, 0)
    ledger.create_transfers(events)
    with pytest.raises(Unsupported):
        ledger.create_transfers(events)  # the same ids again
    balancing = gen.batch(0, 1)
    balancing["flags"][0] = 1 << 4
    with pytest.raises(Unsupported):
        ledger.create_transfers(balancing)


def test_same_seed_same_bytes_and_seeds_share_the_sizes():
    a = Generator(CONFIG, traffic("two_phase_sat"), 7)
    b = Generator(CONFIG, traffic("two_phase_sat"), 7)
    c = Generator(CONFIG, traffic("two_phase_sat"), 8)
    for k in range(3):
        x, y, z = a.batch(1, k), b.batch(1, k), c.batch(1, k)
        assert x.tobytes() == y.tobytes()
        assert x.tobytes() != z.tobytes()
        # another seed: other accounts and amounts, the same number of each kind
        assert sorted(x["flags"].tolist()) == sorted(z["flags"].tolist())
