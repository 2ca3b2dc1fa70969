"""The cells PR 27 added, each driven through the whole of `run.run_cell` on
the CPU at test_min size (rehearse.py): the sound server is `correct`, the
TPC-B cell's traced run reports the per-layer metrics its deployment
brought, and its control (`chains_unlinked`: a chain that must roll back
is applied link by link) is not. About 15 s a case.

    python -m pytest benchmarks/tests/test_new_cells.py -q -p no:cacheprovider
"""

import pytest
from test_faults import rehearse

TPCB, OK_SAT = "tpcb_1m.debit_credit_sat", "ledger_1m.transfers_ok_sat"
BROUGHT = {"chains_per_batch", "chains_rolled_back_per_batch", "slots_touched_per_batch",
           "hot_slot_postings_per_batch", "commit_plan_ms_per_batch"}


@pytest.mark.parametrize("workload", [TPCB, OK_SAT])
def test_the_sound_server_is_correct(workload):
    result = rehearse(workload)
    assert result["correct"] is True and result["failed"] == 0 < result["attempted"]
    assert {"tx_per_s", "write_p50_ms", "setup_s"} == set(result["metrics"])
    assert result["compared"]["accounts_compared"][0] == 1000


def test_the_traced_run_reads_what_the_deployment_brought():
    metrics = rehearse(TPCB, "", "--trace", "1")["metrics"]
    assert BROUGHT <= set(metrics)
    # every batch, all of it; a scrape reads the two counters a moment apart, and at 4 ms a batch
    # one of some 250 batches can fall between them
    assert metrics["chains_per_batch"]["value"] == pytest.approx(64 // 3, rel=0.02)
    assert 0 < metrics["chains_rolled_back_per_batch"]["value"] < 64 // 3
    assert metrics["hot_slot_postings_per_batch"]["value"] >= 3  # a cash account, at least
    assert metrics["slots_touched_per_batch"]["value"] <= 4 * (64 // 3) + 2
    assert "commit_prefetch_ms_per_batch" not in metrics  # the sibling's, not listed here


def test_the_all_success_cell_reports_none_of_them():
    result = rehearse(OK_SAT, "", "--trace", "1")
    assert result["correct"] is True and not BROUGHT & set(result["metrics"])


def test_transfers_ok_sat_is_transfers_sat_without_a_failing_event():
    from benchmarks.generators.ledger_mix import Generator
    from benchmarks.reference import Ledger
    from benchmarks.run import load_json

    sat, ok = (load_json("traffic", name + ".json")
               for name in ("transfers_sat", "transfers_ok_sat"))
    parameters = lambda t: {k: v for k, v in t.items() if not k.startswith("why_")}  # noqa: E731
    assert parameters(ok) == {**parameters(sat), "fail_share": 0.0} != parameters(sat)
    config = {"accounts": 300, "batch": 8190}
    for traffic, failing in ((ok, False), (sat, True)):
        gen, ledger = Generator(config, traffic, 3_000_000_047), Ledger(config["accounts"])
        for acc in gen.account_batches():
            ledger.create_accounts(acc)
        results = [ledger.create_transfers(gen.batch(s, 0))[0] for s in range(3)]
        assert all(len(r) > 0 for r in results) if failing else not any(len(r) for r in results)


def test_chains_unlinked_is_not_correct():
    result = rehearse(TPCB, "chains_unlinked")
    assert result["correct"] is False
    assert result["compared"]["code_mismatches"][0] > 0, result["compared"]
