"""The cell PR 34 added, `tpcb_16m.debit_credit_sat`, driven through the whole
of `run.run_cell` on the CPU at test_min size: the sound server is `correct`
over every balance, the traced run reports the two checkpoint metrics the cell
brought (more than 0: `test_min` checkpoints every 16 ops, so the window holds
many) beside what `tpcb_1m.debit_credit_sat` reports, and the control
(`chains_unlinked`) is not correct. About 15 s a case.

    python -m pytest benchmarks/tests/test_tpcb_16m.py -q -p no:cacheprovider

rehearse.py's sizes hold ten branches (1,000 accounts) and this deployment
has 160, so the rehearsal is made here: run as a script, this file is
rehearse.py for this cell, with 16 branches of 50 accounts.
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

CELL, SIBLING = "tpcb_16m.debit_credit_sat", "tpcb_1m.debit_credit_sat"
BROUGHT = {"checkpoint_s_in_window", "checkpoint_blob_bytes_in_window"}
ACCOUNTS = 16 * (50 + 10 + 2)  # 16 branches under test_min's 1,024 slots


def rehearse(fault: str = "", *more: str) -> dict:
    argv = [sys.executable, os.path.abspath(__file__), "--seconds", "2", *more]
    env = dict(os.environ)
    if fault:
        argv += ["--child", os.path.join(HERE, "broken_serve.py")]
        env["BENCH_FAULT"] = fault
    r = subprocess.run(argv, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_manifest_has_the_cell_as_the_issue_states_it():
    m = manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    sibling = next(w for w in m["workloads"] if w["name"] == SIBLING)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpcb_16m", sibling["traffic"], 1)
    assert not any(w["chips"] == 4 for w in m["workloads"]) and len(m["workloads"]) == 7
    for p in m["per_layer"]:
        listed = p.get("workloads")
        if p["name"] in BROUGHT:
            # (first of the cells whose window reaches a checkpoint; PR 36 added a second)
            assert listed[0] == CELL and (p["layer"], p["moves"]) == ("consensus", "tx_per_s")
        elif listed is not None:  # read in the new cell wherever it is read in the sibling
            assert (CELL in listed) == (SIBLING in listed), p["name"]
    reported = {e["name"] for e in m["end_to_end"] if CELL in e.get("workloads", [CELL])}
    assert reported == {"tx_per_s", "write_p50_ms", "setup_s"}


def test_the_sound_server_is_correct_over_every_balance():
    result = rehearse()
    assert result["correct"] is True and result["failed"] == 0 < result["attempted"]
    assert {"tx_per_s", "write_p50_ms", "setup_s"} == set(result["metrics"])
    compared = result["compared"]
    assert compared["accounts_compared"][0] == ACCOUNTS
    assert all(compared[k] == [0, 0] for k in ("code_mismatches", "balance_mismatches",
                                               "store_mismatches", "requests_never_answered"))


def test_the_traced_run_reads_the_checkpoints_and_what_the_sibling_reads():
    metrics = rehearse("", "--trace", "1")["metrics"]
    assert BROUGHT <= set(metrics)
    # a window of 2 s at test_min's 16 ops a checkpoint holds several, each with a blob of
    # 128 B an account and more
    assert metrics["checkpoint_s_in_window"]["value"] > 0
    assert metrics["checkpoint_blob_bytes_in_window"]["value"] > ACCOUNTS * 128
    m = manifest()
    owed = {p["name"] for p in m["per_layer"] if CELL in p.get("workloads", [CELL])}
    owed_by_sibling = {p["name"] for p in m["per_layer"]
                       if SIBLING in p.get("workloads", [SIBLING])}
    assert owed == owed_by_sibling | BROUGHT and len(owed) == 36
    # what a CPU rehearsal cannot read: the device's (no kernel runs under a TPU's name), and
    # the mirrors of tables that a store of this size never builds
    assert owed - set(metrics) <= {"create_transfers_exact_roofline", "device_idle_pct",
                                   "mirror_rows_built_per_batch"}
    assert metrics["chains_per_batch"]["value"] > 20  # 64 // 3 chains in every batch


def test_chains_unlinked_is_not_correct():
    result = rehearse("chains_unlinked")
    assert result["correct"] is False
    assert result["compared"]["code_mismatches"][0] > 0, result["compared"]


def main() -> int:
    import argparse

    import rehearse as r

    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seed", type=int, default=3_000_000_019)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--child", default=None)
    args = ap.parse_args()
    os.environ.update(r.ROUTES_AS_ON_CHIP)
    sys.path.insert(0, REPO)
    from benchmarks import run

    tiny = {**r.TINY, "config": {**r.TINY["config"], "scale": 16, "accounts": ACCOUNTS}}
    return run.run_cell(CELL, args.seed, args.seconds, bool(args.trace), expect=r.expect_cpu,
                        overrides=tiny, child=args.child, device_prefix="/host:CPU",
                        distinct=r.distinct_cpu)


if __name__ == "__main__":
    sys.exit(main())
