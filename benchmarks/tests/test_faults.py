"""Drive a whole run with the timed path broken underneath and see
`correct` come out false: once for each fault a cell can have, and for
the control. On the CPU at test_min size, through rehearse.py (which
skips only the harness's look for a chip). Slow: about 20 s a case.

    python -m pytest benchmarks/tests/test_faults.py -q -p no:cacheprovider
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

CELL_1, CELL_2 = "ledger_1m.transfers_sat", "settlement_1m.two_phase_sat"
WALLETS = "wallets_1m.spend_sat"  # under pending/: a rehearsal, not a cell
SMALLBANK = "smallbank_1m.hotspot_sat"
BALANCE = "smallbank_1m.hotspot_balance_sat"  # the sibling with SmallBank's reads (PR 37)

# fault -> the compared numbers of which at least one must leave its limit, and the cells that
# can have it. Each cell has one control: under `lossy_scatter` the exact kernel sees its
# pending balances underflow and bails every batch to the host's serial path, which answers
# rightly at a crawl (my chip run, PR 24: no batch of cell 2's prefill in 1,130 s), so cell 2's
# control breaks the guarantee its own mix adds. The wallets' cell (PR 36) likewise: its
# control drops the guarantee its configuration adds, the limit on a wallet; the SmallBank
# cell's is the same, on its customers' accounts. `ops_relabelled` is the control of the order
# the replay follows: in cell 1, where no answer depends on that order, nothing else can tell.
EXPECT = {
    "lossy_scatter": ({"balance_mismatches"}, [CELL_1]),
    "chains_unlinked": ({"code_mismatches"}, [CELL_2, WALLETS]),
    "limit_flags_dropped": ({"code_mismatches"}, [WALLETS, SMALLBANK, BALANCE]),
    "ops_relabelled": ({"realtime_order_violations"}, [CELL_1, SMALLBANK, BALANCE]),
    "stale_reads": ({"read_mismatches"}, [BALANCE]),
    "state_unchanged": ({"balance_mismatches"}, [CELL_1, CELL_2, WALLETS, SMALLBANK, BALANCE]),
    "half_left_out": ({"balance_mismatches"}, [CELL_1, CELL_2, WALLETS, SMALLBANK, BALANCE]),
    "code_altered": ({"code_mismatches"}, [CELL_1, CELL_2, WALLETS, SMALLBANK, BALANCE]),
    "store_altered": ({"store_mismatches"}, [CELL_1, CELL_2, WALLETS, SMALLBANK, BALANCE]),
}


def rehearse(workload: str, fault: str = "", *more: str) -> dict:
    argv = [sys.executable, os.path.join(HERE, "rehearse.py"), workload, "--seconds", "2", *more]
    env = dict(os.environ)
    if fault:
        argv += ["--child", os.path.join(HERE, "broken_serve.py")]
        env["BENCH_FAULT"] = fault
    r = subprocess.run(argv, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,fault", [(cell, fault) for fault in sorted(EXPECT)
                                            for cell in EXPECT[fault][1]])
def test_a_broken_server_is_not_correct(workload, fault):
    result = rehearse(workload, fault)
    assert result["correct"] is False
    off = {name for name, (value, limit) in result["compared"].items()
           if limit is not None and value != limit}
    assert off & EXPECT[fault][0], result["compared"]
    if fault == "stale_reads":  # everything else is sound: nothing but the reads can tell
        assert off == {"read_mismatches"}, result["compared"]
    if workload == BALANCE and fault in ("state_unchanged", "half_left_out"):
        assert "read_mismatches" in off  # a read under load sees the state the kernel left


@pytest.mark.parametrize("workload", [CELL_1, CELL_2, WALLETS, SMALLBANK, BALANCE])
def test_the_sound_server_is_correct(workload):
    result = rehearse(workload)
    assert result["correct"] is True and result["failed"] == 0
