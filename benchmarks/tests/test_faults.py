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

# fault -> the compared numbers of which at least one must leave its limit, and the cells that
# can have it. Each cell has one control: under `lossy_scatter` the exact kernel sees its
# pending balances underflow and bails every batch to the host's serial path, which answers
# rightly at a crawl (my chip run, PR 24: no batch of cell 2's prefill in 1,130 s), so cell 2's
# control breaks the guarantee its own mix adds.
EXPECT = {
    "lossy_scatter": ({"balance_mismatches"}, [CELL_1]),
    "chains_unlinked": ({"code_mismatches"}, [CELL_2]),
    "state_unchanged": ({"balance_mismatches"}, [CELL_1, CELL_2]),
    "half_left_out": ({"balance_mismatches"}, [CELL_1, CELL_2]),
    "code_altered": ({"code_mismatches"}, [CELL_1, CELL_2]),
    "store_altered": ({"store_mismatches"}, [CELL_1, CELL_2]),
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


@pytest.mark.parametrize("workload", [CELL_1, CELL_2])
def test_the_sound_server_is_correct(workload):
    result = rehearse(workload)
    assert result["correct"] is True and result["failed"] == 0
