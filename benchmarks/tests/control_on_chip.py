#!/usr/bin/env python3
"""The control and the planted faults ON THE CHIP, at a cell's own size:
the whole run of run.py (same sessions, same batches, same read-back)
against `broken_serve.py`, a short window per seed. Every run must print
`"correct": false`; the numbers beside their limits are the upper
readings PERF.md quotes. The benchmark's own runs never run this.

    chiprun -- python3 benchmarks/tests/control_on_chip.py \
        --workload ledger_1m.transfers_sat --fault lossy_scatter --seeds 7,8,9
"""

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    os.environ["BENCH_FAULT"] = args.fault
    from benchmarks import run

    worst = 0
    for seed in args.seeds.split(","):
        print(f"=== {args.workload} fault={args.fault} seed={seed}", flush=True)
        worst = max(worst, run.run_cell(args.workload, int(seed), args.seconds, False,
                                        child=os.path.join(HERE, "broken_serve.py")))
    return worst


if __name__ == "__main__":
    sys.exit(main())
