#!/usr/bin/env python3
"""The control and the planted faults ON THE CHIP, at a cell's own size:
the whole run of run.py (same sessions, same batches, same read-back)
against `broken_serve.py`, a short window per seed. Every run must print
`"correct": false`; the numbers beside their limits are the upper
readings PERF.md quotes. The benchmark's own runs never run this.

    chiprun -- python3 benchmarks/tests/control_on_chip.py \
        --workload ledger_1m.transfers_sat --fault lossy_scatter --seeds 7,8,9
    chiprun -- python3 benchmarks/tests/control_on_chip.py \
        --workload smallbank_1m.hotspot_balance_sat --fault stale_reads --seeds 7,8,9

A cell that BENCHMARK.json does not hold yet is found under `pending/`, as
rehearse.py finds it; `--fault sound` with `--seconds 40` (and `--trace 1`)
is then that cell's own run on the chip, which run.py's command line
cannot make. `--traffic '{"hotspot_customers": 0}'` lays those keys over
the cell's traffic file for the run: how a window is read under another
value of a parameter the source does not fix (what then depends on the
choice is said in PERF.md; such a run is no cell's and sets no bound).
"""

import argparse
import json
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
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--traffic", type=json.loads, default={})
    args = ap.parse_args()
    os.environ["BENCH_FAULT"] = args.fault
    sys.path.insert(0, HERE)
    from benchmarks import run
    from rehearse import manifest

    worst = 0
    for seed in args.seeds.split(","):
        print(f"=== {args.workload} fault={args.fault} seed={seed}", flush=True)
        worst = max(worst, run.run_cell(args.workload, int(seed), args.seconds, bool(args.trace),
                                        overrides={"manifest": manifest(args.workload),
                                                   "traffic": args.traffic},
                                        child=None if args.fault == "sound" else
                                        os.path.join(HERE, "broken_serve.py")))
    return worst


if __name__ == "__main__":
    sys.exit(main())
