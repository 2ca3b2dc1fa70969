#!/usr/bin/env python3
"""Rehearse a cell on the CPU at a tiny size: every phase of run.py with
the platform the rehearsal expects named HERE, in the test, as
tests/test_chip_smoke.py does (run.py itself has no switch for it).

    python3 benchmarks/tests/rehearse.py <workload> [--trace 1] [--seed n] [--seconds s]

The sizes are `test_min`'s: 64-event batches, 3 sessions + the read-back client (its client
table), 1,000 accounts. Nothing it prints is a measurement.
"""

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

# The CPU stands in for the chip: take the routes the chip takes.
ROUTES_AS_ON_CHIP = {"JAX_PLATFORMS": "cpu", "TIGERBEETLE_TPU_DEVICE_MERGE": "1",
                     "TIGERBEETLE_TPU_COMMIT_DEPTH": "4"}

TINY = {
    "config": {"start": {"config": "test_min", "backend": "jax"}, "accounts": 1000,
               "batch": 64, "transfers_max": 4096},
    # (a chain that rolls back in every batch or two of 64 events, where the cell has one in
    # 200 of its 546 chains a batch)
    "traffic": {"sessions": 3, "prefill_batches": 12, "trace_at_s": None,
                "chain_fail_one_in": 8},
}


def expect_cpu(device: dict, chips: int) -> None:
    assert device["platform"] == "cpu", device


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seed", type=int, default=3_000_000_019)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--child", default=None)
    args = ap.parse_args()
    os.environ.update(ROUTES_AS_ON_CHIP)
    from benchmarks import run

    return run.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                        expect=expect_cpu, overrides=TINY, child=args.child,
                        device_prefix="/host:CPU")


if __name__ == "__main__":
    sys.exit(main())
