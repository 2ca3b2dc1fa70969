#!/usr/bin/env python3
"""Rehearse a cell on the CPU at a tiny size: every phase of run.py with
the platform the rehearsal expects named HERE, in the test, as
tests/test_chip_smoke.py does (run.py itself has no switch for it).

    python3 benchmarks/tests/rehearse.py <workload> [--trace 1] [--seed n] [--seconds s]

The sizes are `test_min`'s: 64-event batches, 3 sessions + the read-back client (its client
table), 1,000 accounts. A cell whose configuration has three replicas gets three CPU
processes. A cell that BENCHMARK.json does not hold yet is found under `pending/`, by its
name. A cell whose traffic holds reads (`smallbank_1m.hotspot_balance_sat`) sends 18 ids a
read where the cell sends 2,456. Nothing it prints is a measurement.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
PENDING = os.path.join(REPO, "benchmarks", "tests", "pending")

# The CPU stands in for the chip: take the routes the chip takes.
ROUTES_AS_ON_CHIP = {"JAX_PLATFORMS": "cpu", "TIGERBEETLE_TPU_COMMIT_DEPTH": "4"}

TINY = {
    "config": {"start": {"config": "test_min", "backend": "jax"}, "accounts": 1000,
               "batch": 64, "transfers_max": 4096},
    # (a chain that rolls back in every batch or two of 64 events, where the cell has one in
    # 200 of its 546 chains a batch)
    "traffic": {"sessions": 3, "prefill_batches": 12, "trace_at_s": None,
                "chain_fail_one_in": 8},
}


def manifest(workload: str):
    """BENCHMARK.json, or, for a cell it does not hold yet, what it will be once it takes the
    cell's entries from tests/pending/<cell>.json: those added, and the cell read by every
    accepted per-layer entry that lists `read_as_in`, its single-node baseline, or that the
    file names under `join`."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        accepted = json.load(f)
    path = os.path.join(PENDING, workload + ".json")
    if not os.path.exists(path):
        return accepted
    with open(path) as f:
        pending = json.load(f)
    names = [w["name"] for w in pending["workloads"]]
    per_layer = [{**m, "workloads": m["workloads"] + names}
                 if pending["read_as_in"] in m.get("workloads", [])
                 or m["name"] in pending.get("join", []) else m
                 for m in accepted["per_layer"]]
    return {**accepted, "per_layer": per_layer + pending["per_layer"],
            "configs": accepted["configs"] + pending["configs"],
            "workloads": accepted["workloads"] + pending["workloads"]}


def expect_cpu(device: dict, chips: int) -> None:
    """For a server's `listening` line and, behind a cluster, for what the
    host shows once the replicas have let go."""
    assert device["platform"] == "cpu", device


def distinct_cpu(pid: int) -> set:
    """Where `launch.chips_held` finds a /dev/vfio node per process, a CPU
    rehearsal has none: each replica "holds" a chip named after its pid."""
    return {f"/dev/vfio/pid{pid}"}


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
                        expect=expect_cpu, overrides={**TINY, "manifest": manifest(args.workload)},
                        child=args.child,
                        device_prefix="/host:CPU", distinct=distinct_cpu)


if __name__ == "__main__":
    sys.exit(main())
