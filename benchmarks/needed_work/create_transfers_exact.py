"""What one call of the exact commit kernel has to move, from shapes only.

As the fast kernel, plus the pending transfer each post or void event is
handed (its amount, its two account slots, its state). The sweeps to a
fixed point are the implementation's: the need counts each row once.
"""

from benchmarks.needed_work import create_transfers_fast as fast

PENDING_ROW = 16 + 4 + 4 + 4  # u128 amount, two slots, state


def needed(config: dict, traffic: dict) -> dict:
    n = int(config["batch"])
    settles = int(round(float(traffic.get("shares", {}).get("post_void", 0.0)) * n))
    return {"bytes": fast.needed(config, traffic)["bytes"] + settles * PENDING_ROW,
            "bound": "bytes"}
