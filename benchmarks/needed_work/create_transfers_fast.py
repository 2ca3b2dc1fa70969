"""What one call of the fast commit kernel has to move, from shapes only.

The algorithm's need, not the implementation's: whatever posts the
balances, a batch of n events needs its staged events in, its result
codes out, and every balance row it touches read once and written once.
Integer compare-and-add on those bytes: the kernel is bound by memory,
so the roofline is bytes over the chip's HBM bandwidth.
"""

STAGED_EVENT = 4 + 4 + 16 + 4 + 4 + 4 + 4 + 8  # two slots, u128 amount, flags, ledger, code, host code, timestamp
RESULT = 4
BALANCE_ROW = 4 * 16  # four u128 balances of one account


def needed(config: dict, traffic: dict) -> dict:
    n = int(config["batch"])
    rows = 2 * n  # at most: a debit and a credit account per event
    return {"bytes": n * (STAGED_EVENT + RESULT) + rows * BALANCE_ROW * 2,
            "bound": "bytes"}
