"""Counts the server's child wrapper keeps (`benchmarks/serve.py`),
as a difference between the window's two ends.

`"field": "compiles"`: programs the process compiled or read from the
persistent cache (`jax.monitoring`, backend-compile duration events).
"""


def read(spec: dict, ctx: dict):
    before, after = ctx.get("monitor_before"), ctx.get("monitor_after")
    if before is None or after is None:
        return None
    return float(after[spec["field"]] - before[spec["field"]])
