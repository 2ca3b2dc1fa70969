"""Device metrics from the reduced profiler trace (`reduce_trace.py`).

A metric file with `"reader": "trace"` gives `arithmetic`:

  idle       100 * (1 - busy seconds / traced seconds)
  roofline   100 * (needed bytes / peak bytes per second) / (device
             seconds per call of the modules matching `module`, a regular
             expression); `needed_work` names the file under
             `benchmarks/needed_work/` that computes the bytes

Nothing traced, or no module matching: nothing returned. Never 0 for a
share of a roofline.
"""

import importlib
import re


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace") or {}
    if not trace.get("window_s") or not trace.get("busy_s"):
        return None  # no operation ran on a device: nothing to take a share of
    if spec["arithmetic"] == "idle":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    if spec["arithmetic"] == "roofline":
        pattern = re.compile(spec["module"])
        hits = [m for name, m in trace["modules"].items() if pattern.search(name)]
        calls = sum(m["calls"] for m in hits)
        seconds = sum(m["seconds"] for m in hits)
        if not calls or seconds <= 0.0:
            return None
        work = importlib.import_module(
            "benchmarks.needed_work." + spec["needed_work"]).needed(
                ctx["config"], ctx["traffic"])
        least = work["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
        return 100.0 * least / (seconds / calls)
    raise ValueError(f"unknown trace arithmetic {spec['arithmetic']!r}")
