"""From a profiler trace (`*.xplane.pb`) to device busy time, idle gaps
and time per XLA module. The only code that turns a trace into numbers.

    python3 benchmarks/reduce_trace.py <trace dir> [<plane prefix>]

prints one JSON object. It runs as a process of its own, after the
server has let go of the chip, because reading a trace needs
`jax.profiler.ProfileData` and the benchmark's parent stays off JAX.

What is read, on each device plane (`/device:TPU:<n>`):

- line `XLA Ops`: one event per operation the device executed. The
  union of their intervals is the time the device was busy.
- line `XLA Modules`: one event per executed program, named
  `jit_<function>(<fingerprint>)`. Summed per name (fingerprint cut off)
  they give each kernel's device time and its number of calls.

The window is the span from the first to the last event of the trace on
any plane, host planes included, so a device that idles at either end of
the trace is not flattered. Busy seconds are averaged over the device
planes. A trace without a device plane gives no numbers at all (`{}`):
there is no fallback to host time. A directory that holds several traces
(the slices of one window) is reduced trace by trace and added up.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def union_seconds(intervals: list) -> tuple:
    """(seconds covered, [(gap start, gap end)]) of [(start_ns, end_ns)]."""
    covered, gaps = 0, []
    end = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            if end is not None:
                gaps.append((end, a))
            covered += b - a
            end = b
        elif b > end:
            covered += b - end
            end = b
    return covered / 1e9, gaps


def top(seconds_by_name: dict) -> list:
    """The ten largest, as [[name, seconds], ...]."""
    return [[k, v] for k, v in sorted(seconds_by_name.items(), key=lambda kv: -kv[1])[:10]]


def module_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """`fusion.147 u32[3,262144]` of the whole HLO line an op is named by."""
    m = re.match(r"%?(\S+) = \(?(\w+\[[\d,]*\])?", event_name)
    return event_name[:80] if m is None else " ".join(g for g in m.groups() if g)


def reduce_planes(planes: list, device_prefix: str) -> dict:
    """`planes`: [(plane name, [(line name, [(event name, start_ns,
    duration_ns)])])], the part of ProfileData this reduction reads."""
    first, last = None, None
    for _name, lines in planes:
        for _line, events in lines:
            for _e, start, dur in events:
                first = start if first is None else min(first, start)
                last = start + dur if last is None else max(last, start + dur)
    devices = [(n, lines) for n, lines in planes if n.startswith(device_prefix)]
    if not devices or first is None:
        return {}
    busy, modules, ops, gaps = [], {}, {}, {}
    for _name, lines in devices:
        by_line = dict(lines)
        op_events = by_line.get(OPS_LINE, [])
        covered, holes = union_seconds([(s, s + d) for _e, s, d in op_events])
        busy.append(covered)
        for e, _s, d in op_events:
            ops[op_name(e)] = ops.get(op_name(e), 0.0) + d / 1e9
        module_events = sorted(by_line.get(MODULES_LINE, []), key=lambda ev: ev[1])
        for e, _s, d in module_events:
            m = modules.setdefault(module_name(e), {"seconds": 0.0, "calls": 0})
            m["seconds"] += d / 1e9
            m["calls"] += 1
        # An idle gap is named by the program the device ran next: what
        # the host was doing in it needs annotations the program lacks.
        starts = [s for _e, s, _d in module_events]
        for a, b in holes:
            i = bisect.bisect_left(starts, b - 1000)
            nxt = module_name(module_events[i][0]) if i < len(starts) else "end of trace"
            key = f"before {nxt}"
            gaps[key] = gaps.get(key, 0.0) + (b - a) / 1e9
    return {
        "window_s": (last - first) / 1e9,
        "busy_s": sum(busy) / len(busy),
        "devices": len(devices),
        "modules": modules,
        "device_ops": top(ops),
        "idle_gaps": top(gaps),
    }


def read_planes(path: str) -> list:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [(plane.name,
             [(line.name, [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                           for ev in line.events])
              for line in plane.lines])
            for plane in data.planes]


def combine(slices: list) -> dict:
    """Several traced slices of one window as one: seconds and calls add up."""
    slices = [r for r in slices if r]
    if not slices:
        return {}
    modules, ops, gaps = {}, {}, {}
    for r in slices:
        for name, m in r["modules"].items():
            into = modules.setdefault(name, {"seconds": 0.0, "calls": 0})
            into["seconds"] += m["seconds"]
            into["calls"] += m["calls"]
        for into, pairs in ((ops, r["device_ops"]), (gaps, r["idle_gaps"])):
            for name, seconds in pairs:
                into[name] = into.get(name, 0.0) + seconds
    return {
        "window_s": sum(r["window_s"] for r in slices),
        "busy_s": sum(r["busy_s"] for r in slices),
        "devices": max(r["devices"] for r in slices),
        "modules": modules,
        "device_ops": top(ops),
        "idle_gaps": top(gaps),
        "slices": [{"window_s": r["window_s"], "busy_s": r["busy_s"],
                    "modules": r["modules"]} for r in slices],
    }


def reduce_dir(trace_dir: str, device_prefix: str = "/device:TPU") -> dict:
    """Every trace under `trace_dir` (one per traced slice), as one."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return combine([reduce_planes(read_planes(f), device_prefix) for f in files])


if __name__ == "__main__":
    print(json.dumps(reduce_dir(*sys.argv[1:3])))
