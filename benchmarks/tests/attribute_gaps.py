#!/usr/bin/env python3
"""What `tiled_serve.py` kept of one traced run, read four ways:

    python3 benchmarks/tests/attribute_gaps.py <kept dir> [<device plane prefix>]

1. the tiling: per worker thread, leaf-span seconds over busy seconds
   (elapsed minus the thread's waits) between the window's two ends (a
   wait in progress at an end is counted whole or not at all: in a storm
   one stall is a second or more), and again inside each slice, from the
   annotations, where no wait is cut;
2. per trace slice, `device.unfed` over the slice against the slice's own
   idle share from the device plane (unfed is a lower bound: it must not
   be the larger);
3. the host plane's annotations: which of the tracer's allow-listed spans
   the profiler holds, on which line (thread), how often;
4. the three largest idle gaps of each slice, and which annotations on the
   commit and the store thread cover most of each.

A throw-away reader for PR 25's findings; `reduce_trace.py` is the
benchmark's reduction and is not changed by it (PERF.md, Open questions,
has the edit a `benchmark` issue should make there).
"""

import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import reduce_trace  # noqa: E402
from tigerbeetle_tpu import tracer  # noqa: E402

from tigerbeetle_tpu.tracer import (  # noqa: E402
    COMMIT_LEAVES, COMMIT_WAITS, STORE_LEAVES, STORE_WAITS, TILING_PARENTS as PARENTS,
)


def load(keep: str) -> list:
    docs = []
    for path in sorted(glob.glob(os.path.join(keep, "threads_*.json"))):
        with open(path) as f:
            docs.append(json.load(f))
    return docs


def seconds(before: dict, after: dict, thread: str, events) -> float:
    ns = lambda doc, e: doc["threads"].get(thread, {}).get(e, [0, 0])[1]  # noqa: E731
    return sum(ns(after, e) - ns(before, e) for e in events) / 1e9


def tiling(docs: list) -> None:
    ends = [d for d in docs if d["tag"] == "window"]
    before, after = ends[0], ends[-1]
    elapsed = (after["perf_ns"] - before["perf_ns"]) / 1e9
    print(f"--- tiling over the window ({elapsed:.3f} s between the two `compiles` requests)")
    for thread, leaves, waits in (("commit-executor", COMMIT_LEAVES, COMMIT_WAITS),
                                  ("store-executor", STORE_LEAVES, STORE_WAITS)):
        waited = seconds(before, after, thread, waits)
        leaf = seconds(before, after, thread, leaves)
        busy = elapsed - waited
        print(f"{thread}: waits {waited:.3f} s ("
              + ", ".join(f"{w} {seconds(before, after, thread, [w]):.3f}" for w in waits)
              + f"), busy {busy:.3f} s = {100 * busy / elapsed:.1f}% of the window; "
              f"leaves {leaf:.3f} s = {100 * leaf / busy:.1f}% of busy")
        for e in leaves:
            print(f"    {e:28s} {seconds(before, after, thread, [e]):9.3f} s")
    for thread in sorted(after["threads"]):
        compiled = seconds(before, after, thread, ["device.compile"])
        if compiled:
            print(f"device.compile on {thread}: {compiled:.3f} s in the window")


def intervals_overlap(events: list, a: int, b: int) -> dict:
    """{name: ns of [a, b) covered by events of that name}."""
    out = {}
    for name, start, dur in events:
        lo, hi = max(a, start), min(b, start + dur)
        if hi > lo:
            out[name] = out.get(name, 0) + hi - lo
    return out


def whose(counts: dict) -> str:
    """The thread a host line belongs to, by the annotations only it makes."""
    if any(n.startswith("sm.ct.") or n in ("replica.execute", "pipeline.commit.idle",
                                           "pipeline.store.stall") for n in counts):
        return "commit thread"
    if any(n in ("stage.store_async", "sm.beat", "lsm.compact.merge", "pipeline.store.idle",
                 "pipeline.store.prefetch", "sm.store.log") for n in counts):
        return "store thread"
    return "WAL thread" if "wal.write" in counts else "another thread"


def stretch_tiling(who: str, line_events: list) -> None:
    """The tiling inside a slice, from the annotations themselves: between
    the first annotation's start and the last one's end on the line (a
    span in progress at either edge of the slice is not in the trace, and
    a storm's stall or beat lasts longer than a slice), leaf seconds over
    that stretch minus its waits."""
    leaves, waits = ((COMMIT_LEAVES, COMMIT_WAITS) if who == "commit thread"
                     else (STORE_LEAVES, STORE_WAITS))
    flat = [(s, s + d, n) for n, s, d in line_events if n in leaves or n in waits]
    if not flat:
        return
    a, b = min(f[0] for f in flat), max(f[1] for f in flat)
    waited = sum(t1 - t0 for t0, t1, n in flat if n in waits)
    leaf = sum(t1 - t0 for t0, t1, n in flat if n in leaves)
    busy = (b - a) - waited
    if busy > 0:
        print(f"        tiling inside the slice: stretch {(b - a) / 1e6:.1f} ms, waits "
              f"{waited / 1e6:.1f} ms, leaves {leaf / 1e6:.1f} ms = {100 * leaf / busy:.1f}% of busy")


def unnamed_stretches(line_events: list, first: int, last: int) -> None:
    """Where a thread's line holds no leaf and no wait: the stretches
    between them, added up by what came before and after."""
    flat = sorted((s, s + d, n) for n, s, d in line_events if n not in PARENTS)
    between, end, prev = {}, first, "start of slice"
    for a, b, name in flat + [(last, last, "end of slice")]:
        if a > end:
            key = f"{prev} -> {name}"
            between[key] = between.get(key, 0) + a - end
        if b > end:
            end, prev = b, name
    total = sum(between.values())
    print(f"        unnamed on this line: {total / 1e6:.1f} ms of {(last - first) / 1e6:.1f} ms: "
          + "; ".join(f"{k} {v / 1e6:.1f} ms" for k, v in
                      sorted(between.items(), key=lambda kv: -kv[1])[:4]))


def slices(keep: str, docs: list, device_prefix: str) -> None:
    starts = [d for d in docs if d["tag"] == "slice_start"]
    stops = [d for d in docs if d["tag"] == "slice_stop"]
    files = sorted(glob.glob(os.path.join(keep, "trace", "**", "*.xplane.pb"), recursive=True))
    for i, path in enumerate(files):
        planes = reduce_trace.read_planes(path)
        reduced = reduce_trace.reduce_planes(planes, device_prefix)
        print(f"--- slice {i}: {path} ({os.path.getsize(path):,} bytes)")
        if i < len(starts) and i < len(stops):
            span = (stops[i]["perf_ns"] - starts[i]["perf_ns"]) / 1e9
            unfed = sum(seconds(starts[i], stops[i], t, ["device.unfed"])
                        for t in stops[i]["threads"])
            idle = (1 - reduced["busy_s"] / reduced["window_s"]) if reduced else float("nan")
            print(f"device.unfed over the slice: {unfed:.4f} s of {span:.4f} s = "
                  f"{100 * unfed / span:.2f}%; the slice's idle share on the device plane: "
                  f"{100 * idle:.2f}% ({'unfed <= idle: holds' if unfed / span <= idle else 'UNFED ABOVE IDLE'})")
        host_lines = []
        everything = [(s, s + d) for _p, lines in planes for _l, evs in lines for _e, s, d in evs]
        first, last = min(a for a, _b in everything), max(b for _a, b in everything)
        for plane, lines in planes:
            if not plane.startswith("/host:"):
                continue
            for line, events in lines:
                mine = [ev for ev in events if ev[0] in tracer.ANNOTATED_SPANS]
                if mine:
                    counts = {}
                    for name, _s, _d in mine:
                        counts[name] = counts.get(name, 0) + 1
                    who = whose(counts)
                    print(f"host plane {plane!r} line {line!r} ({who}): {len(mine)} annotations: "
                          + ", ".join(f"{n} x{c}" for n, c in sorted(counts.items())))
                    if who in ("commit thread", "store thread"):
                        stretch_tiling(who, mine)
                        unnamed_stretches(mine, first, last)
                    host_lines.append((who, mine))
        if not reduced:
            continue
        for plane, lines in planes:
            if not plane.startswith(device_prefix):
                continue
            ops = dict(lines).get(reduce_trace.OPS_LINE, [])
            _covered, holes = reduce_trace.union_seconds([(s, s + d) for _e, s, d in ops])
            for a, b in sorted(holes, key=lambda h: h[0] - h[1])[:3]:
                print(f"gap of {(b - a) / 1e6:.2f} ms at {(a - min(s for _e, s, _d in ops)) / 1e6:.1f} ms:")
                for who, mine in host_lines:
                    cover = intervals_overlap(mine, a, b)
                    leaves = sorted(((ns, n) for n, ns in cover.items() if n not in PARENTS),
                                    reverse=True)[:3]
                    parents = [(cover[p], p) for p in PARENTS if p in cover]
                    named, _holes = reduce_trace.union_seconds(
                        [(max(a, s0), min(b, s0 + d)) for _n, s0, d in mine
                         if min(b, s0 + d) > max(a, s0)])
                    print(f"    {who}: "
                          + (", ".join(f"{n} {100 * ns / (b - a):.0f}%" for ns, n in leaves)
                             or "no annotation")
                          + (f" [inside {', '.join(f'{p} {100 * ns / (b - a):.0f}%' for ns, p in parents)}]"
                             if parents else "")
                          + f"; some annotation over {100 * named * 1e9 / (b - a):.0f}% of the gap")

if __name__ == "__main__":
    keep = sys.argv[1]
    prefix = sys.argv[2] if len(sys.argv) > 2 else "/device:TPU"
    docs = load(keep)
    tiling(docs)
    slices(keep, docs, prefix)
