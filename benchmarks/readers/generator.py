"""What the load generator's own request records say of the window.

The server runs in cycles of a burst and a compaction storm (PERF.md,
section 4), and `tx_per_s` depends on how much of each the window holds.
These metrics record the phase, so that a change in `tx_per_s` can be
read for what it is. `"arithmetic"` is one of

  burst_tx_per_s     transfers answered from the window's start to the
                     storm's onset, over those seconds
  storm_start_batch  requests answered since the run's first, prefill
                     included, when the storm sets on
  storm_share_pct    share of the window's seconds behind the onset

The onset is the first reply of the window after which no reply came for
`gap_s` seconds (the metric file's: several of the burst's batch
periods). A window without such a gap has its onset at its end: the
burst's rate is then the window's, the share 0, and the batch the last
one the window answered (the storm starts later than that).
"""


def percentile(sorted_values: list, q: float) -> float:
    return sorted_values[min(len(sorted_values) - 1, int(len(sorted_values) * q))]


def onset(done: list, seconds: float, gap_s: float) -> tuple:
    """(seconds into the window, replies up to then) of the first reply
    followed by `gap_s` seconds without one; `done` is sorted, in seconds
    from the window's start."""
    for i, t in enumerate(done):
        following = done[i + 1] if i + 1 < len(done) else seconds
        if following - t > gap_s:
            return t, i + 1
    return seconds, len(done)


def read(spec: dict, ctx: dict):
    records, window = ctx.get("window_records"), ctx.get("window")
    if not records or not window:
        return None
    by_done = sorted(records, key=lambda r: r.done)
    at, n = onset([r.done - window["t0"] for r in by_done], window["seconds"],
                  float(spec["gap_s"]))
    how = spec["arithmetic"]
    if how == "burst_tx_per_s":
        return sum(r.events for r in by_done[:n]) / at if at > 0.0 else None
    if how == "storm_start_batch":
        return float(window["answered_before"] + n)
    if how == "storm_share_pct":
        return 100.0 * (1.0 - at / window["seconds"])
    raise ValueError(f"unknown generator arithmetic {how!r}")
