"""Host spans and counters, as a DIFFERENCE between two scrapes of the
server's `/metrics` page taken at the window's two ends. The registry
accumulates from process start, so a page's own means and percentiles
are never a window's numbers.

A metric file with `"reader": "spans"` gives:

  events      the spans whose seconds are summed
  per         the span or counter whose count is the denominator
              (default: the first of `events`)
  family      where `per` is counted: "span" (default) or "counter"
  absent_is_zero  a span that is recorded only when the thing happens (a
              stall): absent from the page, it reads 0 and not nothing
  weights     instead of `events`: {counter: weight}; the value is the
              weighted mean sum(weight * count) / sum(count) of the counters'
              differences (a histogram kept as one counter per bucket)
  scale       multiplied in: 1000 for ms; 1e9 where the registry holds a
              raw value as nanoseconds (pipeline.commit.inflight_depth)
"""

import re

LINE = re.compile(r'^(tbtpu_\w+)\{event="([^"]*)"\} (\S+)$')


def parse(text: str) -> dict:
    """{family: {event: value}} of a /metrics page."""
    out = {}
    for line in text.splitlines():
        m = LINE.match(line)
        if m:
            out.setdefault(m.group(1), {})[m.group(2)] = float(m.group(3))
    return out


def delta(ctx: dict, family: str, event: str) -> float:
    before = ctx["scrape_before"].get(family, {}).get(event, 0.0)
    after = ctx["scrape_after"].get(family, {}).get(event)
    return None if after is None else after - before


def read(spec: dict, ctx: dict):
    if "scrape_after" not in ctx:
        return None
    if "weights" in spec:
        counts = {e: delta(ctx, "tbtpu_events_total", e) or 0.0 for e in spec["weights"]}
        total = sum(counts.values())
        if not total:
            return None
        return sum(spec["weights"][e] * c for e, c in counts.items()) / total
    sums = [delta(ctx, "tbtpu_span_seconds_sum", e) for e in spec["events"]]
    if spec.get("absent_is_zero"):
        sums = [0.0 if s is None else s for s in sums]
    per = spec.get("per", spec["events"][0])
    family = ("tbtpu_events_total" if spec.get("family") == "counter"
              else "tbtpu_span_seconds_count")
    count = delta(ctx, family, per)
    if any(s is None for s in sums) or not count:
        return None
    return sum(sums) / count * float(spec.get("scale", 1.0))
