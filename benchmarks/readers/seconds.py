"""Span seconds spent between the two scrapes of `/metrics`, as a sum or
as a share of the window (see `spans.py` for why only differences count).

A metric file with `"reader": "seconds"` gives:

  events          the spans whose seconds are added up
  of_window       true: the sum over `ctx["window"]["seconds"]`, which
                  `run.py` sets before the metric loop; without a window
                  (or one of no length) there is no share, and none is
                  made up
  complement      true: 1 minus that share (the events are a thread's
                  WAITS: what is left is the share it was busy)
  absent_is_zero  the spans that exist only once the thing has happened
                  (a stall, a barrier, a compile): absent from the page,
                  such a one reads 0 seconds. Any other absent span is a
                  thread or a seam that never ran: nothing is returned
  scale           multiplied in: 100 for a percentage

What a share can and cannot say. A wait still in progress at a scrape is
not on the page yet (a span is recorded when it ends), and one in progress
at the first scrape is recorded whole when it ends: a share is off by at
most one wait at either end. The scrapes themselves lie a request's
length outside the window's two ends, tens of milliseconds of 40 s, while
the denominator is the window's own length: a thread that waits all the
time can read a little below 0% busy.
"""

from benchmarks.readers import spans

FAMILY = "tbtpu_span_seconds_sum"


def read(spec: dict, ctx: dict):
    if "scrape_after" not in ctx:
        return None
    zero = set(spec.get("absent_is_zero", ()))
    total = 0.0
    for event in spec["events"]:
        seconds = spans.delta(ctx, FAMILY, event)
        if seconds is None:
            if event not in zero:
                return None
            seconds = 0.0
        total += seconds
    if spec.get("of_window"):
        window = (ctx.get("window") or {}).get("seconds")
        if not window or window <= 0:
            return None
        total /= window
        if spec.get("complement"):
            total = 1.0 - total
    return total * float(spec.get("scale", 1.0))
