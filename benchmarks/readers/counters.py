"""A ratio of counters, as DIFFERENCES between the two scrapes of the
server's `/metrics` page (see `spans.py`: the registry counts from process
start, so only a difference is a window's number).

A metric file with `"reader": "counters"` gives:

  count   the counters whose differences are added up
  per     the counter whose difference is the denominator; without it the
          value is the sum itself, a count of what happened in the window
  absent_is_zero  those of `count` that exist only once the thing has
          happened (a view change, a link to a peer that is never dialled):
          absent from the second page, such a one counts 0
  scale   multiplied in (default 1)

A counter absent from the second page, or a denominator that did not move
in the window, reads as nothing: the thing did not happen, and 0 would say
it happened and cost nothing. A counter absent from the FIRST page counts
from 0 (it was first bumped inside the window).
"""

from benchmarks.readers import spans

FAMILY = "tbtpu_events_total"


def read(spec: dict, ctx: dict):
    if "scrape_after" not in ctx:
        return None
    zero = set(spec.get("absent_is_zero", ()))
    counts = [spans.delta(ctx, FAMILY, e) for e in spec["count"]]
    counts = [0.0 if c is None and e in zero else c for c, e in zip(counts, spec["count"])]
    per = spans.delta(ctx, FAMILY, spec["per"]) if "per" in spec else 1.0
    if any(c is None for c in counts) or not per:
        return None
    return sum(counts) / per * float(spec.get("scale", 1.0))
