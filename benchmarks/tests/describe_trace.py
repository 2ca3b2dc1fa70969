#!/usr/bin/env python3
"""Look at a trace by hand: planes, lines, event counts, the commonest
names. `python3 benchmarks/tests/describe_trace.py <trace dir>`"""

import collections
import glob
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks import reduce_trace  # noqa: E402

for path in glob.glob(os.path.join(sys.argv[1], "**", "*.xplane.pb"), recursive=True):
    print(path, os.path.getsize(path), "bytes")
    for plane, lines in reduce_trace.read_planes(path):
        print("plane", repr(plane))
        for line, events in lines:
            names = collections.Counter(reduce_trace.module_name(e) for e, _s, _d in events)
            total = sum(d for _e, _s, d in events) / 1e9
            print(f"  line {line!r}: {len(events)} events, {total:.4f} s; "
                  + ", ".join(f"{n} x{c}" for n, c in names.most_common(6)))
