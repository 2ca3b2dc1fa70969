#!/usr/bin/env python3
"""One traced run of a cell ON THE CHIP with the trace kept and the threads
told apart, then `attribute_gaps.py` over what was kept: the acceptance
readings of PR 25 (tiling per thread, unfed against idle per slice, the
annotations on the host plane, the gaps' attribution). The benchmark's own
runs never run this.

    chiprun -- python3 benchmarks/tests/tiling_on_chip.py --workload <cell> --seed <n>
"""

import argparse
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    args = ap.parse_args()
    keep = os.path.join(REPO, ".bench_keep", args.workload)
    os.environ["BENCH_KEEP_DIR"] = keep
    from benchmarks import run

    rc = run.run_cell(args.workload, args.seed, args.seconds, True,
                      child=os.path.join(HERE, "tiled_serve.py"))
    out = subprocess.run([sys.executable, os.path.join(HERE, "attribute_gaps.py"), keep],
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    return rc or out.returncode


if __name__ == "__main__":
    sys.exit(main())
