#!/usr/bin/env python3
"""Record the small trace test_reduce_trace.py checks the reduction on:
a toy program on the chip, three calls each of two jitted functions with
idle sleeps between them. Run on the chip; writes the trace under
chiprun_out/small_trace/ and prints what reduce_trace.py makes of it.

    chiprun -- python3 benchmarks/tests/record_trace.py
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks import reduce_trace

    @jax.jit
    def toy_matmul(x):
        return (x @ x).sum()

    @jax.jit
    def toy_scan(x):
        return jnp.cumsum(x, axis=0).max()

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    toy_matmul(x).block_until_ready()
    toy_scan(x).block_until_ready()
    out = os.path.join(REPO, "chiprun_out", "small_trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=options)
    for _ in range(3):
        toy_matmul(x).block_until_ready()
        time.sleep(0.02)
        toy_scan(x).block_until_ready()
        time.sleep(0.03)
    jax.profiler.stop_trace()
    print(json.dumps(reduce_trace.reduce_dir(out), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
