"""`benchmarks/serve.py`, which also writes down what only the process that
runs the program can see, for `tiling_on_chip.py`:

- what EACH THREAD recorded (`tracer.by_thread()`; the `/metrics` page adds
  the threads up) at every `compiles` request, which the harness makes at
  the window's two ends, and at every trace slice's two ends;
- every trace slice, copied aside before the harness deletes its directory.

All of it goes under BENCH_KEEP_DIR. The program is not touched: the hooks
are on this child's own `answer` and on `jax.profiler`'s start and stop.
"""

import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, os.path.dirname(HERE))

KEEP = os.environ["BENCH_KEEP_DIR"]
taken = []


def snapshot(tag: str) -> None:
    from tigerbeetle_tpu import tracer

    doc = {"tag": tag, "perf_ns": time.perf_counter_ns(),
           "threads": {name: {event: list(v) for event, v in spans.items()}
                       for name, spans in tracer.by_thread().items()}}
    with open(os.path.join(KEEP, f"threads_{len(taken):02d}_{tag}.json"), "w") as f:
        json.dump(doc, f)
    taken.append(tag)


def main(argv) -> int:
    import jax.profiler

    from benchmarks import serve

    os.makedirs(KEEP, exist_ok=True)
    answer, start, stop = serve.answer, jax.profiler.start_trace, jax.profiler.stop_trace
    slices = []

    def answer_and_note(request, obj):
        if request == "compiles":
            snapshot("window")
        answer(request, obj)

    def start_and_note(log_dir, **kw):
        start(log_dir, **kw)
        slices.append(log_dir)
        snapshot("slice_start")

    def stop_and_keep():
        snapshot("slice_stop")
        stop()
        shutil.copytree(slices[-1], os.path.join(KEEP, "trace", str(len(slices) - 1)))

    serve.answer = answer_and_note
    jax.profiler.start_trace, jax.profiler.stop_trace = start_and_note, stop_and_keep
    return serve.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
