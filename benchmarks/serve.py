"""The child that holds the chip: `tigerbeetle_tpu.cli start`, unchanged,
with the three things only the process that owns the device can do.

    python3 benchmarks/serve.py <cli start arguments...>

- It counts compilations: `jax.monitoring` listeners on the backend-
  compile duration (which fires for a compile and for a read from the
  persistent cache alike: either means a shape the process had not seen).
- It traces the device on request: `jax.profiler.start_trace` /
  `stop_trace`, called from a thread of its own, because the program has
  no profiler call.
- It reads the device's peak memory.

The parent asks over this process's stdin, one line each, and is answered
on stdout by one line that starts with `BENCH ` and carries JSON:

    compiles            -> BENCH {"re": "compiles", "compiles": n, "cache_hits": m, "seconds": s,
                                  "names": [[program, when ready, seconds, "read"|"compiled"], ...
                                            the last 256],
                                  "started": when this process began, "imported": when it had
                                            imported JAX and the program}
    memory              -> BENCH {"re": "memory", "memory_peak_bytes": n}
    cpu                 -> BENCH {"re": "cpu", "seconds": s}   processor time this process has had, all threads
    trace_start <dir>   -> BENCH {"re": "trace_start", "seconds": s}
    trace_stop          -> BENCH {"re": "trace_stop", "seconds": s}

Stopping a trace collects and writes it, which can take many seconds; it
runs on a thread of its own so that the other requests are still served.
With nothing asked the wrapper costs one thread blocked on a read.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time

T_STARTED = time.perf_counter()  # the machine's clock: the parent's too
T_IMPORTED = [T_STARTED]  # when JAX and the program were imported

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class Monitor(logging.Handler):
    """Counts compilations (the listener) and keeps their names (JAX logs
    "Finished XLA compilation of <name>" at debug level: this handler reads
    that one message and lets nothing through to the server's stderr)."""

    def __init__(self) -> None:
        super().__init__(logging.DEBUG)
        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0
        self.names = []  # [program, when it was ready, seconds it took, "read" or "compiled"]
        self.reading = set()  # threads whose compile in progress was found in the cache
        self.mutex = threading.Lock()  # (logging.Handler owns `lock`)

    def emit(self, record: logging.LogRecord) -> None:
        message = record.getMessage()
        if message.startswith("Finished XLA compilation of "):
            words = message.split()
            me = threading.get_ident()
            with self.mutex:  # perf_counter is the machine's: the parent's clock too
                self.names.append([words[4], time.perf_counter(), float(words[6]),
                                   "read" if me in self.reading else "compiled"])
                self.reading.discard(me)

    def on_duration(self, event: str, seconds: float, **_kw) -> None:
        with self.mutex:
            if event == COMPILE_EVENT:
                self.compiles += 1
                self.seconds += seconds
            elif event == CACHE_HIT_EVENT:  # comes first, on the compiling thread
                self.cache_hits += 1
                self.reading.add(threading.get_ident())


def answer(request: str, obj: dict) -> None:
    sys.stdout.write("BENCH " + json.dumps({"re": request, **obj}) + "\n")
    sys.stdout.flush()


def stop_trace() -> None:
    import jax

    t = time.perf_counter()
    try:
        jax.profiler.stop_trace()
        answer("trace_stop", {"seconds": time.perf_counter() - t})
    except Exception as e:  # noqa: BLE001 — the parent decides what a failed request means
        answer("trace_stop", {"error": repr(e)})


def control(monitor: Monitor) -> None:
    """Serve the parent's requests until it closes the pipe."""
    import jax

    for line in sys.stdin:
        words = line.split()
        if not words:
            continue
        try:
            if words[0] == "compiles":
                with monitor.mutex:
                    answer("compiles", {"compiles": monitor.compiles,
                                        "cache_hits": monitor.cache_hits,
                                        "seconds": monitor.seconds,
                                        "names": monitor.names[-256:],
                                        "started": T_STARTED, "imported": T_IMPORTED[0]})
            elif words[0] == "memory":
                answer("memory", {"memory_peak_bytes": max(
                    (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in jax.local_devices())})
            elif words[0] == "cpu":
                answer("cpu", {"seconds": time.process_time()})
            elif words[0] == "trace_start":
                # Device events and the runtime's own host spans only: the
                # Python tracer would record every call of a busy server.
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 1
                t = time.perf_counter()
                jax.profiler.start_trace(words[1], profiler_options=options)
                answer("trace_start", {"seconds": time.perf_counter() - t})
            elif words[0] == "trace_stop":
                threading.Thread(target=stop_trace, daemon=True).start()
            else:
                answer(words[0], {"error": "unknown request"})
        except Exception as e:  # noqa: BLE001 — the parent decides what a failed request means
            answer(words[0], {"error": repr(e)})


def main(argv) -> int:
    sys.path.insert(0, REPO)
    import jax.monitoring

    from tigerbeetle_tpu import cli

    monitor = Monitor()
    jax.monitoring.register_event_duration_secs_listener(monitor.on_duration)
    dispatch_log = logging.getLogger("jax._src.dispatch")
    dispatch_log.addHandler(monitor)
    dispatch_log.setLevel(logging.DEBUG)
    dispatch_log.propagate = False
    T_IMPORTED[0] = time.perf_counter()
    threading.Thread(target=control, args=(monitor,), daemon=True).start()
    return cli.main(["start", *argv])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
