#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chip, from the client's side.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run: build the C shims, format a data file in a temporary directory,
start the server (`benchmarks/serve.py`, which is `cli.py start` unchanged
plus a profiler and a compile counter), register the accounts, start the
cell's client sessions and let them run the cell's own traffic as prefill;
cut the window out of the running system; drain; read every balance and a
sample of the window's transfers back; stop the server; replay every
answered request through the plain reference (`benchmarks/reference.py`)
and compare. The last line of stdout is the result.

With `--trace 0` the server runs without its tracer and the metrics are
the end-to-end ones. With `--trace 1` the server serves `/metrics`, which
is scraped at the window's two ends, the device is traced for some
seconds in the middle of the window, and the metrics are the per-layer
ones. Everything that belongs to one configuration, one traffic mix or
one per-layer metric is a file this harness finds by the name in
BENCHMARK.json: see `configs/`, `traffic/`, `generators/`,
`layer_metrics/`, `readers/`, `needed_work/`.

This process never imports JAX: the chip belongs to the server, and the
device is the one the server names on its `listening` line. Anything but
the TPUs the cell asks for is a failure: no result line, exit code 1.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import urllib.request  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(REPO, "benchmarks")
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

# Of device trace in all. The store thread's merges run some 600,000 device
# operations a second: collecting and writing 3 s of them took 187 s, 1 s of
# them 45 to 67 s (my chip runs, PR 24); no run can afford more.
TRACE_SECONDS = 1.0
READ_BACK_BATCHES = 8  # of the window's batches read back by id, the last among them
TRACE_STOP_TIMEOUT_S = 240.0  # collecting and writing the trace
PHASE_GAP_S = 0.5  # the line every run prints of its phase; the metric files have their own
DRAIN_TIMEOUT_S = 300.0  # past the window's close, for the requests in flight: late is not wrong
REQUEST_TIMEOUT_S = 900.0  # one replica never drops a request; a cold compile is long
DEADLINE_S = 1150.0  # a first run in a checkout compiles; the watchdog ends anything longer


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(workload: str) -> tuple:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")
    if traffic.get("loop", "closed") != "closed":
        raise SystemExit(f"traffic {cell['traffic']!r}: only the closed loop is built")
    return manifest, cell, config, traffic


def scrape(port: int) -> str:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=60) as r:
        return r.read().decode()


# --- the load: prefill, window, drain ------------------------------------------


async def drive(load, traffic: dict, seconds: float, trace_dir, server, mport, ctx: dict):
    """Returns (t0, t1): the window's two ends on this process's clock."""
    from benchmarks.readers import spans

    loop = asyncio.get_running_loop()
    await load.start()
    await load.until_completed(int(traffic["prefill_batches"]))
    if load.errors:
        return 0.0, 0.0
    # The sessions do not pause: the window is cut out of a running system.
    ctx["monitor_before"] = await loop.run_in_executor(None, server.ask, "compiles")
    if mport:
        ctx["scrape_before"] = spans.parse(await loop.run_in_executor(None, scrape, mport))
    t0 = time.perf_counter()
    tracing = asyncio.ensure_future(
        trace_slices(loop, server, trace_dir, traffic, seconds, t0, ctx))
    await asyncio.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    t1 = t0 + seconds
    ctx["monitor_after"] = await loop.run_in_executor(None, server.ask, "compiles")
    if mport:
        ctx["scrape_after"] = spans.parse(await loop.run_in_executor(None, scrape, mport))
    # An answer that comes late is late, not wrong: wait for it, minutes if a
    # first run in a checkout compiles a store shape just then.
    await load.drain(timeout=DRAIN_TIMEOUT_S)
    await tracing  # the trace may take longer to write than the window lasts
    return t0, t1


async def trace_slices(loop, server, trace_dir, traffic: dict, seconds: float, t0: float,
                       ctx: dict) -> None:
    """TRACE_SECONDS of device trace in all, in equal slices that begin
    `trace_at_s` seconds into the window (the traffic file's list: a mix
    whose phases differ is traced in each; default: one slice around the
    middle). Stopping a slice collects and writes it, which takes longer
    than the slice: one that would no longer fit into the window is left out."""
    if not trace_dir:
        return
    starts = traffic.get("trace_at_s") or [(seconds - TRACE_SECONDS) / 2]
    length = TRACE_SECONDS / len(starts)
    ctx["trace_slices"] = []
    for i, at in enumerate(starts):
        wait = max(0.0, t0 + at - time.perf_counter())
        if time.perf_counter() + wait + length > t0 + seconds:
            break
        await asyncio.sleep(wait)
        began = time.perf_counter() - t0
        start = await loop.run_in_executor(
            None, server.ask, f"trace_start {os.path.join(trace_dir, str(i))}")
        await asyncio.sleep(length)
        stop = await loop.run_in_executor(None, server.ask, "trace_stop", TRACE_STOP_TIMEOUT_S)
        ctx["trace_slices"].append(
            f"at {began:.1f} s (start {start['seconds']:.1f} s, stop {stop['seconds']:.1f} s)")


# --- correctness: the plain reference against what was served ---------------------


def no_timestamp(records: np.ndarray) -> np.ndarray:
    out = np.array(records)
    out["timestamp"] = 0
    return out


def rows_differing(got: np.ndarray, want: np.ndarray) -> int:
    """Records that differ, byte for byte; a missing or extra one counts."""
    k = min(len(got), len(want))
    a = np.frombuffer(got[:k].tobytes(), np.uint8).reshape(k, -1)
    b = np.frombuffer(want[:k].tobytes(), np.uint8).reshape(k, -1)
    return int((a != b).any(axis=1).sum()) + abs(len(got) - len(want))


def compare(generator, ledger, records: list, sample: set, read_back: dict,
            accounts_got: list) -> dict:
    """Replay every answered request through the reference, each session
    in its own order, and count what differs from what was served."""
    from benchmarks.reference import RESULT

    code_events = code_mismatches = stored_compared = store_mismatches = 0
    mismatched_requests = {}
    for accounts in generator.account_batches():
        if len(ledger.create_accounts(accounts)):
            raise ValueError("the generator's accounts are not all valid")
    for rec in sorted(records, key=lambda r: (r.session, r.seq)):
        if rec.reply is None:
            continue  # never answered: counted by the caller, nothing to replay
        events = generator.batch(rec.session, rec.seq)
        want, stored = ledger.create_transfers(events)
        got = np.frombuffer(rec.reply, dtype=RESULT)
        code_events += len(events)
        if got.tobytes() != want.tobytes():
            bad = len(np.setxor1d(got.view(np.uint64), want.view(np.uint64)))
            code_mismatches += bad
            mismatched_requests[(rec.session, rec.seq)] = bad
        if (rec.session, rec.seq) in sample:
            served = no_timestamp(read_back[(rec.session, rec.seq)])
            stored_compared += len(stored)
            store_mismatches += rows_differing(served, stored)
    balance_mismatches = accounts_compared = 0
    for ids, got in accounts_got:
        want = ledger.lookup_accounts(ids)
        accounts_compared += len(want)
        balance_mismatches += rows_differing(no_timestamp(got), want)
    return {
        "code_mismatches": code_mismatches, "code_events_compared": code_events,
        "balance_mismatches": balance_mismatches, "accounts_compared": accounts_compared,
        "store_mismatches": store_mismatches, "transfers_read_back": stored_compared,
        "mismatched_requests": mismatched_requests,
    }


# --- one run ---------------------------------------------------------------------------


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             expect=None, overrides=None, child=None, device_prefix="/device:TPU") -> int:
    """`expect`, `overrides`, `child` and `device_prefix` are for the
    rehearsals, controls and fault tests under benchmarks/tests/, which
    have to drive this whole run on a CPU at a tiny size, or against a
    server broken on purpose; the command line has no switch for them."""
    from benchmarks import launch
    from benchmarks.launch import Failure, say
    from benchmarks.readers import spans
    from benchmarks.readers.generator import onset, percentile
    from benchmarks.reference import Ledger
    from benchmarks.sessions import Load

    manifest, cell, config, traffic = load_cell(workload)
    config = {**config, **(overrides or {}).get("config", {})}
    traffic = {**traffic, **(overrides or {}).get("traffic", {})}
    expect = expect or launch.require_tpu
    peaks_table = load_json("peaks.json")

    launch.load_shims()
    from tigerbeetle_tpu.client import Client

    workdir = tempfile.mkdtemp(prefix="tbtpu_bench_")
    server = launch.Server(workdir, DEADLINE_S, child or launch.SERVE)
    try:
        path = os.path.join(workdir, "0.tigerbeetle")
        launch.format_file(path, config["start"]["config"])
        port, mport = launch.free_ports(2)
        args = [f"--addresses=127.0.0.1:{port}", "--replica=0",
                f"--config={config['start']['config']}",
                f"--backend={config['start']['backend']}"]
        if trace:
            args.append(f"--metrics-port={mport}")  # the tracer is on only in the traced run
        device = server.start([*args, path])
        listening_s = time.perf_counter() - T_PROCESS_START
        tag = (f"[{device['platform']} {device['device_kind']!r} x{device['device_count']}]")
        say(f"{tag} {workload} seed {seed}: listening after {listening_s:.1f} s; "
            f"durable writes: {launch.durable_mode(path)}")
        expect(device, cell["chips"])
        if device["device_kind"] not in peaks_table and device["platform"] == "tpu":
            raise Failure(f"no peaks for device kind {device['device_kind']!r} "
                          "in benchmarks/peaks.json")

        generator = importlib.import_module(
            "benchmarks.generators." + traffic["generator"]).Generator(config, traffic, seed)
        Client.REQUEST_TIMEOUT = REQUEST_TIMEOUT_S
        client = Client([("127.0.0.1", port)])
        t = time.perf_counter()
        for acc in generator.account_batches():
            got = client.create_accounts(acc)
            if len(got):
                raise Failure(f"create_accounts answered {len(got)} failures")
        say(f"{tag} {config['accounts']:,} accounts registered in "
            f"{time.perf_counter() - t:.1f} s")

        load = Load(("127.0.0.1", port), int(traffic["sessions"]), generator.batch,
                    REQUEST_TIMEOUT_S)
        ctx = {"config": config, "traffic": traffic,
               "peaks": peaks_table.get(device["device_kind"], {})}
        trace_dir = os.path.join(workdir, "trace") if trace else None
        t0, t1 = asyncio.run(drive(load, traffic, seconds, trace_dir, server,
                                   mport if trace else 0, ctx))
        if load.errors and t1 == 0.0:
            raise Failure("a session gave up during prefill: " + "; ".join(load.errors[:3]))
        setup_s = t0 - T_PROCESS_START
        memory = server.ask("memory")

        records = load.records
        answered = [r for r in records if r.reply is not None]
        window = [r for r in answered if t0 <= r.done < t1]
        lost = [r for r in records if r.reply is None and r.sent > 0.0]
        prefill = [r for r in answered if r.done < t0]
        say(f"{tag} prefill {len(prefill)} batches ({sum(r.events for r in prefill):,} "
            f"transfers) in {t0 - min(r.sent for r in records):.1f} s; set-up {setup_s:.1f} s; "
            f"window {seconds:g} s: {len(window)} requests answered, {len(lost)} never; "
            f"{sum(x.resends for x in load.sessions)} resent, "
            f"{sum(x.busy for x in load.sessions)} answered BUSY")

        # Read back: every balance, and a sample of the window's batches by id.
        rng = np.random.default_rng([seed, 3])
        by_done = sorted(window, key=lambda r: r.done)
        picks = set(rng.choice(len(by_done), min(READ_BACK_BATCHES, len(by_done)),
                               replace=False).tolist()) | ({len(by_done) - 1} if by_done else set())
        sample = {(by_done[i].session, by_done[i].seq) for i in picks}
        t = time.perf_counter()
        read_back = {(s, k): client.lookup_transfers(generator.ids(s, k))
                     for s, k in sorted(sample)}
        transfers_s = time.perf_counter() - t
        n_batch = int(config["batch"])
        accounts_got = []
        for start in range(1, int(config["accounts"]) + 1, n_batch):
            ids = np.arange(start, min(start + n_batch, int(config["accounts"]) + 1),
                            dtype=np.uint64)
            accounts_got.append((ids, client.lookup_accounts([int(v) for v in ids])))
        read_back_s = time.perf_counter() - t
        client.close()
        server.stop()  # the chip is free from here on

        t = time.perf_counter()
        verdict = compare(generator, Ledger(int(config["accounts"])), records, sample,
                          read_back, accounts_got)
        reference_s = time.perf_counter() - t
        transfers_issued = sum(r.events for r in records)
        say(f"{tag} read back in {read_back_s:.1f} s ({transfers_s:.1f} s of it the "
            f"{len(sample)} batches of transfers), reference replay in {reference_s:.1f} s; "
            f"{transfers_issued:,} transfers issued in all "
            f"({100.0 * transfers_issued / int(config['transfers_max']):.0f}% of transfers_max)")

        # The end-to-end numbers, over all the work and all the time of the window.
        in_window = {(r.session, r.seq) for r in window}
        bad_in_window = sum(n for key, n in verdict["mismatched_requests"].items()
                            if key in in_window)
        attempted = sum(r.events for r in window) + sum(r.events for r in lost)
        failed = bad_in_window + sum(r.events for r in lost)
        drained_at = max([r.done for r in answered] + [t1])
        latencies = sorted([r.latency * 1e3 for r in window]
                           + [(drained_at - r.sent) * 1e3 for r in lost])
        say(f"{tag} write latency over {len(latencies)} requests of the window "
            f"(p95 has {len(latencies) - int(len(latencies) * 0.95)} beyond it)")
        if not window:
            raise Failure("no request was answered inside the window")
        # Which phase of the store's cycle the window held (PERF.md, section 4).
        at, n = onset([r.done - t0 for r in by_done], seconds, PHASE_GAP_S)
        burst_rate = sum(r.events for r in by_done[:n]) / max(at, 1e-9)
        say(f"{tag} phase: burst of {n} requests in {at:.1f} s ({burst_rate:,.0f} tx/s), then "
            f"{len(window) - n} in {seconds - at:.1f} s; "
            + (f"the first {PHASE_GAP_S:g} s without a reply came after batch "
               f"{len(prefill) + n} of the run" if at < seconds else
               f"no {PHASE_GAP_S:g} s without a reply up to batch {len(prefill) + n} of the run"))
        end_to_end = {
            "tx_per_s": sum(r.events for r in window) / seconds,
            "write_p50_ms": percentile(latencies, 0.50),
            "write_p95_ms": percentile(latencies, 0.95),
            "setup_s": setup_s,
        }
        ctx["window_records"] = window
        ctx["window"] = {"t0": t0, "seconds": seconds, "answered_before": len(prefill)}

        device_out = {"platform": device["platform"], "kind": device["device_kind"],
                      "count": device["device_count"],
                      "memory_peak_bytes": int(memory["memory_peak_bytes"])}
        result = {"correct": None, "attempted": attempted, "failed": failed}
        problems = []
        if trace:
            out = subprocess.run(
                [sys.executable, os.path.join(HERE, "reduce_trace.py"), trace_dir,
                 device_prefix], env={**os.environ, "JAX_PLATFORMS": "cpu"},
                capture_output=True, text=True, timeout=300)
            reduced = json.loads(out.stdout.strip().splitlines()[-1]) if out.returncode == 0 else {}
            if not reduced:
                problems.append("the device trace is missing or holds no device plane: "
                                + out.stderr[-500:])
            ctx["trace"] = reduced
            routes = {r: spans.delta(ctx, "tbtpu_events_total", f"sm.route.{r}_batches") or 0
                      for r in ("fast", "exact", "serial", "bail")}
            say(f"{tag} {TRACE_SECONDS:g} s of device trace in slices "
                + ", ".join(ctx["trace_slices"]))
            say(f"{tag} commit routes in the window: {routes}")
            if routes["bail"] > 0:
                problems.append(f"sm.route.bail_batches rose by {routes['bail']} in the window")
            metrics = {}
            for m in manifest["per_layer"]:
                if workload not in m.get("workloads", [workload]):
                    continue
                spec = load_json("layer_metrics", m["name"] + ".json")
                value = importlib.import_module(
                    "benchmarks.readers." + spec["reader"]).read(spec, ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            if reduced:
                device_out["busy_s"] = reduced["busy_s"]
                device_out["window_s"] = reduced["window_s"]
                result["breakdown"] = {"device_ops": reduced["device_ops"],
                                       "idle_gaps": reduced["idle_gaps"]}
        else:
            # Those of the harness's end-to-end numbers that the manifest lists for this cell.
            metrics = {m["name"]: {"value": end_to_end[m["name"]], "unit": m["unit"]}
                       for m in manifest["end_to_end"]
                       if workload in m.get("workloads", [workload])}
        before, after = ctx["monitor_before"], ctx["monitor_after"]
        compiled_in_window = [f"{name} {how} in {took:.1f} s at {t - t0:.1f} s"
                              for name, t, took, how in after["names"] if t0 <= t < t1]
        say(f"{tag} programs compiled or read from the compile cache: {before['compiles']} in "
            f"set-up ({before['cache_hits']} of them cache reads, {before['seconds']:.1f} s), "
            f"{after['compiles'] - before['compiles']} in the window {compiled_in_window}")
        say(f"{tag} end to end: " + ", ".join(f"{k} {v:.4f}" for k, v in end_to_end.items()))

        # Each number compared, beside its limit (every comparison is exact: limit 0).
        compared = {
            "code_mismatches": [verdict["code_mismatches"], 0],
            "balance_mismatches": [verdict["balance_mismatches"], 0],
            "store_mismatches": [verdict["store_mismatches"], 0],
            "requests_never_answered": [len(lost) + len(load.errors), 0],
            "code_events_compared": [verdict["code_events_compared"], None],
            "accounts_compared": [verdict["accounts_compared"], None],
            "transfers_read_back": [verdict["transfers_read_back"], None],
        }
        correct = all(v == limit for v, limit in compared.values() if limit is not None)
        if problems:
            raise Failure("; ".join(problems))
        result.update(correct=correct, metrics=metrics, device=device_out, compared=compared)
        for name, (value, limit) in compared.items():
            say(f"compared {name}: {value}" + (f" (limit {limit})" if limit is not None else ""))
        print(json.dumps(result), flush=True)
        return 0
    except Failure as e:
        say(f"FAIL: {e}")
        say(f"--- the server's stderr (its end):\n{server.stderr_tail()}")
        return 1
    finally:
        server.stop()
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    return run_cell(args.workload, args.seed, args.seconds, bool(args.trace))


if __name__ == "__main__":
    sys.exit(main())
