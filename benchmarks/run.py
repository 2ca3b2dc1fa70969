#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chip, from the client's side.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run: build the C shims, format a data file in a temporary directory,
start the server (`benchmarks/serve.py`, which is `cli.py start` unchanged
plus a profiler and a compile counter), register the accounts, start the
cell's client sessions and let them run the cell's own traffic as prefill;
cut the window out of the running system; drain; read every balance and a
sample of the window's transfers back; stop the server; replay every
answered request through the plain reference (`benchmarks/reference.py`),
in the order the server committed them (the `op` of each reply's header:
where a balance check can fail, the answers depend on it), and compare.
The last line of stdout is the result.

A request is an operation and a body, as the cell's generator names them
(`request(session, seq)`; a generator that has only `batch` sends
`create_transfers` of its batches): writes, and since PR 37 reads
(`lookup_accounts`) among them. A read is answered by the reference at its
own place in the commit order and compared row for row. The rate and the
write latencies are taken over the writes alone.

A configuration whose `replica_count` is n > 1 gets what it states: n data
files, n servers in parallel, each on a chip of its own, sessions that
find the primary. Its read-back has a second half: the primary is killed
(SIGKILL) and every balance and the sample are read again from the
replicas that are left, which must answer in a later view; those rows are
the ones compared. The traced run scrapes every replica's `/metrics`; the
device trace and the metrics that name no `page` are the primary's.

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
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
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
# (a cluster drops what a backup is sent: its configuration gives `client_timeout_s`)
SURVIVOR_TIMEOUT_S = 10.0  # after the kill everything is compiled and the dead address refuses
ELECTION_PROBE_S = 1.0  # a try at registering with a cluster: how long it had no primary, to the second
ELECTION_TIMEOUT_S = 120.0  # no primary by then: the run fails
PIPELINE_SLACK = 8  # prepares in flight at a scrape: a page is not a snapshot
DEADLINE_S = 1150.0  # a first run in a checkout compiles; the watchdog ends anything longer
WRITE, READ = "create_transfers", "lookup_accounts"  # what a generator may name (sessions.OPERATIONS)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(workload: str, manifest=None) -> tuple:
    """`manifest`: BENCHMARK.json as a rehearsal or test under
    benchmarks/tests/ hands it in (with a cell the file does not hold
    yet); the command line has none."""
    if manifest is None:
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


def scrape_all(mports: list) -> list:
    """Every replica's page, parsed, in replica order."""
    from benchmarks.readers import spans

    return [spans.parse(scrape(p)) for p in mports]


def cpu_seconds(servers: list) -> list:
    """Processor time each server has had so far (all its threads; the
    child's own `time.process_time()`), in replica order, and this
    process's, the load generator's, last. (`/proc/stat` reads 0 on the
    chip's machine, a sandbox: the processes are asked themselves.)"""
    return [s.ask("cpu")["seconds"] for s in servers] + [time.process_time()]


def read_metric(spec: dict, ctx: dict):
    """One per-layer metric on the page its file names: `primary` (the
    default: the replica whose chip is traced; the only one a one-replica
    cell has), `backups_max` (the larger of the other replicas' readings)
    or `all_sum`."""
    reader = importlib.import_module("benchmarks.readers." + spec["reader"])
    page = spec.get("page", "primary")
    if page == "primary":
        return reader.read(spec, ctx)
    pages = [(b, a) for i, (b, a) in enumerate(zip(ctx.get("pages_before", []),
                                                   ctx.get("pages_after", [])))
             if page == "all_sum" or i != ctx["primary"]]
    values = [reader.read(spec, {**ctx, "scrape_before": b, "scrape_after": a})
              for b, a in pages]
    values = [v for v in values if v is not None]
    if not values:
        return None
    return {"backups_max": max, "all_sum": sum}[page](values)


# --- the load: prefill, window, drain ------------------------------------------


async def drive(load, traffic: dict, seconds: float, trace_dir, servers, mports, ctx: dict):
    """Returns (t0, t1): the window's two ends on this process's clock.
    `mports`: every replica's metrics port in the traced run, else none."""
    loop = asyncio.get_running_loop()
    await load.start()
    await load.until_completed(int(traffic["prefill_batches"]))
    if load.errors:
        return 0.0, 0.0
    # The primary is the replica that answers the sessions (of one replica: that one).
    ctx["primary"] = primary = load.primary()
    server = servers[primary]
    # The sessions do not pause: the window is cut out of a running system.
    ctx["monitor_before"] = await loop.run_in_executor(None, server.ask, "compiles")
    if mports:
        ctx["pages_before"] = await loop.run_in_executor(None, scrape_all, mports)
        ctx["scrape_before"] = ctx["pages_before"][primary]
    ctx["cpu_before"] = await loop.run_in_executor(None, cpu_seconds, servers)
    t0 = time.perf_counter()
    tracing = asyncio.ensure_future(
        trace_slices(loop, server, trace_dir, traffic, seconds, t0, ctx))
    await asyncio.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    t1 = t0 + seconds
    ctx["cpu_after"] = await loop.run_in_executor(None, cpu_seconds, servers)
    ctx["monitor_after"] = await loop.run_in_executor(None, server.ask, "compiles")
    if mports:
        ctx["pages_after"] = await loop.run_in_executor(None, scrape_all, mports)
        ctx["scrape_after"] = ctx["pages_after"][primary]
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


def requests_of(generator) -> tuple:
    """(request, transfer_ids) of a generator: `request(session, seq)` gives
    that request as (operation, body); `transfer_ids(session, seq)` the ids
    of a create_transfers request, for the read-back. A generator with no
    `request` of its own sends its batches and nothing else."""
    if hasattr(generator, "request"):
        return generator.request, lambda s, seq: generator.request(s, seq)[1]["id_lo"].tolist()
    return (lambda s, seq: (WRITE, generator.batch(s, seq))), generator.ids


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
    """Replay every answered request through the reference, in the order
    the server committed them (each reply's `op`), and count what differs
    from what was served: a write's result codes (and, of the sample, its
    stored rows), a read's rows, which the reference answers at the read's
    own place in that order. Three numbers hold that order to what it must be
    (the op is the program's own word): no two answered requests share an
    op, every session's own order is its op order (one request in flight a
    session), and, across sessions, no request whose reply had arrived
    before another was first sent stands behind it in op order (strict
    serializability's real-time half, on this process's one clock)."""
    from benchmarks.reference import ACCOUNT, RESULT

    code_events = code_mismatches = stored_compared = store_mismatches = 0
    read_rows = read_mismatches = 0
    mismatched_requests = {}
    request, _ids = requests_of(generator)
    for accounts in generator.account_batches():
        if len(ledger.create_accounts(accounts)):
            raise ValueError("the generator's accounts are not all valid")
    # (never answered: counted by the caller, nothing to replay)
    answered = sorted((r for r in records if r.reply is not None),
                      key=lambda r: (r.op, r.session, r.seq))
    replies_sharing_an_op = len(answered) - len({r.op for r in answered})
    last_op, session_order_violations = {}, 0
    for rec in sorted(answered, key=lambda r: (r.session, r.seq)):
        session_order_violations += rec.op <= last_op.get(rec.session, -1)
        last_op[rec.session] = rec.op
    latest_sent, realtime_order_violations = 0.0, 0
    for rec in answered:  # in op order: was any request before it sent after its reply?
        realtime_order_violations += rec.done < latest_sent
        latest_sent = max(latest_sent, rec.sent)
    for rec in answered:
        operation, events = request(rec.session, rec.seq)
        if operation == READ:
            want = ledger.lookup_accounts(events)
            bad = rows_differing(no_timestamp(np.frombuffer(rec.reply, dtype=ACCOUNT)), want)
            read_rows += len(want)
            read_mismatches += bad
            if bad:
                mismatched_requests[(rec.session, rec.seq)] = bad
            continue
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
        "replies_sharing_an_op": replies_sharing_an_op,
        "session_order_violations": session_order_violations,
        "realtime_order_violations": realtime_order_violations,
        "read_mismatches": read_mismatches, "read_rows_compared": read_rows,
    }


def cluster_problems(ctx: dict, replicas: int, tag: str) -> list:
    """What a traced run can show of "a reply is sent only after its prepare
    is durable in the WAL of a replication quorum": on the primary's page,
    the quorum completions over all peers (its own self-ack among them) rise
    by as many as the commits do, and `vsr.replication.lag` (one sample per
    BACKUP's ack) takes at least as many samples: a completion is the second
    of three possible acks, so at least one of the two is a backup's. And no
    replica may bail out of a device kernel. A window that held a view
    change has no one primary's page to read this from (the old primary's
    open windows are closed unstamped, `peerstats.close_all`), so the
    guarantee cannot be examined: that traced run fails, like one with a
    bail batch."""
    from benchmarks.launch import say
    from benchmarks.readers import spans

    problems = []
    pages = [{"scrape_before": b, "scrape_after": a}
             for b, a in zip(ctx["pages_before"], ctx["pages_after"])]
    view_changes = sum(spans.delta(page, "tbtpu_events_total", f"vsr.view_change.{how}") or 0
                       for page in pages for how in ("elected", "adopted"))
    commits = spans.delta(ctx, "tbtpu_events_total", "vsr.commits") or 0
    completions = sum(spans.delta(ctx, "tbtpu_events_total", f"vsr.peer.{r}.quorum_complete")
                      or 0 for r in range(replicas))
    acks = spans.delta(ctx, "tbtpu_span_seconds_count", "vsr.replication.lag") or 0
    say(f"{tag} on the primary (replica {ctx['primary']}) in the window: {commits:.0f} commits, "
        f"{completions:.0f} quorum completions, {acks:.0f} acks from backups")
    if view_changes:
        problems.append(f"a view change inside the traced window ({view_changes:.0f} elected or "
                        "adopted): the quorum's counts cannot be held against each other")
    elif not commits or abs(completions - commits) > PIPELINE_SLACK:
        problems.append(f"{completions:.0f} quorum completions for {commits:.0f} commits")
    elif acks < commits - PIPELINE_SLACK:
        problems.append(f"{acks:.0f} acks from backups for {commits:.0f} commits")
    for i, page in enumerate(pages):
        bails = spans.delta(page, "tbtpu_events_total", "sm.route.bail_batches") or 0
        backup_commits = spans.delta(page, "tbtpu_events_total", "vsr.commits") or 0
        if i != ctx["primary"]:
            say(f"{tag} replica {i} (a backup) committed {backup_commits:.0f} in the window")
        if bails > 0:
            problems.append(f"sm.route.bail_batches rose by {bails:.0f} on replica {i}")
    return problems


# --- one run ---------------------------------------------------------------------------


def view_client(addresses: list):
    """`tigerbeetle_tpu.client.Client` (it registers at once), remembering
    which replica answered last and in which view: the reply's header says both."""
    from tigerbeetle_tpu.client import Client

    class ViewClient(Client):
        view = replica = -1

        def _roundtrip(self, operation, body):
            reply = super()._roundtrip(operation, body)
            self.view, self.replica = int(reply.header["view"]), int(reply.header["replica"])
            return reply

    return ViewClient(addresses)


def first_reply(addresses: list, since: float, what: str):
    """A new client registered with a cluster that may have no primary yet,
    or not the one the client tries first (what a backup is sent is
    forwarded and its answer lost): short tries walk it on to the primary,
    and say to the second how long that took."""
    from benchmarks.launch import Failure
    from tigerbeetle_tpu.client import Client

    Client.REQUEST_TIMEOUT = ELECTION_PROBE_S
    while True:
        try:
            return view_client(addresses)
        except Exception as e:  # noqa: BLE001 - ClientError: no primary yet
            if time.perf_counter() - since > ELECTION_TIMEOUT_S:
                raise Failure(f"no replica answered {ELECTION_TIMEOUT_S:g} s after "
                              f"{what}: {e!r}") from None


def read_back_from(client, generator, config: dict, sample: set) -> tuple:
    """Every balance, and the sampled batches by id: (transfers by batch,
    [(ids, accounts)], seconds the transfers took)."""
    t = time.perf_counter()
    _request, transfer_ids = requests_of(generator)
    transfers = {(s, k): client.lookup_transfers(transfer_ids(s, k))
                 for s, k in sorted(sample)}
    transfers_s = time.perf_counter() - t
    n_batch = int(config["batch"])
    accounts = []
    for start in range(1, int(config["accounts"]) + 1, n_batch):
        ids = np.arange(start, min(start + n_batch, int(config["accounts"]) + 1),
                        dtype=np.uint64)
        accounts.append((ids, client.lookup_accounts([int(v) for v in ids])))
    return transfers, accounts, transfers_s


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             expect=None, overrides=None, child=None, device_prefix="/device:TPU",
             distinct=None) -> int:
    """`expect`, `overrides`, `child`, `device_prefix` and `distinct` are
    for the rehearsals, controls and fault tests under benchmarks/tests/,
    which have to drive this whole run on a CPU at a tiny size, or against
    a server broken on purpose; the command line has no switch for them."""
    from benchmarks import launch
    from benchmarks.launch import Failure, say
    from benchmarks.readers import spans
    from benchmarks.readers.generator import onset, percentile, result_codes
    from benchmarks.reference import Ledger
    from benchmarks.sessions import Load

    manifest, cell, config, traffic = load_cell(workload, (overrides or {}).get("manifest"))
    config = {**config, **(overrides or {}).get("config", {})}
    traffic = {**traffic, **(overrides or {}).get("traffic", {})}
    expect = expect or launch.require_tpu
    distinct = distinct or launch.chips_held
    peaks_table = load_json("peaks.json")
    replicas = int(config.get("replica_count", 1))
    if replicas > cell["chips"]:
        raise SystemExit(f"{workload}: {replicas} replicas do not fit on {cell['chips']} chip(s)")
    request_timeout_s = float(config.get("client_timeout_s", REQUEST_TIMEOUT_S))

    launch.load_shims()
    shims_s = time.perf_counter() - T_PROCESS_START
    from tigerbeetle_tpu.client import Client

    workdir = tempfile.mkdtemp(prefix="tbtpu_bench_")
    watchdog = launch.Watchdog(workdir, DEADLINE_S)
    if replicas == 1:
        servers = [launch.Server(watchdog, child or launch.SERVE)]
    else:
        servers = [launch.Server(watchdog, child or launch.SERVE, f"replica {i}",
                                 f"server.{i}.stderr") for i in range(replicas)]

    def stderr_tails() -> str:
        return "\n".join(f"--- {s.name}'s stderr (its end):\n{s.stderr_tail()}"
                         for s in servers)

    try:
        paths = [os.path.join(workdir, f"{i}.tigerbeetle") for i in range(replicas)]
        for i, path in enumerate(paths):
            launch.format_file(path, config["start"]["config"], i, replicas)
        formatted_s = time.perf_counter() - T_PROCESS_START
        ports = launch.free_ports(2 * replicas)
        ports, mports = ports[:replicas], (ports[replicas:] if trace else [])
        # (the tracer is on only in the traced run)
        commands = [launch.start_args(ports, i, config["start"], mports[i] if trace else 0,
                                      paths[i]) for i in range(replicas)]
        with ThreadPoolExecutor(replicas) as pool:  # in parallel: each takes 15 s to reach its chip
            devices = list(pool.map(
                lambda i: servers[i].start(commands[i], launch.chip_env(i, replicas)),
                range(replicas)))
        device = devices[0]
        listening_s = time.perf_counter() - T_PROCESS_START
        boot = servers[0].ask("compiles")
        tag = (f"[{device['platform']} {device['device_kind']!r} x{device['device_count']}]")
        say(f"{tag} {workload} seed {seed}: listening after {listening_s:.1f} s (imports and shims "
            f"{shims_s:.1f} s, format {formatted_s - shims_s:.1f} s, the server's start "
            f"{listening_s - formatted_s:.1f} s); durable writes: {launch.durable_mode(paths[0])}")
        # Where the server's start went (replica 0's): its stages end when the process
        # began, when it had imported JAX and the program, when its first and its last
        # program of the start were ready (compiled or read from the cache), when it listened.
        ready = [t for _name, t, _took, _how in boot["names"]] or [boot["imported"]]
        marks = [formatted_s + T_PROCESS_START, boot["started"], boot["imported"],
                 ready[0], ready[-1], listening_s + T_PROCESS_START]
        say(f"{tag} the server's start, stage by stage: " + ", ".join(
            f"{what} {b - a:.1f} s" for what, a, b in zip(
                ("spawn", "imports", "to its first program (device init, storage, tables)",
                 f"its {len(ready)} programs", "open and listen"), marks, marks[1:])))
        held = []
        if replicas == 1:
            expect(device, cell["chips"])
        else:
            # One chip each, three chips in all: told apart from outside.
            for i, d in enumerate(devices):
                expect(d, 1)
                held.append(distinct(servers[i].proc.pid))
                say(f"{tag} replica {i} (pid {servers[i].proc.pid}): {d['platform']} "
                    f"{d['device_kind']!r} x{d['device_count']}, holds {sorted(held[-1])}")
            launch.require_distinct_chips(held)
            if len({(d["platform"], d["device_kind"]) for d in devices}) != 1:
                raise Failure(f"the replicas run on different devices: {devices}")
        if device["device_kind"] not in peaks_table and device["platform"] == "tpu":
            raise Failure(f"no peaks for device kind {device['device_kind']!r} "
                          "in benchmarks/peaks.json")

        generator = importlib.import_module(
            "benchmarks.generators." + traffic["generator"]).Generator(config, traffic, seed)
        addresses = [("127.0.0.1", p) for p in ports]
        if replicas == 1:
            Client.REQUEST_TIMEOUT = request_timeout_s
            client = view_client(addresses)
        else:
            # A cluster started in parallel comes up through a view change, and the client's
            # first try goes to replica 0: 30 s of set-up if it waits the time-out out.
            client = first_reply(addresses, time.perf_counter(), "the cluster began to listen")
            Client.REQUEST_TIMEOUT = request_timeout_s
        t = time.perf_counter()
        for acc in generator.account_batches():
            got = client.create_accounts(acc)
            if len(got):
                raise Failure(f"create_accounts answered {len(got)} failures")
        say(f"{tag} {config['accounts']:,} accounts registered in "
            f"{time.perf_counter() - t:.1f} s")

        load = Load(addresses, int(traffic["sessions"]), requests_of(generator)[0],
                    request_timeout_s)
        ctx = {"config": config, "traffic": traffic,
               "peaks": peaks_table.get(device["device_kind"], {})}
        trace_dir = os.path.join(workdir, "trace") if trace else None
        t0, t1 = asyncio.run(drive(load, traffic, seconds, trace_dir, servers, mports, ctx))
        if load.errors and t1 == 0.0:
            raise Failure("a session gave up during prefill: " + "; ".join(load.errors[:3]))
        setup_s = t0 - T_PROCESS_START
        memory = max(int(s.ask("memory")["memory_peak_bytes"]) for s in servers)

        # The writes are what the rate and the write latencies are taken over; the reads
        # (a mix that has them) are in the cost, and have a latency of their own.
        records = load.records
        answered = [r for r in records if r.reply is not None]
        lost_all = [r for r in records if r.reply is None and r.sent > 0.0]
        window_all = [r for r in answered if t0 <= r.done < t1]
        window = [r for r in window_all if r.operation == WRITE]
        reads = [r for r in window_all if r.operation == READ]
        lost = [r for r in lost_all if r.operation == WRITE]
        prefill = [r for r in answered if r.done < t0 and r.operation == WRITE]
        reads_before = sum(r.done < t0 and r.operation == READ for r in answered)
        say(f"{tag} prefill {len(prefill)} batches ({sum(r.events for r in prefill):,} "
            f"transfers) in {t0 - min(r.sent for r in records):.1f} s"
            + (f", {reads_before} reads among them" if reads_before else "")
            + f"; set-up {setup_s:.1f} s; "
            f"window {seconds:g} s: {len(window_all)} requests answered"
            + (f" ({len(reads)} of them reads)" if reads else "") + f", {len(lost_all)} never; "
            f"{sum(x.resends for x in load.sessions)} resent, "
            f"{sum(x.busy for x in load.sessions)} answered BUSY")

        # Read back: every balance, and a sample of the window's batches by id.
        rng = np.random.default_rng([seed, 3])
        by_done = sorted(window, key=lambda r: r.done)
        picks = set(rng.choice(len(by_done), min(READ_BACK_BATCHES, len(by_done)),
                               replace=False).tolist()) | ({len(by_done) - 1} if by_done else set())
        sample = {(by_done[i].session, by_done[i].seq) for i in picks}
        t = time.perf_counter()
        survivors_differ = old_view_answers = None
        if replicas == 1:
            read_back, accounts_got, transfers_s = read_back_from(
                client, generator, config, sample)
        else:
            # The cluster first (the sample only), then a quorum WITHOUT the primary:
            # what the survivors serve is what gets compared.
            first = {(s, k): client.lookup_transfers(generator.ids(s, k))
                     for s, k in sorted(sample)}
            primary, view = client.replica, client.view
            if primary != ctx["primary"]:
                say(f"{tag} the primary was replica {ctx['primary']} at the window's start "
                    f"and is replica {primary} now")
            client.close()
            say(f"{tag} killing the primary (replica {primary} in view {view}, SIGKILL)")
            killed = time.perf_counter()
            servers[primary].stop(kill=True)
            # Everything is compiled by now and the dead address refuses connections.
            client = first_reply(addresses, killed, "the primary was killed")
            say(f"{tag} first reply from the remaining {replicas - 1} after "
                f"{time.perf_counter() - killed:.1f} s (tries of {ELECTION_PROBE_S:g} s): "
                f"replica {client.replica} in view {client.view}")
            Client.REQUEST_TIMEOUT = SURVIVOR_TIMEOUT_S
            read_back, accounts_got, transfers_s = read_back_from(
                client, generator, config, sample)
            old_view_answers = int(client.view <= view or client.replica == primary)
            survivors_differ = sum(rows_differing(read_back[key], first[key]) for key in first)
        read_back_s = time.perf_counter() - t
        client.close()
        for s in servers:
            s.stop()  # the chips are free from here on
        for s in servers:  # what a child broken on purpose says of itself (tests/broken_serve.py)
            for line in s.stderr_tail(1 << 16).splitlines():
                if line.startswith("NOTE: "):
                    say(f"{tag} {s.name}: {line[6:]}")
        host = None
        if replicas > 1:
            host = launch.probe_devices()
            say(f"{tag} the host, once the replicas let go: {host}")
            expect(host, cell["chips"])

        t = time.perf_counter()
        verdict = compare(generator, Ledger(int(config["accounts"])), records, sample,
                          read_back, accounts_got)
        reference_s = time.perf_counter() - t
        transfers_issued = sum(r.events for r in records if r.operation == WRITE)
        say(f"{tag} read back in {read_back_s:.1f} s ({transfers_s:.1f} s of it the "
            f"{len(sample)} batches of transfers), reference replay in {reference_s:.1f} s; "
            f"{transfers_issued:,} transfers issued in all "
            f"({100.0 * transfers_issued / int(config['transfers_max']):.0f}% of transfers_max)")

        # The end-to-end numbers, over all the work and all the time of the window.
        # (`attempted` and `failed` count transfers and looked-up ids)
        in_window = {(r.session, r.seq) for r in window_all}
        bad_in_window = sum(n for key, n in verdict["mismatched_requests"].items()
                            if key in in_window)
        attempted = sum(r.events for r in window_all) + sum(r.events for r in lost_all)
        failed = bad_in_window + sum(r.events for r in lost_all)
        drained_at = max([r.done for r in answered] + [t1])

        def latencies_ms(done: list, never: list) -> list:
            return sorted([r.latency * 1e3 for r in done]
                          + [(drained_at - r.sent) * 1e3 for r in never])

        latencies = latencies_ms(window, lost)
        say(f"{tag} write latency over {len(latencies)} requests of the window "
            f"(p95 has {len(latencies) - int(len(latencies) * 0.95)} beyond it)")
        if not window:
            raise Failure("no request was answered inside the window")
        read_latencies = latencies_ms(reads, [r for r in lost_all if r.operation == READ])
        if read_latencies:
            say(f"{tag} read latency over {len(read_latencies)} lookup_accounts requests of the "
                f"window ({sum(r.events for r in reads) / max(len(reads), 1):,.0f} ids each): "
                f"p50 {percentile(read_latencies, 0.50):.1f} ms, "
                f"p95 {percentile(read_latencies, 0.95):.1f} ms "
                f"({len(read_latencies) - int(len(read_latencies) * 0.95)} beyond it)")
        # Which phase of the store's cycle the window held (PERF.md, section 4).
        at, n = onset([r.done - t0 for r in by_done], seconds, PHASE_GAP_S)
        burst_rate = sum(r.events for r in by_done[:n]) / max(at, 1e-9)
        say(f"{tag} phase: burst of {n} requests in {at:.1f} s ({burst_rate:,.0f} tx/s), then "
            f"{len(window) - n} in {seconds - at:.1f} s; "
            + (f"the first {PHASE_GAP_S:g} s without a reply came after batch "
               f"{len(prefill) + n} of the run" if at < seconds else
               f"no {PHASE_GAP_S:g} s without a reply up to batch {len(prefill) + n} of the run")
            + (f"; views in the window's replies: {by_done[0].view} at its start, "
               f"{by_done[-1].view} at its end"
               + (" (A VIEW CHANGE INSIDE THE WINDOW: late is late, the numbers stand)"
                  if by_done[0].view != by_done[-1].view else "")
               + f"; {sum(x.steered for x in load.sessions)} sessions steered by a hello's "
               f"answer, {sum(x.moves for x in load.sessions)} moves to the next address"
               if replicas > 1 else ""))
        refused, counts = np.unique(result_codes(window), return_counts=True)
        say(f"{tag} events the window's replies refused, per request, by result code: "
            + (", ".join(f"{c}: {k / len(window):.1f}" for c, k in zip(refused, counts))
               or "none"))
        cores = [(a - b) / seconds for a, b in zip(ctx["cpu_after"], ctx["cpu_before"])]
        ctx["cores"] = os.cpu_count()
        say(f"{tag} processor time over the window, in cores: "
            + ", ".join(f"replica {i}{' (primary)' if i == ctx['primary'] and replicas > 1 else ''} "
                        f"{c:.2f}" for i, c in enumerate(cores[:-1]))
            + f", the load generator {cores[-1]:.2f}; the host has {ctx['cores']}")
        end_to_end = {
            "tx_per_s": sum(r.events for r in window) / seconds,
            "write_p50_ms": percentile(latencies, 0.50),
            "write_p95_ms": percentile(latencies, 0.95),
            "setup_s": setup_s,
            **({"read_p50_ms": percentile(read_latencies, 0.50)} if read_latencies else {}),
        }
        ctx["window_records"] = window
        ctx["window"] = {"t0": t0, "seconds": seconds, "answered_before": len(prefill)}

        # A cluster's device is the host's, as a fresh process finds it once the replicas
        # have let go; the peak is the fullest replica's.
        device_out = {"platform": device["platform"], "kind": device["device_kind"],
                      "count": (host or device)["device_count"], "memory_peak_bytes": memory}
        if replicas > 1:
            device_out.update(replicas=replicas, chips_held=sorted(set().union(*held)))
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
            if replicas > 1:
                problems += cluster_problems(ctx, replicas, tag)
            metrics = {}
            for m in manifest["per_layer"]:
                if workload not in m.get("workloads", [workload]):
                    continue
                value = read_metric(load_json("layer_metrics", m["name"] + ".json"), ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            if reduced:
                device_out["busy_s"] = reduced["busy_s"]
                device_out["window_s"] = reduced["window_s"]
                result["breakdown"] = {"device_ops": reduced["device_ops"],
                                       "idle_gaps": reduced["idle_gaps"]}
        else:
            # Those of the harness's end-to-end numbers that the manifest lists for this cell.
            owed = [m for m in manifest["end_to_end"] if workload in m.get("workloads", [workload])]
            missing = sorted({m["name"] for m in owed} - set(end_to_end))
            if missing:
                raise Failure(f"the run has no {missing}: the cell lists it and its traffic "
                              "sent no such request")
            metrics = {m["name"]: {"value": end_to_end[m["name"]], "unit": m["unit"]} for m in owed}
        before, after = ctx["monitor_before"], ctx["monitor_after"]
        compiled_in_window = [f"{name} {how} in {took:.1f} s at {t - t0:.1f} s"
                              for name, t, took, how in after["names"] if t0 <= t < t1]
        say(f"{tag} programs compiled or read from the compile cache: {before['compiles']} in "
            f"set-up ({before['cache_hits']} of them cache reads, {before['seconds']:.1f} s), "
            f"{after['compiles'] - before['compiles']} in the window {compiled_in_window}")
        say(f"{tag} end to end: " + ", ".join(f"{k} {v:.4f}" for k, v in end_to_end.items()))
        if trace:  # said here too, so that a run that fails below still leaves its readings
            say(f"{tag} per layer: " + ", ".join(f"{k} {v['value']:.6g}" for k, v in metrics.items()))

        # Each number compared, beside its limit (every comparison is exact: limit 0).
        compared = {
            "code_mismatches": [verdict["code_mismatches"], 0],
            "balance_mismatches": [verdict["balance_mismatches"], 0],
            "store_mismatches": [verdict["store_mismatches"], 0],
            "requests_never_answered": [len(lost_all) + len(load.errors), 0],
            "replies_sharing_an_op": [verdict["replies_sharing_an_op"], 0],
            "session_order_violations": [verdict["session_order_violations"], 0],
            "realtime_order_violations": [verdict["realtime_order_violations"], 0],
            "read_mismatches": [verdict["read_mismatches"], 0],
            **({"survivor_rows_differing": [survivors_differ, 0],
                "survivors_answered_in_old_view": [old_view_answers, 0]}
               if replicas > 1 else {}),
            "code_events_compared": [verdict["code_events_compared"], None],
            "accounts_compared": [verdict["accounts_compared"], None],
            "transfers_read_back": [verdict["transfers_read_back"], None],
            "read_rows_compared": [verdict["read_rows_compared"], None],
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
        say(stderr_tails())
        return 1
    finally:
        for s in servers:
            s.stop()
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
