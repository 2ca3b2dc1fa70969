"""In-process profiler for the replica's request->commit pipeline: feeds
sealed REQUEST messages straight into Replica.on_message (no TCP) with
the full four-thread pipeline attached (event loop + WalWriter +
CommitExecutor + StoreExecutor), then reports everything from the
tracer registry — per-stage ms/batch with p50/p99 tail latency, the
stall/idle rows, and a Perfetto-loadable timeline of the thread
overlap (tracer.dump). Not part of the test suite.

The registry is the single timing source: the one wall-clock
measurement is only used to cross-check the `server.total` span (must
agree within 5%), and the per-stage table rows are disjoint spans, so
their sum can never exceed the server total (asserted — this is the
guard against re-introducing double-counted regions).
"""

import os
import sys
import tempfile
import time
from collections import deque

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tigerbeetle_tpu import tracer, types
from tigerbeetle_tpu.constants import config_by_name
from tigerbeetle_tpu.io.storage import FileStorage, Zone
from tigerbeetle_tpu.vsr import header as hdr
from tigerbeetle_tpu.vsr.header import Command, Header, Message, Operation
from tigerbeetle_tpu.vsr.journal import WalWriter
from tigerbeetle_tpu.vsr.replica import Replica

BATCH = 8190


class DummyBus:
    def __init__(self):
        self.replies = []

    def send_to_replica(self, r, msg):
        pass

    def send_to_client(self, c, msg):
        self.replies.append(msg)


def main(backend="numpy", batches=40, overlap=True, store_async=True,
         warmup=2, commit_depth=0):
    tracer.enable()
    # Compile-count guard (tidy/jaxlint.py CompileRegistry): after the
    # warmup batches the measured window must be retrace-free — any new
    # XLA compile inside it is a shape/dtype-instability bug, asserted
    # below. The numpy backend never compiles; the registry then reports
    # zeros without importing jax.
    from tigerbeetle_tpu.tidy.jaxlint import compile_registry

    if backend != "numpy":
        from tigerbeetle_tpu import compilecache

        compilecache.configure()
        compile_registry.install()
        compile_registry.track_default_entries()
    tmp = tempfile.mkdtemp(prefix="tbtpu-prof-")
    path = os.path.join(tmp, "prof.tigerbeetle")
    config = config_by_name("production")
    zone = Zone.for_config(
        config.journal_slot_count, config.message_size_max,
        grid_block_count=config.grid_block_count,
        grid_block_size=config.lsm_block_size,
    )
    storage = FileStorage(path, size=zone.total_size, create=True)
    Replica.format(storage, zone, 0, 0, 1)
    storage.close()
    storage = FileStorage(path)
    bus = DummyBus()
    replica = Replica(
        cluster=0, replica_index=0, replica_count=1, storage=storage,
        zone=zone, config=config, bus=bus, sm_backend=backend,
    )
    replica.open()
    ops = getattr(replica.state_machine, "_ops", None)
    if ops is not None and hasattr(ops, "track_compiles"):
        ops.track_compiles(compile_registry)  # mesh-built jit entries

    # The full pipeline (docs/COMMIT_PIPELINE.md): WAL writer + commit
    # executor + async store stage. Worker threads post loop-side
    # callbacks (acks, completions, fault notifications) onto `posts`,
    # drained by pump() — standing in for the asyncio loop.
    posts = deque()
    if overlap or store_async:
        replica.wal_writer = WalWriter(storage, posts.append)
        replica.journal.writer = replica.wal_writer
    if overlap:
        # commit_depth=0: adaptive (accelerator → min(pipeline_max, 4),
        # host backends → 1); depth=N on the command line forces — the
        # cross-batch window A/B and its occupancy section below.
        replica.attach_executor(posts.append, commit_depth=commit_depth)
    if store_async:
        replica.attach_store_executor(posts.append)

    def pump():
        while posts:
            posts.popleft()()

    def settle(expect_replies, deadline_s=300.0):
        """Pump until every fed request has replied (worker threads run
        between pumps; the tiny sleep yields the GIL to them)."""
        t_end = time.perf_counter() + deadline_s
        while len(bus.replies) < expect_replies:
            pump()
            if len(bus.replies) >= expect_replies:
                break
            if time.perf_counter() > t_end:
                raise RuntimeError(
                    f"stalled: {len(bus.replies)}/{expect_replies} replies"
                )
            time.sleep(0.0002)

    client_id = 0x1234567
    reqno = 0

    def request(operation, body):
        nonlocal reqno
        reqno += 1
        h = hdr.make(
            Command.REQUEST, 0, client=client_id, request=reqno,
            operation=operation,
        )
        return Message(h, body).seal()

    replica.on_message(request(Operation.REGISTER, b""))
    settle(1)
    assert bus.replies, "register reply missing"

    n_accounts = 10_000
    ids = np.arange(1, n_accounts + 1, dtype=np.uint64)
    for s in range(0, n_accounts, BATCH):
        chunk = ids[s : s + BATCH]
        ev = np.zeros(len(chunk), dtype=types.ACCOUNT_DTYPE)
        ev["id_lo"] = chunk
        ev["ledger"] = 1
        ev["code"] = 10
        n_before = len(bus.replies)
        replica.on_message(request(Operation.CREATE_ACCOUNTS, ev.tobytes()))
        settle(n_before + 1)

    # Pre-marshal request bodies (client-side cost measured separately).
    # The first `warmup` batches are fed before the measured window so
    # every kernel bucket is compiled; the window itself must then be
    # compile-free (asserted after the run).
    rng = np.random.default_rng(7)
    bodies = []
    next_id = 1
    t0 = time.perf_counter()
    for _ in range(batches + warmup):
        ev = np.zeros(BATCH, dtype=types.TRANSFER_DTYPE)
        ev["id_lo"] = np.arange(next_id, next_id + BATCH, dtype=np.uint64)
        next_id += BATCH
        dr = rng.integers(1, n_accounts + 1, BATCH).astype(np.uint64)
        cr = rng.integers(1, n_accounts + 1, BATCH).astype(np.uint64)
        cr = np.where(cr == dr, (cr % n_accounts) + 1, cr)
        ev["debit_account_id_lo"] = dr
        ev["credit_account_id_lo"] = cr
        ev["amount_lo"] = rng.integers(1, 1000, BATCH)
        ev["ledger"] = 1
        ev["code"] = 7
        bodies.append(ev.tobytes())
    marshal_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    msgs = [request(Operation.CREATE_TRANSFERS, b) for b in bodies]
    seal_s = time.perf_counter() - t0

    # Native-datapath ingress (docs/NATIVE_DATAPATH.md): when the codec
    # is enabled, the feed loop re-parses each message from its wire
    # bytes through the C scanner — exactly the server bus's ingress —
    # so the stage table's parse row (and the nested bus.scan/bus.decode
    # sub-spans) attribute the real codec cost. Pre-serialized here
    # (client-side cost, like marshal/seal above).
    from tigerbeetle_tpu.net import codec

    bus_scanner = codec.scanner()
    frames = [m.to_bytes() for m in msgs] if bus_scanner is not None else None

    # Warmup: compile every kernel bucket outside the measured window.
    # The store stage is DRAINED before the compile baseline is snapped:
    # its work trails the replies by up to a full queue.
    n_warm = len(bus.replies)
    for m in msgs[:warmup]:
        replica.on_message(m)
        pump()
    settle(n_warm + warmup)
    if replica.store_executor is not None:
        replica.store_executor.drain()
        pump()
    msgs = msgs[warmup:]
    if frames is not None:
        frames = frames[warmup:]
    compile_snap = compile_registry.snapshot()

    tracer.reset()  # measure only the transfer load (all threads re-arm)
    n0 = len(bus.replies)
    wall0 = time.perf_counter()
    with tracer.span("server.total"):
        for mi, m in enumerate(msgs):
            # Feed with pipeline backpressure: past pipeline_max the
            # round-14 front door sheds with BUSY (one backlog slot per
            # session), and a shed batch would silently vanish from the
            # profile — pace the feed like a real client's flow control
            # instead. A fast backend never waits here; a slow one keeps
            # the prepare pipeline exactly full.
            while (
                len(replica.pipeline) >= replica.config.pipeline_max
                or replica.request_queue
            ):
                pump()
                time.sleep(0.0002)
            # Ingress runs here exactly as the server bus does — the C
            # scan+decode on the native datapath (zero-copy body off the
            # frame buffer, verified flag set), the Python body MAC on
            # the fallback — so the stage table attributes it too.
            with tracer.span("stage.parse"):
                if bus_scanner is not None:
                    raw = frames[mi]
                    with tracer.span("bus.scan"):
                        rows, _consumed, _need, status = bus_scanner.scan(raw)
                    assert status == codec.STATUS_OK and len(rows) == 1
                    with tracer.span("bus.decode"):
                        m = codec.messages_from_scan(raw, rows)[0]
                else:
                    assert m.header.valid_checksum_body(m.body)
            replica.on_message(m)
            pump()
        settle(n0 + batches)
    wall_s = time.perf_counter() - wall0
    # Replies are all out; the async store stage may still be draining the
    # tail of its queue — settle it and report the lag separately.
    drain_s = 0.0
    if replica.store_executor is not None:
        t0d = time.perf_counter()
        replica.store_executor.drain()
        drain_s = time.perf_counter() - t0d
        pump()
    assert len(bus.replies) - n0 == batches, (len(bus.replies) - n0, batches)

    snap = tracer.snapshot()
    # Every reply above is a genuine commit: the paced feed must never
    # trip the admission door (a BUSY shed would count as a reply and
    # silently shrink the measured op set).
    assert snap.get("vsr.sheds", {}).get("count", 0) == 0, snap.get("vsr.sheds")
    # Dedup invariant 1: the registry's server.total span IS the wall
    # measurement (one clock, one source of truth) — the ad-hoc
    # time.perf_counter pair exists only to cross-check it.
    total_ms = snap["server.total"]["total_ms"]
    assert abs(total_ms / 1e3 - wall_s) / wall_s < 0.05, (total_ms, wall_s)

    compile_delta = compile_registry.delta(compile_snap)
    new_compiles = compile_registry.total_delta(compile_snap)

    print(f"backend={backend} batches={batches} overlap={overlap} "
          f"store_async={store_async} warmup={warmup}"
          + (f" commit_depth={replica.commit_depth}" if overlap else ""))
    print(f"client marshal: {marshal_s / (batches + warmup) * 1e3:.2f} ms/batch")
    print(f"client seal:    {seal_s / (batches + warmup) * 1e3:.2f} ms/batch")
    print(f"server total:   {total_ms / batches:.2f} ms/batch "
          f"({batches * BATCH / (total_ms / 1e3) / 1e6:.2f}M tx/s)")
    if store_async:
        print(f"store drain tail after last reply: {drain_s * 1e3:.2f} ms")

    def span_ms(keys):
        return sum(snap[k]["total_ms"] for k in keys if k in snap)

    def span_pcts(keys):
        """(p50_us, p99_us) of the dominant (largest-total) event."""
        best = None
        for k in keys:
            rec = snap.get(k)
            if rec and "p50_us" in rec:
                if best is None or rec["total_ms"] > best["total_ms"]:
                    best = rec
        return (best["p50_us"], best["p99_us"]) if best else (0.0, 0.0)

    # Stage-attribution table (docs/COMMIT_PIPELINE.md stages): where the
    # per-batch milliseconds live. Rows are DISJOINT spans: with the
    # commit executor, execute/reply run on the commit thread and exclude
    # each other; on the serial path the reply and store barrier nest
    # inside replica.execute and are subtracted to keep rows disjoint.
    stages = {
        "parse": ("stage.parse",),
        "wal": ("journal.write_prepare", "stage.wal"),
        "replicate": ("stage.replicate",),
        "execute": ("replica.execute",),
        "reply": ("stage.reply",),
    }
    store_rows = {
        "store.log": ("sm.store.log",),
        "store.idx": ("sm.store.idx",),
        "store.rows": ("sm.store.rows",),
        "store.query": ("sm.store.query",),
        "beat": ("sm.beat",),
    }
    if store_async:
        stages["store.wait"] = ("sm.store.barrier",)
        stages["store.stall"] = ("pipeline.store.stall",)
    else:
        stages.update(store_rows)

    reply_ms = snap.get("stage.reply", {}).get("total_ms", 0.0)
    print("\nstage attribution (per batch; p50/p99 per span; compiles = jit "
          "cache misses inside the measured window):")
    header = (f"  {'stage':12s} {'ms/batch':>9s} {'% wall':>7s} "
              f"{'p50_us':>9s} {'p99_us':>9s} {'compiles':>9s}")
    print(header)
    record = {}
    attributed = 0.0
    for stage, keys in stages.items():
        ms = span_ms(keys)
        if stage == "execute" and not overlap:
            # Serial path: reply build (and barrier wait) nest inside the
            # execute span; subtract to report the stages disjointly.
            ms -= reply_ms + span_ms(("sm.store.barrier",)) * store_async
        attributed += ms
        p50, p99 = span_pcts(keys)
        record[stage] = round(ms / batches, 3)
        record[f"{stage}_p99_us"] = p99
        # Device kernels dispatch from the execute stage: it carries the
        # window's total compile count; every other stage is host-only.
        n_comp = new_compiles if stage == "execute" else 0
        print(f"  {stage:12s} {ms / batches:9.2f} {100 * ms / total_ms:6.1f}% "
              f"{p50:9.1f} {p99:9.1f} {n_comp:9d}")
    other = total_ms - attributed
    record["other"] = round(other / batches, 3)
    record["compiles"] = new_compiles
    print(f"  {'other':12s} {other / batches:9.2f} {100 * other / total_ms:6.1f}%")
    per_entry = {
        k: v for k, v in compile_delta.items()
        if k != "__global__" and v
    }
    if per_entry:
        print("  jit compiles by entry point: " + ", ".join(
            f"{k}={v}" for k, v in sorted(per_entry.items())
        ))
    # The measured window must be retrace-free: every kernel bucket is
    # compiled during the warmup batches, so a nonzero count here is a
    # shape/dtype-instability regression (the same invariant bench_gate
    # enforces on recorded runs via steady_compiles).
    assert new_compiles == 0, (
        f"jit compiled {new_compiles} time(s) inside the measured window "
        f"(per entry: {per_entry or compile_delta}) — retrace regression"
    )
    # Dedup invariant 2 (serial commit only): with every commit-path row
    # on the loop thread, disjoint rows can never sum past the window —
    # a re-introduced double-counted region (the old execute-includes-
    # reply accounting) trips this immediately. In overlap mode the rows
    # straddle two concurrent threads, so their sum may legitimately
    # exceed wall time and only the per-thread checks below apply.
    if not overlap:
        assert attributed <= total_ms * 1.05, (attributed, total_ms)

    # Query-index decomposition: the sub-spans NEST inside the
    # store.query row, so they are reported as their own table and never
    # added to the disjoint stage attribution above. `keys` is the
    # per-commit key build (the numpy block); `sort`/`merge`/`build` are
    # the flush phases (radix vs k-way merge, then the grid table
    # build); `prefetch` is the store worker's idle compaction
    # read-ahead.
    query_rows = {
        "query.keys": ("sm.store.query.keys",),
        "query.sort": ("lsm.query_rows.flush.sort",),
        "query.merge": ("lsm.query_rows.flush.merge",),
        "query.build": ("lsm.query_rows.flush.build",),
        "query.prefetch": ("pipeline.store.prefetch",),
    }
    if any(span_ms(keys) for keys in query_rows.values()):
        print("\nquery index (inside store.query + flush):")
        print(f"  {'span':14s} {'ms/batch':>9s} {'p50_us':>9s} {'p99_us':>9s}")
        for stage, keys in query_rows.items():
            ms = span_ms(keys)
            if not ms:
                continue
            p50, p99 = span_pcts(keys)
            record[stage] = round(ms / batches, 3)
            print(f"  {stage:14s} {ms / batches:9.2f} {p50:9.1f} {p99:9.1f}")

    # Streaming-compaction decomposition (docs/COMMIT_PIPELINE.md
    # "Streaming compaction"): the merge/bloom/build sub-spans NEST
    # inside the beat row (sm.beat → compact_step) — so this is its own
    # table, never added to the disjoint stage attribution above. compact.beat
    # repeats the beat row as the table's enclosing total; forward is
    # the fault-retry fast-forward replay (zero in a healthy run).
    compact_rows = {
        "compact.beat": ("sm.beat",),
        "compact.forward": ("lsm.compact.forward",),
        "compact.merge": ("lsm.compact.merge",),
        "compact.bloom": ("lsm.compact.bloom",),
        "compact.build": ("lsm.compact.build",),
    }
    if any(span_ms(keys) for keys in compact_rows.values()
           if keys != ("sm.beat",)):
        print("\nstreaming compaction (nested inside the beat row):")
        print(f"  {'span':16s} {'ms/batch':>9s} {'p50_us':>9s} {'p99_us':>9s}")
        for stage, keys in compact_rows.items():
            ms = span_ms(keys)
            if not ms:
                continue
            p50, p99 = span_pcts(keys)
            record[stage] = round(ms / batches, 3)
            print(f"  {stage:16s} {ms / batches:9.2f} {p50:9.1f} {p99:9.1f}")

    # Native bus codec sub-spans (docs/NATIVE_DATAPATH.md): scan+decode
    # nest inside the parse row, encode inside the reply row — their own
    # table, never added to the disjoint stage attribution above. This
    # is the exact before/after attribution for the C-datapath swap.
    bus_rows = {
        "bus.scan": ("bus.scan",),
        "bus.decode": ("bus.decode",),
        "bus.encode": ("bus.encode",),
    }
    if any(span_ms(keys) for keys in bus_rows.values()):
        print("\nnative bus codec (nested inside parse/reply rows; "
              "TIGERBEETLE_TPU_NATIVE_BUS governs):")
        print(f"  {'span':14s} {'ms/batch':>9s} {'p50_us':>9s} {'p99_us':>9s}")
        for stage, keys in bus_rows.items():
            ms = span_ms(keys)
            if not ms:
                continue
            p50, p99 = span_pcts(keys)
            record[stage] = round(ms / batches, 3)
            print(f"  {stage:14s} {ms / batches:9.2f} {p50:9.1f} {p99:9.1f}")

    if overlap or store_async:
        print("\nworker threads (off the commit path; overlaps the wall "
              "time above):")
        print(f"  {'stage':12s} {'ms/batch':>9s} {'% wall':>7s} "
              f"{'p50_us':>9s} {'p99_us':>9s}")
        worker_rows = {"wal.write": ("wal.write",)}
        if store_async:
            worker_rows.update(store_rows)
            worker_rows["store.total"] = ("stage.store_async",)
        for stage, keys in worker_rows.items():
            ms = span_ms(keys)
            p50, p99 = span_pcts(keys)
            record[f"async.{stage}"] = round(ms / batches, 3)
            print(f"  {stage:12s} {ms / batches:9.2f} {100 * ms / total_ms:6.1f}% "
                  f"{p50:9.1f} {p99:9.1f}")
        # Per-thread busy time must fit its window too: workers keep
        # draining past the last reply (the measured tail), so their
        # window is server.total plus the drain.
        window_ms = total_ms + drain_s * 1e3
        for group in (("wal.write",), ("stage.store_async",)):
            assert span_ms(group) <= window_ms * 1.05, (group, window_ms)

    stalls = {
        k: snap[k]["total_ms"]
        for k in ("pipeline.commit.idle", "pipeline.store.idle",
                  "pipeline.wal.idle", "pipeline.store.stall")
        if k in snap
    }
    if stalls:
        print("\nstage idle/stall (thread-seconds inside the window):")
        for k, ms in stalls.items():
            print(f"  {k:22s} {ms / batches:9.2f} ms/batch")

    # Per-op lifecycle: the queue-wait vs service decomposition from the
    # registry — where each prepare's latency actually lives, per stage,
    # with Little's-law occupancy (mean prepares resident per stage).
    lifecycle = tracer.lifecycle_summary()
    comps = lifecycle["components"]
    if comps:
        print(f"\nper-op lifecycle decomposition ({lifecycle['ops']} ops, "
              f"window {lifecycle['window_s']:.2f}s):")
        print(f"  {'component':18s} {'ms/op':>9s} {'p50_ms':>9s} "
              f"{'p99_ms':>9s} {'occupancy':>10s}")
        window_sum = 0.0
        for name, s in comps.items():
            occ = lifecycle["occupancy"].get(name, 0.0)
            print(f"  {name:18s} {s['mean_ms']:9.3f} {s['p50_ms']:9.3f} "
                  f"{s['p99_ms']:9.3f} {occ:10.2f}")
            if ".store" not in name:
                window_sum += s["mean_ms"]
        perceived = lifecycle["perceived"]
        if perceived.get("count"):
            print(f"  {'= perceived':18s} {perceived['mean_ms']:9.3f} "
                  f"{perceived['p50_ms']:9.3f} {perceived['p99_ms']:9.3f} "
                  f"{lifecycle['occupancy'].get('total', 0.0):10.2f}")
            # Acceptance invariant: the window components TILE the
            # arrive→reply interval, so their means must sum to the mean
            # perceived latency (within 10% — clamped negatives on
            # cross-thread hand-offs are the only slack).
            drift = abs(window_sum - perceived["mean_ms"])
            assert drift <= 0.10 * perceived["mean_ms"], (
                f"lifecycle decomposition ({window_sum:.3f} ms) does not "
                f"sum to perceived ({perceived['mean_ms']:.3f} ms)"
            )

    # Cross-batch commit-window occupancy (docs/COMMIT_PIPELINE.md):
    # mean in-flight dispatched batches, the exact per-depth histogram
    # (one sample per processed batch), and the dispatch→finish gap —
    # the window the depth-N pipeline exists to keep open. The zero-
    # compiles assert above already ran: the scratch ring must introduce
    # no per-depth shapes, so depth>1 stays retrace-free by the same
    # gate.
    flat = lifecycle["flat"]
    if overlap and "commit_inflight_mean" in flat:
        print(f"\npipeline occupancy (commit window, depth="
              f"{flat.get('commit_depth', 1.0):.0f}):")
        print(f"  in-flight mean {flat['commit_inflight_mean']:.2f}  "
              f"max {flat.get('commit_inflight_max', 0):.0f}  "
              f"p99 {flat.get('commit_inflight_p99', 0.0):.0f}")
        depth_rows = sorted(
            (int(k.rsplit(".d", 1)[1]), v["count"])
            for k, v in snap.items()
            if k.startswith("pipeline.commit.inflight.d")
        )
        if depth_rows:
            total_n = sum(n for _, n in depth_rows)
            print("  per-batch depth histogram: " + "  ".join(
                f"{d}:{n} ({100.0 * n / total_n:.0f}%)"
                for d, n in depth_rows
            ))
        record["commit_inflight_mean"] = flat["commit_inflight_mean"]
        gap = snap.get("device.step.create_transfers_fast")
        if gap and gap.get("count"):
            print(f"  dispatch→finish gap: p50 {gap['p50_us'] / 1e3:.2f} ms  "
                  f"p99 {gap['p99_us'] / 1e3:.2f} ms "
                  f"({gap['count']} dispatches)")

    # Device-step profiler: per-jit-entry device time + transfer bytes
    # (numpy backend never dispatches, so the table is jax-only).
    dev_rows = {
        k: v for k, v in snap.items()
        if k.startswith("device.") and v.get("total_ms")
        # device.xfer.* histograms hold RAW GB/s samples, not durations
        # — they read back below, never as a step row.
        and not k.startswith("device.xfer.")
    }
    if dev_rows:
        print("\ndevice steps (per jit entry; step = dispatch->finish):")
        print(f"  {'entry':34s} {'calls':>7s} {'ms/call':>9s} "
              f"{'p50_us':>9s} {'p99_us':>9s}")
        for k in sorted(dev_rows):
            r = dev_rows[k]
            print(f"  {k:34s} {r['count']:7d} "
                  f"{r['total_ms'] / max(r['count'], 1):9.3f} "
                  f"{r.get('p50_us', 0.0):9.1f} {r.get('p99_us', 0.0):9.1f}")
        h2d = snap.get("device.h2d_bytes", {}).get("count", 0)
        d2h = snap.get("device.d2h_bytes", {}).get("count", 0)
        print(f"  transfers: h2d {h2d / 1e6:.1f} MB, d2h {d2h / 1e6:.1f} MB")

    # Per-entry cost/roofline table (devicestats): static FLOPs/bytes
    # from cost_analysis joined with the measured wall times above. This
    # runs AFTER the retrace assert — the lowering it triggers compiles
    # outside the measured window by construction.
    from tigerbeetle_tpu import devicestats

    cost_rows = devicestats.cost_table(snap)
    if cost_rows:
        print("\ndevice cost/roofline (static cost_analysis x measured "
              "ms/call; bound = static intensity vs backend balance "
              "point):")
        print(f"  {'entry':24s} {'shape':28s} {'ms/call':>8s} "
              f"{'gflops':>8s} {'gbps':>8s} {'bound':>8s}")
        for r in cost_rows:
            shape = r["shape"] if len(r["shape"]) <= 28 else r["shape"][:25] + "..."

            def na(v):
                return f"{v:.3f}" if isinstance(v, float) else "-"

            print(f"  {r['entry']:24s} {shape:28s} "
                  f"{na(r['ms_per_call']):>8s} "
                  f"{na(r.get('achieved_gflops')):>8s} "
                  f"{na(r.get('achieved_gbps')):>8s} {r['bound']:>8s}")
        xfer = devicestats.xfer_summary(snap)
        if xfer.get("h2d_windows") or xfer.get("d2h_windows"):
            print(f"  xfer bandwidth: h2d p50 "
                  f"{xfer.get('h2d_gbps_p50', 0.0):.3f} GB/s  d2h p50 "
                  f"{xfer.get('d2h_gbps_p50', 0.0):.3f} GB/s  "
                  f"bytes/transfer {xfer.get('bytes_per_transfer', '-')}")
        mem = tracer.device_mem_totals()
        if mem["owners"]:
            owners = ", ".join(
                f"{o}={b / 1e6:.1f}MB" for o, b in sorted(mem["owners"].items())
            )
            print(f"  device mem: {owners}  high-water "
                  f"{mem['high_water_bytes'] / 1e6:.1f}MB")

    # Multi-predicate query engine (docs/QUERY.md): a short post-window
    # probe over the transfers just committed — plan/scan/probe/gather
    # nest inside sm.query, so they are reported as their own table and
    # NEVER added to the disjoint stage attribution above (the measured
    # window contains no queries; these run after it, and the deltas
    # below subtract everything before them).
    sm = replica.state_machine
    qf = np.zeros(1, dtype=types.QUERY_FILTER_V2_DTYPE)
    rng_q = np.random.default_rng(11)
    q0 = tracer.snapshot()
    n_queries = 16
    for _ in range(n_queries):
        qf[0]["ledger"] = 1
        qf[0]["code"] = 7
        qf[0]["limit"] = BATCH
        qf[0]["debit_account_id_lo"] = int(rng_q.integers(1, n_accounts + 1))
        sm.query_transfers(qf[0])
    q1 = tracer.snapshot()

    def q_ms(key):
        return (q1.get(key, {}).get("total_ms", 0.0)
                - q0.get(key, {}).get("total_ms", 0.0))

    if q_ms("sm.query"):
        print("\nquery engine (post-window probe; plan/scan/probe/gather "
              "nest inside sm.query — never part of the stage "
              "attribution):")
        print(f"  {'span':16s} {'ms/query':>9s}")
        for stage, key in (
            ("query.total", "sm.query"),
            ("query.plan", "sm.query.plan"),
            ("query.scan", "sm.query.scan"),
            ("query.probe", "sm.query.probe"),
            ("query.gather", "sm.query.gather"),
        ):
            ms = q_ms(key)
            record[stage] = round(ms / n_queries, 3)
            print(f"  {stage:16s} {ms / n_queries:9.3f}")

    trace_path = tracer.dump(
        os.environ.get("TIGERBEETLE_TPU_TRACE_FILE",
                       os.path.join(tmp, "trace_e2e.json"))
    )
    print(f"\nperfetto trace: {trace_path} (open in ui.perfetto.dev; "
          f"summarize: python tools/trace_summary.py {trace_path})")

    tracer.devhub_append(
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "devhub.jsonl"),
        {
            "metric": "e2e_stage_profile_ms_per_batch",
            "value": round(total_ms / batches, 3),
            "unit": "ms/batch",
            "extra": {
                "backend": backend, "batches": batches,
                "overlap": overlap, "store_async": store_async,
                "native_bus": int(bus_scanner is not None),
                "stages": record,
                "lifecycle": lifecycle["flat"],
            },
        },
    )
    storage.close()


if __name__ == "__main__":
    _args = sys.argv[1:]
    _depth = next(
        (int(a.split("=", 1)[1]) for a in _args if a.startswith("depth=")), 0
    )
    main(
        backend=next(
            (a for a in _args
             if a not in ("serial-store", "async-store", "serial-commit")
             and not a.startswith("depth=")),
            "numpy",
        ),
        overlap="serial-commit" not in _args,
        store_async="serial-store" not in _args,
        commit_depth=_depth,
        # Device-merge + deep-window runs need the warmup to cover a
        # flush cycle (see the warmup comment above).
        warmup=8 if any(a == "jax" for a in _args) else 2,
    )
