"""Pipeline-wide tracing, metrics, and the devhub-style benchmark series.

The analog of the reference's observability stack, grown from the flat
count/total/max table into a real subsystem now that three worker
threads (WalWriter, CommitExecutor, StoreExecutor) overlap the event
loop and their stall/idle time decides throughput:

  - /root/reference/src/tracer.zig:48 — typed start/end span events.
    Here: `span(event)` context manager writing one `(event, tid,
    t_start, t_end)` record into a PER-THREAD bounded ring buffer
    (lock-free: each thread owns its ring; steady-state cost is two
    `perf_counter_ns` calls and zero allocation — span objects are
    pooled, ring slots are preallocated arrays).
  - HDR-style log-bucketed latency histograms per event (fixed bucket
    array, 8 sub-buckets per octave ≈ 12.5% value resolution), so
    `snapshot()` reports p50/p95/p99/max — not just averages.
  - /root/reference/src/statsd.zig:12 — metric emission. Here: a
    registry of counters (`count`) and gauges (`gauge`) merged across
    threads; `prometheus_text()` renders the Prometheus text format and
    `serve_metrics(port)` serves `/metrics` + `/trace` from the
    replica's asyncio loop (scrape instead of UDP StatsD — no daemon).
  - Chrome trace-event / Perfetto export: `export_trace()` merges every
    thread's ring into one JSON object loadable in ui.perfetto.dev, so
    the WAL/commit/store overlap is visible as an actual timeline;
    `dump(path)` writes it for offline runs (profile_e2e).
  - /root/reference/src/scripts/devhub.zig:36-52 — the per-merge
    benchmark time series. Here: `devhub_append(path, record)` appends
    one JSON line stamped with the wall clock AND the current git
    revision, so every `devhub.jsonl` row is attributable to a commit.
  - Per-OPERATION lifecycle records (the reference tracer.zig's typed
    replica_commit/checkpoint span lifecycles, not thread aggregates):
    each prepare carries one pooled `OpRecord` stamped at every
    pipeline hand-off (bus arrival, request-queue, prepare, WAL queue
    vs write, quorum, commit-queue vs execute, reply, store-queue vs
    store), yielding an exact queue-wait vs service decomposition per
    stage — `lifecycle_summary()` reports p50/p99 per component plus
    Little's-law pipeline occupancy. The last N completed records form
    the FLIGHT RECORDER ring, dumped (JSON + Perfetto) when an anomaly
    trips: perceived latency beyond a multiple of the running p99, a
    stage stall beyond threshold, or a pipeline exception.
  - Device-step profiler: per-jit-entry device execution time
    (dispatch→finish, isolating device time from host time) and
    h2d/d2h transfer byte counters, entry names validated against the
    jaxlint JIT_ENTRIES manifest so kernel work is always attributable
    to a manifest-declared entry point.
  - Device-plane ledgers (devicestats.py surfaces these): owner-tagged
    device-memory gauges with high-water tracking, per-entry transfer
    bandwidth histograms stamped at the sanctioned sync seams, open
    dispatch-window accounting (flight dumps include it), and the
    Perfetto async device lane built from closed dispatch→finish pairs.

  - One clock with the device trace: the stage-granularity spans that
    tile the commit and store threads (ANNOTATED_SPANS) also enter a
    `jax.profiler.TraceAnnotation`, so a running `jax.profiler` session
    writes them onto the host plane of the same `*.xplane.pb` as the
    device planes; backend compiles land as span `device.compile` on the
    thread that compiled (`jax.monitoring`); and the time with no
    dispatch window open is span `device.unfed` — a whole-run lower
    bound on the device's idle time.

Thread model: every recording path (span/count/observe) writes only
thread-local state created lazily per thread and registered for merge;
`snapshot()`/`trace_events()` read across threads without stopping
them (merges are approximate only while writers are actively mid-
record, exact once they quiesce). `reset()` bumps a generation counter
— threads re-create state on their next record, so no cross-thread
mutation ever races a writer. Enable with TIGERBEETLE_TPU_TRACE=1 or
`tracer.enable()`; the disabled path is one module-global check and
allocates nothing.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time
from array import array
from collections import deque
from typing import Dict, List, Optional, Tuple

from tigerbeetle_tpu.tidy import runtime as tidy_runtime

log = logging.getLogger("tigerbeetle_tpu.tracer")

_enabled = os.environ.get("TIGERBEETLE_TPU_TRACE", "") not in ("", "0")

# --- histogram geometry (log-linear, HDR-lite) --------------------------
#
# Values are nanoseconds. 8 sub-buckets per power of two bound the
# relative quantization error at 1/8 = 12.5%; 488 buckets cover the full
# u64 range, so the array never saturates and merge = elementwise sum.

HIST_SUB_BITS = 3
_HIST_SUB = 1 << HIST_SUB_BITS
HIST_BUCKETS = (64 - HIST_SUB_BITS) * _HIST_SUB
_HIST_ZEROS = bytes(8 * HIST_BUCKETS)


def bucket_index(v: int) -> int:
    """Histogram bucket for a nanosecond value (v >= 0)."""
    if v < _HIST_SUB:
        return v
    msb = v.bit_length() - 1
    return ((msb - HIST_SUB_BITS + 1) << HIST_SUB_BITS) + (
        (v >> (msb - HIST_SUB_BITS)) - _HIST_SUB
    )


def bucket_value(idx: int) -> int:
    """Representative (midpoint) nanosecond value of a bucket."""
    if idx < 2 * _HIST_SUB:
        return idx
    octave = idx >> HIST_SUB_BITS
    sub = idx & (_HIST_SUB - 1)
    shift = octave - 1  # = msb - HIST_SUB_BITS
    lo = (_HIST_SUB + sub) << shift
    return lo + ((1 << shift) - 1) // 2


# --- per-thread recording state -----------------------------------------

RING_DEFAULT = 1 << 15  # span records per thread (~0.75 MiB each)

_ring_size = int(os.environ.get("TIGERBEETLE_TPU_TRACE_RING", RING_DEFAULT))
_registry_lock = tidy_runtime.make_lock("tracer.registry")
_states: List["_ThreadState"] = []  # tidy: guarded-by=_registry_lock
_generation = 0
# Gauges are last-write-wins from ANY thread (stage depths are set by the
# loop, the commit thread, and the store thread) while the metrics scrape
# iterates on the loop — so even the single-key set takes the lock: an
# unlocked dict resize racing `sorted(_gauges)` raises RuntimeError.
_gauges: Dict[str, float] = {}  # tidy: guarded-by=_registry_lock
# Device-plane ledgers (ISSUE 18, docs/OBSERVABILITY.md "Device plane").
# _device_mem: owner tag -> live device bytes (scratch ring buckets,
# balance tables); each write republishes the owner's
# `device.mem.<owner>.bytes` gauge and advances the high-water total. _device_inflight: entry -> {dispatch token:
# h2d bytes} — open dispatch windows, popped at the sanctioned finish
# seam (bounded per entry: an abandoned token is evicted, never leaked).
# _device_pairs: bounded ring of closed (entry, t0, t1, h2d, d2h)
# dispatch→finish windows feeding the Perfetto async device lane.
_device_mem: Dict[str, int] = {}  # tidy: guarded-by=_registry_lock
_device_mem_hw = [0]  # tidy: guarded-by=_registry_lock
_device_inflight: Dict[str, Dict[int, int]] = {}  # tidy: guarded-by=_registry_lock
_DEVICE_INFLIGHT_MAX = 64  # per entry; beyond = abandoned tokens
_device_pairs: deque = deque(maxlen=4096)  # tidy: guarded-by=_registry_lock
# [open dispatch windows across all entries, perf_counter_ns at which the
# count last fell to 0 (0: no window has closed yet)]. The stretch from
# that instant to the next dispatch is `device.unfed`: the host had handed
# the device nothing whose result it had not already taken back.
_device_open = [0, 0]  # tidy: guarded-by=_registry_lock
_tls = threading.local()

# The tiling of the two worker threads (docs/OBSERVABILITY.md, "Thread
# tiling"): on its thread every LEAF is disjoint from the others, so leaf
# seconds may be added up, and busy seconds are elapsed minus the WAITS.
COMMIT_LEAVES = (
    "sm.ct.stage", "sm.ct.prefetch", "sm.ct.dispatch", "sm.ct.sync",
    "sm.ct.post", "sm.ct.serial", "replica.execute.tail", "stage.reply",
    "stage.complete",
)
COMMIT_WAITS = ("pipeline.commit.idle", "pipeline.store.stall", "sm.store.barrier")
STORE_LEAVES = (
    "sm.store.log", "sm.store.idx", "sm.store.rows", "sm.store.query",
    "sm.beat", "pipeline.store.prefetch",
)
STORE_WAITS = ("pipeline.store.idle",)
TILING_PARENTS = ("replica.execute", "stage.store_async")
# Spans that also enter a `jax.profiler.TraceAnnotation` (attach_jax): the
# tiling, the two parents around it, and below stage granularity the
# compaction merges (where the device's time goes) and the WAL write. A
# fixed list and not every span: the store thread's `lsm.*` spans run
# thousands a second.
ANNOTATED_SPANS = frozenset(
    COMMIT_LEAVES + COMMIT_WAITS + STORE_LEAVES + STORE_WAITS + TILING_PARENTS
    + ("lsm.compact.merge", "wal.write")
)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# jax.profiler.TraceAnnotation once JAX is in the process and the tracer
# is on (attach_jax); None keeps every span a plain span.
_annotation = None  # tidy: atomic — set once, by attach_jax
_compile_listener_on = False  # tidy: guarded-by=_registry_lock


class _ThreadState:
    """One thread's private recording arena: aggregate table, histograms,
    counters, span-object pool, and the bounded span ring (parallel
    preallocated arrays — no allocation per record)."""

    __slots__ = (
        "gen", "tid", "name", "agg", "hist", "counters", "pool",
        "ring_event", "ring_t0", "ring_t1", "ring_n", "ring_mask",
    )

    def __init__(self, gen: int, ring_size: int) -> None:
        t = threading.current_thread()
        self.gen = gen
        self.tid = t.ident or 0
        self.name = t.name
        self.agg: Dict[str, list] = {}  # event -> [count, total_ns, max_ns]
        self.hist: Dict[str, array] = {}
        self.counters: Dict[str, int] = {}
        self.pool: List[_Span] = []
        self.ring_mask = ring_size - 1
        self.ring_event: List[Optional[str]] = [None] * ring_size
        self.ring_t0 = array("q", bytes(8 * ring_size))
        self.ring_t1 = array("q", bytes(8 * ring_size))
        self.ring_n = 0

    def record(self, event: str, t0: int, t1: int) -> None:
        dt = t1 - t0
        agg = self.agg.get(event)
        if agg is None:
            agg = self.agg[event] = [0, 0, 0]
            self.hist[event] = array("q", _HIST_ZEROS)
        agg[0] += 1
        agg[1] += dt
        if dt > agg[2]:
            agg[2] = dt
        self.hist[event][bucket_index(dt if dt > 0 else 0)] += 1
        i = self.ring_n & self.ring_mask
        self.ring_event[i] = event
        self.ring_t0[i] = t0
        self.ring_t1[i] = t1
        self.ring_n += 1


def _state() -> _ThreadState:
    st = getattr(_tls, "state", None)
    while st is None or st.gen != _generation:
        st = _ThreadState(_generation, _ring_size)
        with _registry_lock:
            # Registration is atomic with the generation check: a reset()
            # that raced the state's creation already cleared the registry,
            # and registering the stale arena would leak it (and its
            # records) into every later snapshot. Rebuild against the new
            # generation instead.
            if st.gen == _generation:
                _states.append(st)
                _tls.state = st
                break
        st = None
    return st


class _Span:
    """Reusable timed-region context manager (pooled per thread)."""

    __slots__ = ("state", "event", "t0", "annotation")

    def __enter__(self) -> "_Span":
        if self.annotation is not None:
            self.annotation.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        state = self.state
        state.record(self.event, self.t0, time.perf_counter_ns())
        if self.annotation is not None:
            self.annotation.__exit__(exc_type, exc, tb)
            self.annotation = None
        if len(state.pool) < 64:
            state.pool.append(self)
        return False


class _NullSpan:
    """Singleton no-op span: the disabled path allocates nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NULL_SPAN = _NullSpan()


def null_span() -> _NullSpan:
    """The shared no-op span, for callers that decide span identity
    themselves (e.g. anonymous trees skipping their flush-phase rows)."""
    return _NULL_SPAN


# --- control ------------------------------------------------------------


def enable() -> None:
    global _enabled
    _enabled = True
    if "jax" in sys.modules:
        attach_jax()


def attach_jax() -> None:
    """JAX is in this process (the state machine's jax backend calls this
    before its first device call; `enable()` calls it when JAX was there
    first): from here on the ANNOTATED_SPANS enter a profiler annotation
    and backend compiles are recorded on the thread that compiled. Nothing
    happens with the tracer off, and the numpy backend never comes here,
    so it never imports JAX on the tracer's account."""
    global _annotation, _compile_listener_on
    if not _enabled:
        return
    import jax.monitoring
    import jax.profiler

    _annotation = jax.profiler.TraceAnnotation
    with _registry_lock:
        register = not _compile_listener_on
        _compile_listener_on = True
    if register:
        jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def _on_jax_duration(event: str, seconds: float, **_kw) -> None:
    """`jax.monitoring` calls this on the compiling thread. The backend-
    compile event fires for a compile and for a read from the persistent
    cache alike (either is a shape the process had not seen); a cache
    read announces itself first, by its own event."""
    if not _enabled:
        return
    if event == COMPILE_EVENT:
        observe("device.compile", int(seconds * 1e9))
    elif event == CACHE_READ_EVENT:
        count("device.compile.cache_reads")


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def reset() -> None:
    """Discard every thread's recorded data and all gauges. Threads
    re-create their state lazily (generation bump), so no cross-thread
    mutation races an active writer; a span straddling the reset lands
    in its old, now-unregistered arena and is dropped."""
    global _generation
    with _registry_lock:
        _generation += 1
        _states.clear()
        _gauges.clear()
        # Lifecycle state re-arms with the spans: ring, pool, running
        # perceived histogram, summary window, and the dump budget.
        _op_ring.clear()
        _op_pool.clear()
        _op_hist[:] = array("q", _HIST_ZEROS)
        _op_window[0] = _op_window[1] = _op_window[2] = 0
        _flight["dumps"] = 0
        _flight["exception_dumps"] = 0
        _flight["last_dump_ns"] = 0
        # Device-plane ledgers re-arm with the registry.
        _device_mem.clear()
        _device_mem_hw[0] = 0
        _device_inflight.clear()
        _device_pairs.clear()
        _device_open[0] = _device_open[1] = 0


def configure(ring_size: Optional[int] = None) -> None:
    """Set the per-thread span-ring capacity (rounded up to a power of
    two). Implies reset(): existing rings are discarded."""
    global _ring_size
    if ring_size is not None:
        n = 1
        while n < ring_size:
            n <<= 1
        _ring_size = n
    reset()


# --- recording ----------------------------------------------------------


def span(event: str):
    """Time a scoped region under `event` (tracer.zig start/end). Enabled
    cost: two perf_counter_ns calls + one pooled object; disabled cost:
    one flag check, zero allocation."""
    if not _enabled:
        return _NULL_SPAN
    st = _state()
    pool = st.pool
    s = pool.pop() if pool else _Span()
    s.state = st
    s.event = event
    s.annotation = (
        _annotation(event)
        if _annotation is not None and event in ANNOTATED_SPANS else None
    )
    return s


def observe(event: str, duration_ns: int) -> None:
    """Record an externally measured duration under `event` (ending now):
    same aggregation/histogram/ring as a span — for callers that already
    hold the two timestamps (stage idle/stall accounting, benchmark
    latencies folded into the registry)."""
    if not _enabled:
        return
    t1 = time.perf_counter_ns()
    _state().record(event, t1 - duration_ns, t1)


def count(event: str, n: int = 1) -> None:
    """Bump a counter without timing (statsd.zig counter semantics).
    Per-thread storage: exact under concurrent bumps from the WAL,
    commit, and store threads."""
    if not _enabled:
        return
    st = _state()
    st.counters[event] = st.counters.get(event, 0) + n


def gauge(name: str, value: float) -> None:
    """Set a last-write-wins gauge (queue depths, table counts)."""
    if not _enabled:
        return
    with _registry_lock:
        _gauges[name] = value


def remove_gauge(name: str) -> None:
    """Retire a gauge whose identity died (a closed connection's send
    queue): per-instance gauge families must not grow without bound."""
    if not _enabled:
        return
    with _registry_lock:
        _gauges.pop(name, None)


def remove_gauges_prefix(prefix: str) -> None:
    """Retire every gauge under a name prefix — the per-peer families
    (`vsr.peer.<r>.*`) when a peer connection unmaps: a dead peer must
    not keep serving stale offset/lag values on every scrape, and the
    registry must stay size-stable across connection churn (the same
    leak class as the per-conn send-queue gauges)."""
    if not _enabled:
        return
    with _registry_lock:
        for name in [n for n in _gauges if n.startswith(prefix)]:
            del _gauges[name]


def gauges() -> Dict[str, float]:
    with _registry_lock:
        return dict(_gauges)


# --- device memory ledger (owner-tagged live device bytes) ---------------
#
# Who holds device memory right now, by owner tag: the dispatch scratch
# ring's generation-keyed buckets (`scratch.<entry>.b<n_pad>`) and the
# resident balance tables (`balances`).
# Byte counts are `.nbytes` shape metadata — never a device sync — and
# every write republishes the owner's `device.mem.<owner>.bytes` gauge
# so the ledger rides the ordinary scrape surface. The high-water mark
# is the lifecycle flat key `device_mem_high_water_bytes` (bench-gated).


def device_mem_set(owner: str, nbytes: int) -> None:
    """Set an owner's live device bytes (absolute)."""
    if not _enabled:
        return
    with _registry_lock:
        _device_mem[owner] = int(nbytes)
        _gauges[f"device.mem.{owner}.bytes"] = float(nbytes)
        total = sum(_device_mem.values())
        if total > _device_mem_hw[0]:
            _device_mem_hw[0] = total


def device_mem_retire_prefix(prefix: str) -> None:
    """Retire every ledger owner (and gauge) under a tag prefix — the
    scratch-ring bucket families (`scratch.<entry>.b<n_pad>`) when a
    workload shift strands a bucket shape that is never reused: the
    ledger and the gauge registry must stay bounded under bucket churn
    (same leak class as the per-peer gauge retirement)."""
    if not _enabled:
        return
    with _registry_lock:
        for owner in [o for o in _device_mem if o.startswith(prefix)]:
            del _device_mem[owner]
        gp = f"device.mem.{prefix}"
        for name in [n for n in _gauges if n.startswith(gp)]:
            del _gauges[name]


def device_mem_totals() -> dict:
    """Ledger snapshot: per-owner live bytes, the live total, and the
    process high-water total (monotone until reset)."""
    with _registry_lock:
        owners = dict(_device_mem)
        hw = _device_mem_hw[0]
    return {
        "owners": owners,
        "total_bytes": sum(owners.values()),
        "high_water_bytes": hw,
    }


def device_inflight() -> dict:
    """Open dispatch windows right now: per-entry count of dispatched-
    but-unfinished tokens, plus the total window depth."""
    with _registry_lock:
        per = {e: len(toks) for e, toks in _device_inflight.items() if toks}
    return {"entries": per, "window_depth": sum(per.values())}


# --- per-operation lifecycle (queue-wait vs service decomposition) ------
#
# One pooled OpRecord per prepare, stamped at every pipeline hand-off.
# The stamps are plain perf_counter_ns writes into a preallocated array
# slot; each stamp index is written by exactly one thread at a known
# hand-off point, and the record travels WITH the op (message attribute /
# job dict), so stamp writes are ordered by the same queue hand-offs that
# order the op itself — no locking on the stamp path. Finalization
# (op_finish, loop thread) observes the derived components into the
# ordinary span histograms and files the record in the flight ring.

# Stamp indices. Components telescope: the window components (request →
# reply) tile [ARRIVE, REPLY] exactly, so their means sum to the mean
# server-perceived latency by construction. Store components trail the
# reply (the async store stage runs behind it) and are reported
# separately.
(
    OP_ARRIVE, OP_PREPARE, OP_WAL_ENQUEUE, OP_WAL_WRITE, OP_WAL_DURABLE,
    OP_COMMIT_SUBMIT, OP_EXEC_START, OP_EXEC_END, OP_REPLY,
    OP_STORE_SUBMIT, OP_STORE_START, OP_STORE_END,
) = range(12)
OP_STAMPS = 12
OP_STAMP_NAMES = (
    "arrive", "prepare", "wal_enqueue", "wal_write", "wal_durable",
    "commit_submit", "exec_start", "exec_end", "reply",
    "store_submit", "store_start", "store_end",
)

# (event, from-stamp, to-stamp): the arrive→reply window decomposition.
OP_COMPONENTS = (
    ("op.queue.request", OP_ARRIVE, OP_PREPARE),
    ("op.service.prepare", OP_PREPARE, OP_WAL_ENQUEUE),
    ("op.queue.wal", OP_WAL_ENQUEUE, OP_WAL_WRITE),
    ("op.service.wal", OP_WAL_WRITE, OP_WAL_DURABLE),
    ("op.queue.quorum", OP_WAL_DURABLE, OP_COMMIT_SUBMIT),
    ("op.queue.commit", OP_COMMIT_SUBMIT, OP_EXEC_START),
    ("op.service.execute", OP_EXEC_START, OP_EXEC_END),
    ("op.service.reply", OP_EXEC_END, OP_REPLY),
)
# Store components trail the reply; excluded from the perceived window.
OP_STORE_COMPONENTS = (
    ("op.queue.store", OP_STORE_SUBMIT, OP_STORE_START),
    ("op.service.store", OP_STORE_START, OP_STORE_END),
)
_OP_ZEROS = bytes(8 * OP_STAMPS)

# Per-peer prepare_ok arrival stamps (cluster-plane telemetry,
# docs/OBSERVABILITY.md): slot index = acking replica index. Active
# replica counts are ≤ 6 (reference constants.zig); 8 keeps the array
# power-of-two and leaves headroom. Stamped by vsr/peerstats.py on the
# primary's loop thread with the same discipline as the lifecycle
# stamps: the record travels with the op, each slot is written by
# exactly one thread at a known hand-off, partial records on view
# change are closed, never fabricated.
OP_PEER_MAX = 8
_PEER_ZEROS = bytes(8 * OP_PEER_MAX)


class OpRecord:
    """One prepare's lifecycle: identity + stamp array. Pooled — reset()
    zeroes in place, no per-op allocation at steady state."""

    __slots__ = (
        "op", "client", "request", "operation", "n_events", "t", "done",
        "released", "peer_t", "peer_bcast", "quorum_t", "quorum_peer",
        "peers_open", "ring_evicted",
    )

    def __init__(self) -> None:
        self.t = array("q", _OP_ZEROS)
        self.peer_t = array("q", _PEER_ZEROS)
        self.reset()

    def reset(self) -> None:
        self.op = 0
        self.client = 0
        self.request = 0
        self.operation = 0
        self.n_events = 0
        self.done = False
        # Set by op_store_done: no thread holds the record any longer,
        # so an eviction may recycle it (see op_finish). Fault-dropped
        # records are never released and fall to the GC instead.
        self.released = False
        # Cluster-plane stamps (vsr/peerstats.py, primary only):
        # broadcast time, per-peer prepare_ok arrivals, the q-th arrival
        # that completed the quorum and which peer it came from.
        # peers_open: the primary's straggler tracker still holds the
        # record (a post-quorum ack may yet stamp it) — eviction must
        # not recycle it until the tracker lets go.
        self.peer_bcast = 0
        self.quorum_t = 0
        self.quorum_peer = -1
        self.peers_open = False
        # The flight ring evicted this record while its peer window was
        # still open (a down peer holds windows open for TRACK_MAX ops,
        # past the ring's eviction horizon): op_peer_release re-offers
        # it to the pool once the tracker lets go, so a degraded period
        # — exactly when the plane matters — stays allocation-free.
        self.ring_evicted = False
        t = self.t
        for i in range(OP_STAMPS):
            t[i] = 0
        pt = self.peer_t
        for i in range(OP_PEER_MAX):
            pt[i] = 0


OP_RING_DEFAULT = 128  # completed records retained for the flight dump

# Clamped ≥ 1: FLIGHT_OPS=0 must degrade to a one-record ring, never an
# empty-deque pop on the first completed op.
_op_ring_size = max(
    1, int(os.environ.get("TIGERBEETLE_TPU_FLIGHT_OPS", OP_RING_DEFAULT))
)
_op_ring: deque = deque()  # tidy: guarded-by=_registry_lock
_op_pool: List[OpRecord] = []  # tidy: guarded-by=_registry_lock
# Running histogram of server-perceived latency (arrive→reply) — the
# anomaly detector's "running p99" source; independent of the per-thread
# arenas so reset generations cannot skew the trip threshold mid-window.
_op_hist = array("q", _HIST_ZEROS)  # tidy: guarded-by=_registry_lock
# [first_finalize_ns, last_finalize_ns, perceived_count]: the summary
# window for Little's-law occupancy.
_op_window = [0, 0, 0]  # tidy: guarded-by=_registry_lock

# Flight-recorder policy. latency_mult: trip when perceived latency
# exceeds mult × running p99; stall_ns: trip when any single component
# exceeds this; min_ops: samples required before the latency rule arms;
# max_dumps/cooldown_ns: disk-spam bounds.
_flight = {  # tidy: guarded-by=_registry_lock
    "latency_mult": float(os.environ.get("TIGERBEETLE_TPU_FLIGHT_MULT", 8.0)),
    "stall_ns": int(
        float(os.environ.get("TIGERBEETLE_TPU_FLIGHT_STALL_MS", 2000.0)) * 1e6
    ),
    "min_ops": 64,
    "max_dumps": 3,
    "cooldown_ns": 5_000_000_000,
    "dir": os.environ.get("TIGERBEETLE_TPU_FLIGHT_DIR", ""),
    "dumps": 0,
    # Pipeline-exception trips specifically (flight_exception), counted
    # even when the dump itself was rate-limited: "did an exception
    # happen" must be answerable separately from "did a latency anomaly
    # trip" — an election legitimately trips the stall rule, an
    # exception never legitimately happens (the failover audit asserts
    # this stays 0).
    "exception_dumps": 0,
    "last_dump_ns": 0,
}


def op_begin() -> Optional[OpRecord]:
    """Claim a pooled lifecycle record (None when tracing is disabled —
    every op_* accessor below accepts None and returns immediately, so
    the disabled path stays allocation-free)."""
    if not _enabled:
        return None
    with _registry_lock:
        rec = _op_pool.pop() if _op_pool else None
    if rec is None:
        return OpRecord()
    rec.reset()
    return rec


def op_stamp(rec: Optional[OpRecord], idx: int, t_ns: Optional[int] = None) -> None:
    """Record one hand-off stamp (now, or an injected t_ns for scripted
    tests). Overwrites: a requeued op (grid repair) re-stamps, so the
    decomposition reflects the final successful pass."""
    if rec is None:
        return
    rec.t[idx] = time.perf_counter_ns() if t_ns is None else t_ns


def op_stamp_first(rec: Optional[OpRecord], idx: int) -> None:
    """Stamp only if unset — the double-buffered device path marks
    exec-start at dispatch; the settle path must not overwrite it."""
    if rec is None or rec.t[idx]:
        return
    rec.t[idx] = time.perf_counter_ns()


def op_clear(rec: Optional[OpRecord], *indices: int) -> None:
    """Unset stamps on a requeued op (grid-repair reclaim): the retry
    re-stamps through op_stamp_first, so the decomposition reflects the
    final successful pass, not the faulted one."""
    if rec is None:
        return
    for i in indices:
        rec.t[i] = 0


def op_meta(rec: Optional[OpRecord], op: int = 0, client: int = 0,
            request: int = 0, operation: int = 0, n_events: int = 0) -> None:
    if rec is None:
        return
    rec.op = op
    rec.client = client
    rec.request = request
    rec.operation = operation
    rec.n_events = n_events


def _op_components(rec: OpRecord, table) -> List[tuple]:
    """[(event, duration_ns)] for components whose BOTH stamps landed.
    Negative spans (cross-thread clock skew or out-of-order hand-offs on
    multi-replica quorums) clamp to 0 — the histograms need v >= 0."""
    t = rec.t
    out = []
    for event, a, b in table:
        ta, tb = t[a], t[b]
        if ta and tb:
            out.append((event, tb - ta if tb > ta else 0))
    return out


def op_finish(rec: Optional[OpRecord]) -> None:
    """Finalize the arrive→reply window: observe every component and the
    totals into the registry histograms, file the record in the flight
    ring, and run the anomaly checks. Called once per op on the loop
    thread (completion application); idempotent via rec.done. Store
    components land later via op_store_done — the record is already in
    the ring and the store thread fills its stamps in place."""
    if rec is None or rec.done:
        return
    rec.done = True
    comps = _op_components(rec, OP_COMPONENTS)
    queue_total = 0
    service_total = 0
    worst = ("", 0)
    for event, d in comps:
        observe(event, d)
        if ".queue." in event:
            queue_total += d
        else:
            service_total += d
        if d > worst[1]:
            worst = (event, d)
    t = rec.t
    perceived = t[OP_REPLY] - t[OP_ARRIVE] if t[OP_REPLY] and t[OP_ARRIVE] else 0
    if perceived > 0:
        # Totals only for FULL arrive→reply records: a journal-path
        # commit (backup/catch-up — execute+store stamps only) would
        # otherwise dilute the gated queue_wait/service_total
        # distributions toward its missing components.
        observe("op.queue.total", queue_total)
        observe("op.service.total", service_total)
    trip = None
    with _registry_lock:
        now = time.perf_counter_ns()
        if not _op_window[0]:
            _op_window[0] = now
        _op_window[1] = now
        if len(_op_ring) >= _op_ring_size:
            evicted = _op_ring.popleft()
            # Recycle only records no thread can still stamp: RELEASED
            # (store phase fully reported — op_store_done ran; a
            # backpressured store backlog may trail arbitrarily) AND
            # WAL-complete (a quorum can commit before the local WAL
            # entry leaves the writer queue, which holds the record
            # until its durable stamp lands). Anything else falls to
            # the GC — a trailing stamp into a reset record would
            # corrupt a fresh op.
            et = evicted.t
            if (
                evicted.released
                and not evicted.peers_open
                and (not et[OP_WAL_ENQUEUE] or et[OP_WAL_DURABLE])
            ):
                # peers_open: the primary's straggler tracker
                # (vsr/peerstats.py) may still stamp a late prepare_ok
                # into peer_t — recycling would let that trailing stamp
                # corrupt a fresh op. Such records are marked instead
                # and re-offered by op_peer_release when the tracker
                # lets go (a down peer would otherwise starve the pool
                # for its whole outage).
                _op_pool.append(evicted)
            else:
                evicted.ring_evicted = True
        _op_ring.append(rec)
        if perceived > 0:
            if _op_window[2] >= _flight["min_ops"]:
                p99 = _hist_percentile(_op_hist, _op_window[2], 0.99)
                if p99 > 0 and perceived > _flight["latency_mult"] * p99:
                    trip = (
                        f"latency: perceived {perceived / 1e6:.1f} ms > "
                        f"{_flight['latency_mult']:g}x running p99 "
                        f"{p99 / 1e6:.1f} ms (op {rec.op})"
                    )
            _op_hist[bucket_index(perceived)] += 1
            _op_window[2] += 1
        if trip is None and worst[1] > _flight["stall_ns"]:
            trip = (
                f"stall: {worst[0]} {worst[1] / 1e6:.1f} ms > "
                f"{_flight['stall_ns'] / 1e6:.0f} ms threshold (op {rec.op})"
            )
    if perceived > 0:
        observe("op.perceived", perceived)
    if trip is not None:
        flight_trip(trip)


def op_store_done(rec: Optional[OpRecord]) -> None:
    """Observe the trailing store components (store thread, after the
    op's reply is long gone) and run the stall check on them."""
    if rec is None:
        return
    worst = ("", 0)
    for event, d in _op_components(rec, OP_STORE_COMPONENTS):
        observe(event, d)
        if d > worst[1]:
            worst = (event, d)
    with _registry_lock:
        stall_ns = _flight["stall_ns"]
    if worst[1] > stall_ns:
        flight_trip(
            f"stall: {worst[0]} {worst[1] / 1e6:.1f} ms > "
            f"{stall_ns / 1e6:.0f} ms threshold (op {rec.op})"
        )
    # Last touch of the record: eviction may now recycle it.
    rec.released = True


def op_peer_release(rec: Optional[OpRecord]) -> None:
    """The peer tracker (vsr/peerstats.py) let go of a record. If the
    flight ring already evicted it while the window was open (a down
    peer holds windows for TRACK_MAX ops, past the ring horizon), pool
    it now — provided every OTHER holder is also done (same conditions
    as the eviction path); otherwise it falls to the GC as before."""
    if rec is None:
        return
    rec.peers_open = False
    if not rec.ring_evicted:
        return  # still in the ring; eviction will pool it
    t = rec.t
    if rec.released and (not t[OP_WAL_ENQUEUE] or t[OP_WAL_DURABLE]):
        rec.ring_evicted = False
        with _registry_lock:
            _op_pool.append(rec)


def op_record_dict(rec: OpRecord) -> dict:
    """JSON-ready view of one lifecycle record: raw stamps share the
    perf_counter timebase with trace_events(), so a flight dump and its
    companion Perfetto trace align op-for-op."""
    t = rec.t
    comps = {
        e: round(d / 1e6, 3)
        for e, d in _op_components(rec, OP_COMPONENTS + OP_STORE_COMPONENTS)
    }
    out = {
        "op": rec.op, "client": rec.client, "request": rec.request,
        "operation": rec.operation, "n_events": rec.n_events,
        "stamps": {
            OP_STAMP_NAMES[i]: t[i] for i in range(OP_STAMPS) if t[i]
        },
        "components": comps,
    }
    if t[OP_REPLY] and t[OP_ARRIVE]:
        out["perceived_ms"] = round((t[OP_REPLY] - t[OP_ARRIVE]) / 1e6, 3)
    if rec.peer_bcast:
        # Cluster-plane sub-rows (primary-proposed prepares only): each
        # peer's prepare_ok arrival relative to the broadcast, plus the
        # quorum point — trace_summary --ops renders these under the
        # queue.quorum component so a straggling link is visible in a
        # flight dump.
        oks = {
            str(r): round((rec.peer_t[r] - rec.peer_bcast) / 1e6, 3)
            for r in range(OP_PEER_MAX) if rec.peer_t[r]
        }
        if oks:
            out["peer_ok_ms"] = oks
        if rec.quorum_t:
            out["quorum_ms"] = round((rec.quorum_t - rec.peer_bcast) / 1e6, 3)
            if rec.quorum_peer >= 0:
                out["quorum_peer"] = rec.quorum_peer
    return out


def flight_records() -> List[dict]:
    """The completed-op ring as JSON-ready dicts (newest last).
    Serialized UNDER the lock: an eviction may recycle (reset + restamp)
    a record concurrently, and a dict mixing two ops' fields would
    corrupt exactly the post-hoc artifact this ring exists for."""
    with _registry_lock:
        return [op_record_dict(r) for r in _op_ring]


def configure_flight(
    latency_mult: Optional[float] = None,
    stall_ms: Optional[float] = None,
    min_ops: Optional[int] = None,
    max_dumps: Optional[int] = None,
    cooldown_s: Optional[float] = None,
    directory: Optional[str] = None,
    ring: Optional[int] = None,
) -> None:
    """Adjust flight-recorder policy; ring resizes (and clears) the
    completed-op ring."""
    global _op_ring_size
    with _registry_lock:
        if latency_mult is not None:
            _flight["latency_mult"] = float(latency_mult)
        if stall_ms is not None:
            _flight["stall_ns"] = int(stall_ms * 1e6)
        if min_ops is not None:
            _flight["min_ops"] = int(min_ops)
        if max_dumps is not None:
            _flight["max_dumps"] = int(max_dumps)
        if cooldown_s is not None:
            _flight["cooldown_ns"] = int(cooldown_s * 1e9)
        if directory is not None:
            _flight["dir"] = directory
        if ring is not None:
            _op_ring_size = max(1, int(ring))
            _op_ring.clear()
            _op_pool.clear()


def flight_trip(reason: str) -> Optional[str]:
    """Dump the flight recorder (op records as JSON + the span rings as
    a Perfetto trace) for post-hoc causality on a tail anomaly. Rate
    limited (max_dumps per process + cooldown) so a pathological run
    cannot spam the disk. Returns the dump path, or None when
    suppressed."""
    if not _enabled:
        return None
    with _registry_lock:
        now = time.perf_counter_ns()
        if _flight["dumps"] >= _flight["max_dumps"]:
            return None
        if now - _flight["last_dump_ns"] < _flight["cooldown_ns"] and _flight["dumps"]:
            return None
        _flight["dumps"] += 1
        seq = _flight["dumps"]
        _flight["last_dump_ns"] = now
        # Serialize under the lock (see flight_records): a concurrent
        # evict-and-recycle must not mix two ops into one dump record.
        recs = [op_record_dict(r) for r in _op_ring]
        directory = _flight["dir"]
        # Device state at trip time (ISSUE 18): open dispatch windows
        # per entry, total window depth, and the memory-ledger totals —
        # an anomaly dump must show what the device was holding/running
        # when the tail event landed.
        dev_inflight = {
            e: len(toks) for e, toks in _device_inflight.items() if toks
        }
        dev_mem = dict(_device_mem)
        dev_hw = _device_mem_hw[0]
    if not directory:
        import tempfile

        directory = tempfile.gettempdir()
    base = os.path.join(directory, f"tbtpu_flight_{os.getpid()}_{seq}")
    doc = {
        "reason": reason,
        "tripped_ns": now,
        "ops": recs,
        "device": {
            "inflight": dev_inflight,
            "window_depth": sum(dev_inflight.values()),
            "mem": dev_mem,
            "mem_total_bytes": sum(dev_mem.values()),
            "mem_high_water_bytes": dev_hw,
        },
    }
    try:
        with open(base + ".json", "w") as f:
            json.dump(doc, f)
        with open(base + "_trace.json", "w") as f:
            json.dump(export_trace(), f)
    except OSError:
        return None  # read-only disk must not take the pipeline down
    count("mark.flight_dump")
    log.warning(
        "flight recorder tripped (%s) — dumped %d op records to %s.json "
        "(+ Perfetto %s_trace.json; waterfall: python tools/trace_summary.py "
        "--ops %s.json)", reason, len(doc["ops"]), base, base, base,
    )
    return base + ".json"


def flight_exception(reason: str) -> Optional[str]:
    """Pipeline-exception trip (stage poison / fail-stop dispatch): dump
    unconditionally of the latency rules — the causal window before a
    crash is exactly what the recorder exists for. Counted separately
    from anomaly trips (and even when the dump was rate-limited) so an
    audit can ask "did any exception happen" without false positives
    from legitimate latency trips."""
    with _registry_lock:
        _flight["exception_dumps"] += 1
    return flight_trip(f"exception: {reason}")


_OCCUPANCY_STAGES = {  # tidy: atomic — immutable constant table, never written after import
    "wal": ("op.queue.wal", "op.service.wal"),
    "execute": ("op.queue.commit", "op.service.execute"),
    "store": ("op.queue.store", "op.service.store"),
    "total": ("op.perceived",),
}


def _op_window_ns() -> int:
    with _registry_lock:
        return max(0, _op_window[1] - _op_window[0])


def perceived_p99_ms(state: Optional[dict] = None) -> Optional[float]:
    """Server-perceived (arrive→reply) p99 in milliseconds from the
    running lifecycle histogram (the same source as the flight recorder's
    anomaly rule). With `state` — a caller-held dict, mutated in place —
    the percentile covers only ops finalized SINCE the previous call with
    that dict: the admission layer's polling window, which must recover
    once an overload passes (a lifetime percentile would stay tripped
    forever after one burst). An EMPTY window (priming call, or zero ops
    finalized — e.g. a total commit stall, when latency is at its worst)
    returns None: "no evidence", so the caller HOLDS its previous armed
    state instead of failing open."""
    with _registry_lock:
        cur = list(_op_hist)
        total = _op_window[2]
    if state is None:
        return _hist_percentile(cur, total, 0.99) / 1e6 if total else 0.0
    prev, prev_total = state.get("hist"), state.get("total", 0)
    state["hist"] = cur
    state["total"] = total
    if prev is None or total <= prev_total:
        return None
    delta = [c - p for c, p in zip(cur, prev)]
    return _hist_percentile(delta, total - prev_total, 0.99) / 1e6


def _stage_occupancy(total_ms_of, window_ns: int) -> Dict[str, float]:
    """Little's-law stage occupancy from per-event total milliseconds
    (shared by lifecycle_summary and the /metrics gauges — the scrape
    reuses its own snapshot instead of paying a second merge)."""
    if window_ns <= 0:
        return {}
    return {
        stage: round(sum(total_ms_of(e) for e in events) * 1e6 / window_ns, 3)
        for stage, events in _OCCUPANCY_STAGES.items()
    }


def lifecycle_summary() -> dict:
    """The per-op decomposition from the registry: per-component
    count/mean/p50/p99 (ms), the server-perceived window, Little's-law
    pipeline occupancy (component total time / summary window — mean
    prepares resident per stage), and flight-recorder status. `flat`
    holds the benchmark-facing key set (queue_wait_*/service_*/
    occupancy_*) that bench.py records and tools/bench_gate.py gates."""
    agg, hists, counters = _merged()
    with _registry_lock:
        first, last, _n = _op_window
        flight = {
            "dumps": _flight["dumps"],
            "exception_dumps": _flight["exception_dumps"],
            "ring": len(_op_ring),
            "latency_mult": _flight["latency_mult"],
            "stall_ms": round(_flight["stall_ns"] / 1e6, 1),
        }
    window_ns = max(0, last - first)
    components: Dict[str, dict] = {}
    flat: Dict[str, float] = {}
    occupancy: Dict[str, float] = {}

    def stats(event):
        rec = agg.get(event)
        if rec is None:
            return None
        n, total, _mx = rec
        h = hists.get(event)
        hn = sum(h) if h else 0
        return {
            "count": n,
            "mean_ms": round(total / n / 1e6, 4) if n else 0.0,
            "total_ms": round(total / 1e6, 3),
            "p50_ms": round(_hist_percentile(h, hn, 0.50) / 1e6, 4) if h else 0.0,
            "p99_ms": round(_hist_percentile(h, hn, 0.99) / 1e6, 4) if h else 0.0,
        }

    for event, _a, _b in OP_COMPONENTS + OP_STORE_COMPONENTS:
        s = stats(event)
        if s is None:
            continue
        short = event[len("op."):]
        components[short] = s
        key = short.replace("queue.", "queue_wait_").replace("service.", "service_")
        flat[f"{key}_ms"] = s["mean_ms"]
        flat[f"{key}_p50_ms"] = s["p50_ms"]
        flat[f"{key}_p99_ms"] = s["p99_ms"]
        if window_ns > 0:
            occupancy[short] = round(s["total_ms"] * 1e6 / window_ns, 3)
    for event, key in (
        ("op.queue.total", "queue_wait_total"),
        ("op.service.total", "service_total"),
        ("op.perceived", "lifecycle_perceived"),
    ):
        s = stats(event)
        if s is None:
            continue
        flat[f"{key}_ms"] = s["mean_ms"]
        flat[f"{key}_p50_ms"] = s["p50_ms"]
        flat[f"{key}_p99_ms"] = s["p99_ms"]
    # Store-stage hot rows, benchmark-gated (tools/bench_gate.py,
    # lower-better): the per-batch cost of the secondary query-index
    # build+flush (the device query-index pipeline's target row) and the
    # commit thread's backpressure stall behind the store stage.
    for event, key in (
        ("sm.store.query", "store_query_ms_per_batch"),
        ("pipeline.store.stall", "store_stall_ms_per_wait"),
    ):
        s = stats(event)
        if s is None:
            continue
        flat[key] = s["mean_ms"]
        flat[f"{key}_p50"] = s["p50_ms"]
        flat[f"{key}_p99"] = s["p99_ms"]
    # Multi-predicate query engine (models/state_machine.query_transfers,
    # docs/QUERY.md): whole-query latency from the sm.query span — plan,
    # driver scan, probes, limit-aware gather. query_p50_ms/query_p99_ms
    # are gated by tools/bench_gate.py (query section, lower-better).
    s = stats("sm.query")
    if s is not None:
        flat["query_ms"] = s["mean_ms"]
        flat["query_p50_ms"] = s["p50_ms"]
        flat["query_p99_ms"] = s["p99_ms"]
    # Cluster-plane replication rows (vsr/peerstats.py, primary only;
    # absent on single-replica runs): broadcast→prepare_ok arrival over
    # every REMOTE peer ack (replication lag as a latency distribution)
    # and the quorum→straggler-arrival overhang. The *_p99_ms keys are
    # gated by tools/bench_gate.py (cluster_plane section, >10% rule).
    for event, key in (
        ("vsr.replication.lag", "replication_lag"),
        ("vsr.quorum.straggler", "quorum_straggler"),
    ):
        s = stats(event)
        if s is None:
            continue
        flat[f"{key}_ms"] = s["mean_ms"]
        flat[f"{key}_p50_ms"] = s["p50_ms"]
        flat[f"{key}_p99_ms"] = s["p99_ms"]
    # Cross-batch commit-window occupancy (vsr/replica.py
    # _stage_note_inflight): one `pipeline.commit.inflight.d<depth>` count
    # per processed batch — mean in-flight dispatched batches, the
    # high-water, and the p99, exact. commit_depth is the CONFIGURED window
    # (pipeline.commit.depth_config gauge) so A/Bs across hosts can see
    # which depth the adaptive default actually selected.
    prefix = "pipeline.commit.inflight.d"
    depths = sorted(
        (int(name[len(prefix):]), n) for name, n in counters.items()
        if name.startswith(prefix) and n > 0
    )
    if depths:
        batches = sum(n for _d, n in depths)
        flat["commit_inflight_mean"] = round(
            sum(d * n for d, n in depths) / batches, 3
        )
        flat["commit_inflight_max"] = depths[-1][0]
        rank, seen = 0.99 * (batches - 1), 0
        for d, n in depths:
            seen += n
            if seen > rank:
                flat["commit_inflight_p99"] = float(d)
                break
    with _registry_lock:
        depth_cfg = _gauges.get("pipeline.commit.depth_config")
        device_hw = _device_mem_hw[0]
    if depth_cfg is not None:
        flat["commit_depth"] = float(depth_cfg)
    # Device memory high-water (ISSUE 18, docs/OBSERVABILITY.md "Device
    # plane"): peak simultaneous owner-tagged device bytes — bench.py's
    # device section records it and tools/bench_gate.py gates it
    # lower-better. Absent when no owner ever registered (numpy backend).
    if device_hw > 0:
        flat["device_mem_high_water_bytes"] = float(device_hw)
    # Stage occupancy: mean prepares resident per pipeline stage (wait +
    # service of that stage), plus the whole arrive→reply window.
    occupancy.update(_stage_occupancy(
        lambda e: agg[e][1] / 1e6 if e in agg else 0.0, window_ns
    ))
    for k in ("wal", "execute", "store", "total"):
        if k in occupancy:
            flat[f"occupancy_{k}"] = occupancy[k]
    perceived = stats("op.perceived") or {"count": 0}
    return {
        "ops": perceived["count"],
        "window_s": round(window_ns / 1e9, 3),
        "components": components,
        "perceived": perceived,
        "occupancy": occupancy,
        "flight": flight,
        "flat": flat,
    }


# --- device-step profiler -----------------------------------------------
#
# Per-jit-entry device execution time and transfer byte counters, keyed
# by the jaxlint JIT_ENTRIES manifest: an entry name this module has
# never heard of raises, so every device kernel's numbers stay
# attributable to a manifest-declared entry point (the same contract the
# retrace pass enforces on the call sites).

_device_entries_extra: set = set()  # tidy: guarded-by=_registry_lock


def register_device_entry(name: str) -> None:
    """Admit a runtime-built jit entry (mesh/sharded kernels) to the
    device-step namespace."""
    with _registry_lock:
        _device_entries_extra.add(name)


def _device_entry_check(entry: str) -> None:
    from tigerbeetle_tpu.tidy import manifest

    if entry in manifest.JIT_ENTRIES:
        return
    with _registry_lock:
        known = entry in _device_entries_extra
    if not known:
        raise ValueError(
            f"unknown device entry {entry!r}: add it to "
            "tidy/manifest.JIT_ENTRIES (or register_device_entry) so its "
            "kernel numbers stay attributable"
        )


def _device_window_open_locked() -> int:  # tidy: holds=_registry_lock
    """One more dispatch window is open. Returns when the last one closed
    if none was open till now (the caller records `device.unfed` from then
    to now, outside the lock), else 0."""
    idle_since = _device_open[1] if _device_open[0] == 0 else 0
    _device_open[0] += 1
    return idle_since


def _record_unfed(idle_since: int, now: int) -> None:
    # Another thread's window may have closed after `now` was read.
    if idle_since and now > idle_since:
        _state().record("device.unfed", idle_since, now)


def _device_window_close_locked(now: int) -> None:  # tidy: holds=_registry_lock
    _device_open[0] -= 1
    if _device_open[0] <= 0:
        _device_open[0] = 0
        _device_open[1] = now


class _DeviceStep:
    """A blocking jit entry's span, which is also one dispatch window."""

    __slots__ = ("span",)

    def __init__(self, span: "_Span") -> None:
        self.span = span

    def __enter__(self) -> "_DeviceStep":
        self.span.__enter__()
        with _registry_lock:
            idle_since = _device_window_open_locked()
        _record_unfed(idle_since, self.span.t0)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.span.__exit__(exc_type, exc, tb)
        with _registry_lock:
            _device_window_close_locked(time.perf_counter_ns())
        return False


def device_step(entry: str):
    """Span over a BLOCKING jit entry (call + materialization):
    `device.<entry>` — wall time the host spends inside the kernel."""
    if not _enabled:
        return _NULL_SPAN
    _device_entry_check(entry)
    return _DeviceStep(span(f"device.{entry}"))


def device_dispatch(entry: str, h2d_bytes: int = 0) -> int:
    """Mark an async kernel dispatch; returns the dispatch timestamp
    token for device_finish (0 when disabled). Counts the host→device
    bytes staged for the call and opens an in-flight window (the staged
    bytes ride the token so the finish seam can attribute h2d bandwidth
    over the same dispatch→finish interval). A window that opens with no
    other open, on any entry, ends a stretch of `device.unfed`."""
    if not _enabled:
        return 0
    _device_entry_check(entry)
    count(f"device.{entry}.dispatches")
    if h2d_bytes:
        count("device.h2d_bytes", h2d_bytes)
    token = time.perf_counter_ns()
    with _registry_lock:
        toks = _device_inflight.setdefault(entry, {})
        while token in toks:
            token += 1  # two dispatches in one clock tick are two windows
        toks[token] = h2d_bytes
        while len(toks) > _DEVICE_INFLIGHT_MAX:
            # Abandoned dispatches (e.g. a bail-path abandon_all that
            # never reaches a finish seam) must not grow the map, nor
            # hold the device "fed" for ever.
            del toks[next(iter(toks))]
            _device_window_close_locked(token)
        idle_since = _device_window_open_locked()
    _record_unfed(idle_since, token)
    return token


def device_finish(entry: str, token: int, d2h_bytes: int = 0) -> None:
    """Close a dispatch: `device.step.<entry>` is the dispatch→finish
    latency — the device execution window isolated from host time
    between the two calls. Stamped only at the sanctioned sync seams
    (tidy/manifest.JAXLINT_SYNC_SEAM), so the transfer-bandwidth
    attribution below never adds a sync of its own:
    `device.xfer.{h2d,d2h}.gbps` histograms hold RAW values in MB/s
    (= GB/s × 1000 — snapshot()'s `p50_us` field therefore reads
    directly as GB/s), and the closed window feeds the Perfetto async
    device lane ring."""
    if not _enabled or not token:
        return
    now = time.perf_counter_ns()
    dur = now - token
    observe(f"device.step.{entry}", dur)
    if d2h_bytes:
        count("device.d2h_bytes", d2h_bytes)
    with _registry_lock:
        toks = _device_inflight.get(entry)
        h2d_bytes = toks.pop(token, None) if toks else None
        if h2d_bytes is None:
            h2d_bytes = 0  # evicted as abandoned: its window is closed already
        else:
            _device_window_close_locked(now)
        _device_pairs.append((entry, token, now, h2d_bytes, d2h_bytes))
    if dur > 0:
        if h2d_bytes:
            observe("device.xfer.h2d.gbps", max(1, h2d_bytes * 1000 // dur))
        if d2h_bytes:
            observe("device.xfer.d2h.gbps", max(1, d2h_bytes * 1000 // dur))


def device_bytes(h2d: int = 0, d2h: int = 0) -> None:
    """Count transfer bytes for a blocking entry (device_step path)."""
    if not _enabled:
        return
    if h2d:
        count("device.h2d_bytes", h2d)
    if d2h:
        count("device.d2h_bytes", d2h)


# --- merge / snapshot ---------------------------------------------------


def _merged() -> Tuple[Dict[str, list], Dict[str, list], Dict[str, int]]:
    """(agg, hist, counters) merged across every registered thread state.
    Reads race active writers benignly: a concurrent insert can make one
    retry; totals are exact once writers quiesce."""
    agg: Dict[str, list] = {}
    hists: Dict[str, list] = {}
    counters: Dict[str, int] = {}
    with _registry_lock:
        states = list(_states)
    for st in states:
        for attempt in range(4):
            try:
                a_items = list(st.agg.items())
                h_items = list(st.hist.items())
                c_items = list(st.counters.items())
                break
            except RuntimeError:  # dict resized mid-iteration
                if attempt == 3:
                    a_items, h_items, c_items = [], [], []
        for event, (n, total, mx) in a_items:
            rec = agg.get(event)
            if rec is None:
                agg[event] = [n, total, mx]
            else:
                rec[0] += n
                rec[1] += total
                if mx > rec[2]:
                    rec[2] = mx
        for event, h in h_items:
            merged = hists.get(event)
            if merged is None:
                hists[event] = list(h)
            else:
                for i, v in enumerate(h):
                    if v:
                        merged[i] += v
        for event, n in c_items:
            counters[event] = counters.get(event, 0) + n
    return agg, hists, counters


def _hist_percentile(buckets: list, total: int, q: float) -> int:
    """q-quantile in nanoseconds from a merged bucket array."""
    if total <= 0:
        return 0
    rank = q * (total - 1)
    cum = 0
    for i, c in enumerate(buckets):
        if c:
            cum += c
            if cum > rank:
                return bucket_value(i)
    return bucket_value(HIST_BUCKETS - 1)


def snapshot() -> Dict[str, dict]:
    """event → {count, total_ms, avg_us, max_us, p50_us, p95_us, p99_us}
    for spans; event → {count, total_ms: 0, ...} for bare counters.
    Merged deterministically across every thread that recorded."""
    agg, hists, counters = _merged()
    out: Dict[str, dict] = {}
    for event in sorted(agg):
        n, total, mx = agg[event]
        rec = {
            "count": n,
            "total_ms": round(total / 1e6, 3),
            "avg_us": round(total / n / 1e3, 1) if n else 0.0,
            "max_us": round(mx / 1e3, 1),
        }
        h = hists.get(event)
        if h is not None:
            hn = sum(h)
            rec["p50_us"] = round(_hist_percentile(h, hn, 0.50) / 1e3, 1)
            rec["p95_us"] = round(_hist_percentile(h, hn, 0.95) / 1e3, 1)
            rec["p99_us"] = round(_hist_percentile(h, hn, 0.99) / 1e3, 1)
        out[event] = rec
    for event in sorted(counters):
        rec = out.get(event)
        if rec is None:
            out[event] = {
                "count": counters[event], "total_ms": 0.0,
                "avg_us": 0.0, "max_us": 0.0,
            }
        else:
            rec["count"] += counters[event]
    return out


def by_thread() -> Dict[str, Dict[str, Tuple[int, int]]]:
    """thread name → {event: (count, total_ns)}: the spans as each thread
    recorded them, unmerged. What says WHICH thread compiled, waited or
    staged; threads that share a name are added up."""
    with _registry_lock:
        states = list(_states)
    out: Dict[str, Dict[str, Tuple[int, int]]] = {}
    for st in states:
        mine = out.setdefault(st.name, {})
        for event, (n, total, _mx) in list(st.agg.items()):
            seen = mine.get(event, (0, 0))
            mine[event] = (seen[0] + n, seen[1] + total)
    return out


def emit_json() -> str:
    return json.dumps(snapshot())


# --- timeline export (Chrome trace-event / Perfetto) --------------------


def trace_events() -> List[tuple]:
    """[(event, thread_name, tid, t0_ns, t1_ns)] merged across threads,
    sorted by start time. Each thread contributes at most its ring
    capacity (oldest records overwritten)."""
    out: List[tuple] = []
    with _registry_lock:
        states = list(_states)
    for st in states:
        n = st.ring_n
        size = st.ring_mask + 1
        for j in range(max(0, n - size), n):
            i = j & st.ring_mask
            ev = st.ring_event[i]
            if ev is not None:
                out.append((ev, st.name, st.tid, st.ring_t0[i], st.ring_t1[i]))
    out.sort(key=lambda r: r[3])
    return out


def export_trace() -> dict:
    """Chrome trace-event JSON (the format ui.perfetto.dev and
    chrome://tracing load): one complete event ('ph': 'X') per span
    record, microsecond timestamps, plus thread-name metadata so the
    loop/WAL/commit/store threads are labeled rows."""
    pid = os.getpid()
    evs: List[dict] = []
    named: set = set()
    for event, name, tid, t0, t1 in trace_events():
        if tid not in named:
            named.add(tid)
            evs.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": name},
            })
        evs.append({
            "name": event, "cat": "tbtpu", "ph": "X", "pid": pid,
            "tid": tid, "ts": t0 / 1e3, "dur": (t1 - t0) / 1e3,
        })
    # Device lane (ISSUE 18): every closed dispatch→finish window as an
    # async span pair ('b'/'e', one id per window) so depth-N overlap is
    # VISIBLE — two in-flight dispatches of the same entry render as
    # overlapping spans on the entry's async track, which the per-thread
    # 'X' rows above structurally cannot show.
    with _registry_lock:
        pairs = list(_device_pairs)
    for i, (entry, t0, t1, h2d, d2h) in enumerate(pairs):
        common = {"name": entry, "cat": "device", "pid": pid, "tid": 0,
                  "id": i}
        evs.append({**common, "ph": "b", "ts": t0 / 1e3,
                    "args": {"h2d_bytes": h2d, "d2h_bytes": d2h}})
        evs.append({**common, "ph": "e", "ts": t1 / 1e3})
    # Timebase anchor: span timestamps are perf_counter_ns (process-
    # local). Pairing one perf reading with the wall clock lets
    # tools/cluster_trace.py map every event onto a shared wall
    # timeline and merge traces from separate replica processes
    # (Perfetto ignores unknown top-level keys).
    return {
        "traceEvents": evs,
        "displayTimeUnit": "ms",
        "timebase": {
            "perf_ns": time.perf_counter_ns(),
            "unix_ns": time.time_ns(),
            "pid": pid,
        },
    }


def dump(path: Optional[str] = None) -> str:
    """Write the merged trace as Perfetto-loadable JSON; returns the
    path (default: $TIGERBEETLE_TPU_TRACE_FILE or /tmp/tbtpu_trace.json)."""
    if path is None:
        path = os.environ.get(
            "TIGERBEETLE_TPU_TRACE_FILE", "/tmp/tbtpu_trace.json"
        )
    with open(path, "w") as f:
        json.dump(export_trace(), f)
    return path


# --- scrape surface (Prometheus text + HTTP) ----------------------------


def _label_escape(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def prometheus_text() -> str:
    """The registry in Prometheus text exposition format: spans as
    summaries (quantile series + _sum/_count), counters and gauges as
    label-keyed families (event names carry dots, so they ride in
    labels rather than metric names)."""
    snap = snapshot()
    spans = {e: r for e, r in snap.items() if "p50_us" in r}
    counters = {e: r for e, r in snap.items() if "p50_us" not in r}
    lines = [
        "# HELP tbtpu_span_seconds Traced span latency by event.",
        "# TYPE tbtpu_span_seconds summary",
    ]
    for e, r in spans.items():
        lab = f'event="{_label_escape(e)}"'
        for q, key in (("0.5", "p50_us"), ("0.95", "p95_us"), ("0.99", "p99_us")):
            lines.append(
                f'tbtpu_span_seconds{{{lab},quantile="{q}"}} {r[key] / 1e6:.9g}'
            )
        lines.append(f"tbtpu_span_seconds_sum{{{lab}}} {r['total_ms'] / 1e3:.9g}")
        lines.append(f"tbtpu_span_seconds_count{{{lab}}} {r['count']}")
    lines += [
        "# HELP tbtpu_span_max_seconds Maximum observed span latency.",
        "# TYPE tbtpu_span_max_seconds gauge",
    ]
    for e, r in spans.items():
        lines.append(
            f'tbtpu_span_max_seconds{{event="{_label_escape(e)}"}} '
            f"{r['max_us'] / 1e6:.9g}"
        )
    lines += [
        "# HELP tbtpu_events_total Counter registry (VSR/LSM/grid/bus marks).",
        "# TYPE tbtpu_events_total counter",
    ]
    for e, r in counters.items():
        lines.append(
            f'tbtpu_events_total{{event="{_label_escape(e)}"}} {r["count"]}'
        )
    lines += [
        "# HELP tbtpu_gauge Gauge registry (queue depths, table counts).",
        "# TYPE tbtpu_gauge gauge",
    ]
    g = gauges()  # locked snapshot: worker threads set gauges mid-scrape
    # Pipeline occupancy (Little's law over the lifecycle registry):
    # mean prepares resident per stage, from the snapshot already merged
    # above — no second cross-thread merge per scrape.
    occ = _stage_occupancy(
        lambda e: snap.get(e, {}).get("total_ms", 0.0), _op_window_ns()
    )
    for stage, v in occ.items():
        g[f"op.occupancy.{stage}"] = v
    for name in sorted(g):
        lines.append(
            f'tbtpu_gauge{{name="{_label_escape(name)}"}} {g[name]:.9g}'
        )
    return "\n".join(lines) + "\n"


async def serve_metrics(port: int, host: str = "127.0.0.1", extra=None):
    """Serve GET /metrics (Prometheus text) and /trace (Perfetto JSON)
    on the current asyncio loop; returns the asyncio.Server. Wired by
    `cli.py start --metrics-port` onto the replica's own event loop —
    a scrape shares the loop, so it observes the live registry with no
    extra thread. `extra` adds caller-owned routes: {path_prefix:
    callable() -> (body_bytes, content_type)} — cli.py mounts /cluster
    (the replica's cluster-plane status, vsr/peerstats.cluster_status)
    there, keeping replica state out of this module."""
    import asyncio

    async def _handle(reader, writer) -> None:
        try:
            # Bounded header read: a half-open probe (port scan, LB health
            # check that never sends) must not pin a coroutine + socket on
            # the replica's event loop forever.
            async def _headers():
                req = await reader.readline()
                while True:
                    line = await reader.readline()
                    if not line or line in (b"\r\n", b"\n"):
                        return req

            req = await asyncio.wait_for(_headers(), timeout=10)
            parts = req.split()
            path = parts[1].decode("latin-1") if len(parts) >= 2 else "/"
            status = "200 OK"
            if path.startswith("/metrics"):
                body = prometheus_text().encode()
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            elif path.startswith("/trace"):
                body = json.dumps(export_trace()).encode()
                ctype = "application/json"
            elif path.startswith("/lifecycle"):
                # Per-op queue/service decomposition + occupancy + flight
                # status — the machine-readable block the benchmark
                # driver folds into its result line.
                body = json.dumps(lifecycle_summary()).encode()
                ctype = "application/json"
            elif path.startswith("/flight"):
                body = json.dumps({"ops": flight_records()}).encode()
                ctype = "application/json"
            elif extra is not None and any(
                path.startswith(p) for p in extra
            ):
                fn = next(extra[p] for p in extra if path.startswith(p))
                body, ctype = fn()
            else:
                routes = "/metrics /trace /lifecycle /flight" + (
                    " " + " ".join(sorted(extra)) if extra else ""
                )
                body = (
                    f"tigerbeetle-tpu observability: {routes}\n".encode()
                )
                ctype = "text/plain; charset=utf-8"
                status = "404 Not Found" if path != "/" else "200 OK"
            writer.write(
                (
                    f"HTTP/1.1 {status}\r\nContent-Type: {ctype}\r\n"
                    f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n"
                ).encode() + body
            )
            await writer.drain()
        except (ConnectionError, asyncio.IncompleteReadError, asyncio.TimeoutError):
            pass
        finally:
            try:
                writer.close()
            except Exception:  # noqa: BLE001 — scrape teardown is best-effort
                pass

    return await asyncio.start_server(_handle, host, port)


# --- devhub series ------------------------------------------------------

_git_revision_cache: Optional[str] = None


def _git_revision() -> str:
    """Short `git rev-parse HEAD` of this checkout (cached; 'unknown'
    outside a repo) — stamps devhub records to a commit."""
    global _git_revision_cache
    if _git_revision_cache is None:
        import subprocess

        try:
            out = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                cwd=os.path.dirname(os.path.abspath(__file__)),
                capture_output=True, text=True, timeout=10,
            )
            _git_revision_cache = out.stdout.strip() or "unknown"
        except Exception:  # noqa: BLE001 — no git, no stamp
            _git_revision_cache = "unknown"
    return _git_revision_cache


def devhub_append(path: str, record: dict) -> None:
    """Append one benchmark record to the JSON-lines series
    (devhub.zig:36-52's git-backed database, minus the git): stamped
    with the wall clock, the current git revision, and the environment
    profile_id (docs/DEVHUB.md) so every row is attributable to a
    commit AND a machine. Records that already carry a fingerprint
    (bench.py puts the full one in extra["env"]) keep it; otherwise the
    stamp is computed here — jax-aware only when jax is already loaded,
    so a jax-free caller (bench_gate) never pulls in the runtime."""
    rec = dict(record)
    rec.setdefault("unix_timestamp", int(time.time()))
    rec.setdefault("git", _git_revision())
    if "profile_id" not in rec:
        try:
            from tigerbeetle_tpu import envprofile

            rec["profile_id"] = envprofile.record_profile_id(rec) if (
                isinstance(rec.get("extra"), dict)
                and isinstance(rec["extra"].get("env"), dict)
            ) else envprofile.fingerprint(
                allow_jax="jax" in sys.modules
            )["profile_id"]
        except Exception:  # noqa: BLE001 — a stamp failure must not lose the row
            pass
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")
