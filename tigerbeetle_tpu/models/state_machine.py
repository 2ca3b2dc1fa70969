"""The accounting state machine: host orchestration over the TPU kernels.

Re-expresses the reference StateMachine (/root/reference/src/state_machine.zig:34)
TPU-first. The reference runs a serial per-event loop over an LSM
(state_machine.zig:1002-1088); here:

  - Account balances are device-resident uint32-limb arrays (ops/commit.py
    LedgerState) — the "model weights" of the flagship kernel.
  - The host resolves ids → slots/rows (the reference's *prefetch* phase,
    state_machine.zig:514-655) using vectorized sorted-run indexes (lsm/).
  - Each batch is classified: fast-path batches (no linked chains, no
    post/void/balancing, no duplicate ids, no limit/history accounts
    touched) commit via the fully-parallel device kernel
    (ops/commit.create_transfers_fast); everything else runs through the
    byte-exact serial oracle over lazily-prefetched state (the reference's
    own execution order), then writes balances back to the device.

Both paths produce byte-identical results to models/oracle.py — the property
tests in tests/test_state_machine.py enforce this.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from tigerbeetle_tpu import devicestats, tracer, types
from tigerbeetle_tpu.tidy import runtime as tidy_runtime
from tigerbeetle_tpu.constants import (
    Config, PIPELINE_PREPARE_QUEUE_MAX, PRODUCTION,
)
from tigerbeetle_tpu.flags import AccountFlags, TransferFlags
from tigerbeetle_tpu.lsm.store import (
    KEY_DTYPE,
    NOT_FOUND,
    Bloom,
    U128Index,
    make_u128_index,
    pack_keys,
    search_run,
    sort_lo_major,
)
from tigerbeetle_tpu.models import oracle as oracle_mod
from tigerbeetle_tpu.models.oracle import Oracle
from tigerbeetle_tpu.results import CreateAccountResult as AR
from tigerbeetle_tpu.results import CreateTransferResult as TR

U64_MAX = types.U64_MAX

# Flags handled by the exact (fixed-point sweep) kernel, not the simple one.
# Since round 3 this covers linked chains and pending post/void too — no
# flag forces the serial path anymore; only duplicate/existing ids and
# post/void of a same-batch pending do (see create_transfers routing).
_EXACT_TRANSFER_FLAGS = np.uint16(
    TransferFlags.BALANCING_DEBIT
    | TransferFlags.BALANCING_CREDIT
    | TransferFlags.LINKED
    | TransferFlags.POST_PENDING_TRANSFER
    | TransferFlags.VOID_PENDING_TRANSFER
)
_PV_FLAGS = np.uint16(
    TransferFlags.POST_PENDING_TRANSFER | TransferFlags.VOID_PENDING_TRANSFER
)
_EXACT_ACCOUNT_FLAGS = np.uint32(
    AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS
    | AccountFlags.CREDITS_MUST_NOT_EXCEED_DEBITS
    | AccountFlags.HISTORY
)

# Hard cap on dispatched-but-unfinished split-phase handles — the
# commit pipeline's cross-batch window (vsr/replica.py commit_depth)
# can never exceed it. Equals the protocol's prepare-queue depth AND
# the dispatch scratch ring size: slot i and slot i+WINDOW share host
# staging buffers, so a slot is only rewritten after its previous
# occupant's kernel has been finished (finish syncs before returning).
DISPATCH_WINDOW_MAX = PIPELINE_PREPARE_QUEUE_MAX

# Of those, how many may be exact-kernel handles. The exact kernel does
# not donate its state, so every such handle keeps the balance tables it
# was dispatched from alive until its finish: 1.125 GiB each at 2^24
# account slots, beside the current token. Two already hide the whole
# sync (the device runs batch N while the host stages and dispatches
# N+1, then posts N); a third holds another table for nothing. The
# commit stage settles its oldest batch once this many are out
# (vsr/replica.py, exact_window_full), so the served path never meets
# the refusal.
EXACT_DISPATCH_MAX = 2

# Slots a checkpoint reads back from the device in one `read_balances` call
# (16 MiB of balances): every such read has this one shape, the last padded
# to it, so a checkpoint compiles nothing new however many slots it reads.
BALANCE_READ_CHUNK = 1 << 18

_FAST_KERNEL = "create_transfers_fast"
_EXACT_KERNEL = "create_transfers_exact"


class _LazyDict(dict):
    """dict that faults entries in from a fetch function on miss.

    Lets the serial oracle run against lazily-materialized store state; keys
    it loaded (vs created) are tracked so writeback knows what is new.
    """

    def __init__(self, fetch) -> None:
        super().__init__()
        self._fetch = fetch
        self.fetched_keys: set = set()

    def get(self, k, default=None):
        if dict.__contains__(self, k):
            return dict.__getitem__(self, k)
        v = self._fetch(k)
        if v is None:
            return default
        self.fetched_keys.add(k)
        dict.__setitem__(self, k, v)
        return v

    def __getitem__(self, k):
        v = self.get(k)
        if v is None:
            raise KeyError(k)
        return v

    def __contains__(self, k) -> bool:
        return self.get(k) is not None

    def preload(self, k, v) -> None:
        if not dict.__contains__(self, k):
            self.fetched_keys.add(k)
            dict.__setitem__(self, k, v)


def _results_array(pairs: List[Tuple[int, int]]) -> np.ndarray:
    out = np.zeros(len(pairs), dtype=types.EVENT_RESULT_DTYPE)
    for i, (index, result) in enumerate(pairs):
        out[i] = (index, result)
    return out


def _codes_to_results(codes: np.ndarray) -> np.ndarray:
    nz = np.nonzero(codes)[0]
    out = np.zeros(len(nz), dtype=types.EVENT_RESULT_DTYPE)
    out["index"] = nz.astype(np.uint32)
    out["result"] = codes[nz].astype(np.uint32)
    return out


def _staged_nbytes(batch, host_code) -> int:
    """Host→device byte volume of a staged kernel call (the device-step
    profiler's h2d counter). Shape metadata only — `.nbytes` never
    materializes a device value."""
    return sum(getattr(a, "nbytes", 0) for a in batch) + getattr(
        host_code, "nbytes", 0
    )


def _batch_has_dup(events: np.ndarray) -> bool:
    """Any duplicate transfer id within the batch? C hash probe when the
    shim is available (~10× the lexsort-adjacency check), else numpy."""
    from tigerbeetle_tpu.lsm.store import _hostops

    lib = _hostops()
    n = len(events)
    if lib is not None:
        import ctypes

        lo = np.ascontiguousarray(events["id_lo"])
        hi = np.ascontiguousarray(events["id_hi"])
        rc = lib.hostops_batch_has_dup(
            n,
            lo.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            hi.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        )
        # rc < 0 = scratch allocation failure: claim "duplicate" so the
        # dispatcher takes the serial path, which handles dups correctly.
        return rc != 0
    keys = pack_keys(events["id_lo"], events["id_hi"])
    # lo-major sort with hi tiebreak: equal-lo duplicates must land
    # adjacent (a lo-only stable sort would leave (hi=1,lo=5),(hi=2,lo=5),
    # (hi=1,lo=5) non-adjacent).
    sk = keys[np.lexsort((keys["hi"], keys["lo"]))]
    adj = sk["lo"][1:] == sk["lo"][:-1]
    return bool(np.any(adj & (sk["hi"][1:] == sk["hi"][:-1])))


class StateMachine:
    """Single-replica accounting state machine (device-accelerated).

    Operations mirror the reference's Operation enum
    (state_machine.zig:318-326): create_accounts, create_transfers,
    lookup_accounts, lookup_transfers, get_account_transfers,
    get_account_history.
    """

    def __init__(
        self, config: Config = PRODUCTION, backend: str = "jax", grid=None,
        mesh=None,
    ) -> None:
        from tigerbeetle_tpu.io.grid import MemGrid

        self.config = config
        self.backend = backend
        self.mesh = mesh
        # The durable LSM tier (grid blocks + tables): replicas pass a grid
        # over their data file's grid zone; standalone use gets a lazy
        # in-memory grid with the same code path.
        self.grid = grid if grid is not None else MemGrid(
            config.grid_block_count,
            config.lsm_block_size,
            config.grid_cache_blocks,
        )
        a = config.accounts_max
        self._balances_nbytes = 0  # tidy: owner=commit

        if backend == "jax":
            from tigerbeetle_tpu.ops import commit as commit_ops

            # Before the first device call, so that its compile is seen.
            tracer.attach_jax()
            if mesh is not None:
                # Multi-chip: the same dispatcher over slot-sharded state
                # (parallel/sharded_ops.py adapter).
                from tigerbeetle_tpu.parallel.sharded_ops import ShardedOps

                self._ops = ShardedOps(mesh, a)
            else:
                self._ops = commit_ops
            self.state = self._ops.init_state(a)
            # Device memory ledger: the resident balance tables. Shape
            # metadata only — `.nbytes` never materializes a device value.
            self._balances_nbytes = sum(
                int(getattr(x, "nbytes", 0)) for x in self.state
            )
            tracer.device_mem_set("balances", self._balances_nbytes)
        else:  # pure-host backend: balances live in numpy mirrors
            self._ops = None
            self._host_bal = {
                name: np.zeros((a, 4), dtype=np.uint32)
                for name in (
                    "debits_pending", "debits_posted",
                    "credits_pending", "credits_posted",
                )
            }

        # Host mirrors of immutable per-account fields (slot-indexed).
        self.acc_key = np.zeros(a, dtype=KEY_DTYPE)
        self.acc_user_data_128_lo = np.zeros(a, dtype=np.uint64)
        self.acc_user_data_128_hi = np.zeros(a, dtype=np.uint64)
        self.acc_user_data_64 = np.zeros(a, dtype=np.uint64)
        self.acc_user_data_32 = np.zeros(a, dtype=np.uint32)
        self.acc_ledger = np.zeros(a, dtype=np.uint32)
        self.acc_code = np.zeros(a, dtype=np.uint32)
        self.acc_flags = np.zeros(a, dtype=np.uint32)
        self.acc_timestamp = np.zeros(a, dtype=np.uint64)
        self.account_count = 0

        from tigerbeetle_tpu.lsm.log import DurableLog
        from tigerbeetle_tpu.lsm.tree import DurableIndex

        # id → slot for accounts stays a RAM index (bounded by accounts_max);
        # the transfer id index, account secondary index, and the object log
        # live on the grid (reference groove.zig: id tree + indexes + object
        # tree).
        self.account_index = make_u128_index(config.accounts_max)
        self.transfer_index = DurableIndex(
            self.grid, unique=True,
            memtable_max=config.index_memtable_rows,
            name="transfer_id",
        )
        self.account_rows = DurableIndex(
            self.grid, unique=False,
            memtable_max=config.index_memtable_rows,
            name="account_rows",
        )
        # Combined secondary query index: (tag<<56 | fold56(field value),
        # timestamp) -> row, for the 8 indexed transfer fields beyond
        # id/dr/cr (reference: one LSM tree per field,
        # state_machine.zig:198-219; see lsm/scan.py for the re-shape).
        # merge_hint="dups": the composite keys are low-cardinality by
        # construction (5 tag blocks over mostly-constant columns), which
        # is the galloping k-way merge's best case at flush.
        self.query_rows = DurableIndex(
            self.grid, unique=False,
            memtable_max=config.index_memtable_rows,
            name="query_rows", merge_hint="dups",
        )
        self.transfer_log = DurableLog(self.grid, types.TRANSFER_DTYPE)
        # The accounts' immutable half (the acc_* mirrors above), appended
        # once at creation in slot order: the checkpoint references its
        # blocks instead of re-encoding every account, and a restore
        # rebuilds the mirrors from it (restore_accounts).
        from tigerbeetle_tpu.lsm.groove import ACCOUNT_LOG_DTYPE, BALANCE_LOG_DTYPE

        self.account_log = DurableLog(self.grid, ACCOUNT_LOG_DTYPE)
        # The other half: each checkpoint appends the balances of the slots
        # its ops touched (snapshot.encode), and a restore replays the rows
        # in order (restore_balances).
        self.balance_log = DurableLog(self.grid, BALANCE_LOG_DTYPE)
        # One byte a slot, set for every slot a committed create_transfers
        # batch names (_mark_balances) and cleared by the checkpoint that
        # writes its row; the spare last byte takes the -1 of an id that
        # names no account.
        # tidy: owner=commit|loop — marked by the op apply, read and cleared by the checkpoint, which runs on a quiescent stage
        self._balance_dirty = np.zeros(a + 1, dtype=bool)
        # Transfer-id membership pre-filter (no false negatives): keeps the
        # per-batch duplicate-id check O(batch) instead of O(tables).
        # tidy: owner=commit|store — adds are commit-side only (the store job passes add_bloom=False); probes are commit-side
        self.transfer_seen = Bloom(config.transfers_max)
        # Durable grooves (reference PostedGroove + account_history groove,
        # state_machine.zig:167-303): bounded RAM, LSM-backed.
        from tigerbeetle_tpu.lsm.groove import HistoryGroove, PostedGroove

        self.posted = PostedGroove(
            self.grid, memtable_max=config.index_memtable_rows // 8 or 512,
        )
        self.history = HistoryGroove(
            self.grid, memtable_max=config.index_memtable_rows // 8 or 512,
        )

        self.prepare_timestamp = 0
        self.commit_timestamp = 0

        # Deferred object-store work for the LAST committed batch:
        # (records, ts override). The reply depends only on validate+post,
        # so the commit path sends it before storing; store_barrier runs
        # before anything that reads the store (every public operation
        # guards, and the replica's _finish_commit applies it in strict
        # op order for determinism — inline, or as a StoreExecutor job
        # when the async store stage is attached).
        self._deferred_store = None  # tidy: owner=commit
        # The same for the last op's new accounts: their account-log
        # records, appended by the op's store job like its transfers, so
        # the log's appends and its beat flushes stay on one thread.
        self._deferred_accounts = None  # tidy: owner=commit
        # Optional async store stage (vsr/pipeline.StoreExecutor, attached
        # by the replica): queued jobs hold this state machine's pending
        # groove/index writes + beats; store_barrier drains it before any
        # store read (read-your-writes).
        # tidy: owner=commit|loop — written at attach/state-sync reinstall (stage quiescent), read on the commit path
        self._store_stage = None
        # Resume point within compact_beat's stage list after a
        # GridReadFault was repaired (see compact_beat).
        self._beat_stage = 0  # tidy: owner=commit|store — advanced only inside the per-op beat, which runs in exactly one context per op
        # Event count of the last committed batch — the adaptive beat
        # quota's load signal (a pure function of the committed op
        # stream, so replicas and WAL replay pace identically).
        self._beat_events = 0  # tidy: owner=commit|store — written by the op apply, read by its own beat

        # Split-phase device dispatch (the overlapped commit pipeline,
        # vsr/pipeline.py): FIFO of outstanding handles whose kernels are
        # dispatched but not yet synced (finish pops strictly in dispatch
        # order); _state_gen fences handles that chained off a state token
        # a serial bail rolled back. Depth is bounded by
        # DISPATCH_WINDOW_MAX (dispatch refuses past it — a pipeline
        # stall, never corruption).
        self._ct_pending: list = []  # tidy: owner=commit
        self._state_gen = 0  # tidy: owner=commit
        # Dispatch scratch ring: one host staging-buffer slot per
        # in-flight generation (keyed seq % DISPATCH_WINDOW_MAX), each
        # lazily holding the padded SoA block per pow-2 bucket size.
        # Shapes depend ONLY on the bucket, never on the ring slot or
        # window depth, so the compile-count gate is depth-independent.
        # A slot is reused only once its previous occupant finished
        # (ring size == the window cap), so even a zero-copy h2d alias
        # could never see a concurrent rewrite.
        # tidy: owner=commit — filled and handed to the kernel on the commit thread only
        self._disp_scratch: list = [
            {} for _ in range(DISPATCH_WINDOW_MAX)
        ]
        self._disp_seq = 0  # tidy: owner=commit
        # Last-use dispatch seq per scratch bucket (pow-2 pad size): a
        # bucket idle for SCRATCH_STALE_AFTER dispatches is retired —
        # buffers freed from every ring slot, its device.mem.scratch.*
        # gauges and devicestats cost rows dropped — so a workload
        # shift can't grow the ring (or the registry) unbounded.
        self._scratch_last_use: Dict[int, int] = {}  # tidy: owner=commit

        # telemetry: how many batches took which path
        self.stats = {
            "fast_batches": 0, "exact_batches": 0,
            "serial_batches": 0, "bail_batches": 0,
        }

    def _count_route(self, route: str) -> None:  # tidy: thread=commit
        """One batch took `route`: the in-process tally plus its mirror
        on the scrape surface (`sm.route.<route>` on /metrics), so a
        launcher can check which commit path served its traffic."""
        self.stats[route] += 1
        tracer.count("sm.route." + route)

    def attach_store_stage(self, stage) -> None:  # tidy: thread=loop
        """Wire the async store stage (replica.attach_store_executor /
        state-sync reinstall). Reads then synchronize via store_barrier."""
        self._store_stage = stage

    def store_barrier(self) -> None:  # tidy: thread=commit
        """Read-your-writes guard: every queued async store job and the
        current op's deferred store are applied before a store read. A
        stage parked on a corrupt block re-raises its GridReadFault here
        — the caller's op aborts cleanly (requeued behind the repair)
        instead of reading half-stored state."""
        stage = self._store_stage
        if stage is not None:
            tracer.count("sm.store_barrier_drains")
            with tracer.span("sm.store.barrier"):
                while True:
                    stage.drain()
                    # drain() returns either idle or parked; re-check in
                    # a loop — the event-loop thread may repair and
                    # resume() (requeueing the faulted job) between the
                    # return and this read, in which case the queue is
                    # live again and must be drained anew.
                    fault = stage.fault
                    if fault is not None and stage.parked:
                        raise fault
                    if stage.idle:
                        break
        self.flush_deferred()

    def flush_deferred(self) -> None:  # tidy: thread=commit
        tidy_runtime.assert_role("commit", "loop")
        d = self._deferred_store
        if d is not None:
            self._deferred_store = None
            recs, ts = d
            with tracer.span("sm.ct.store"):
                # Bloom membership was already published at defer time.
                self._store_new_transfers(recs, ts=ts, add_bloom=False)
        accounts = self.take_deferred_accounts()
        if accounts is not None:
            self.account_log.append_batch(accounts)

    def take_deferred_accounts(self):  # tidy: thread=commit
        """Pop the last op's account-log records (None if it created no
        account), for its store job or for flush_deferred."""
        tidy_runtime.assert_role("commit", "loop")
        d = self._deferred_accounts
        self._deferred_accounts = None
        return d

    def _log_new_accounts(self, first: int, last: int) -> None:  # tidy: thread=commit
        """Defer the account-log records of slots [first, last), just
        written to the mirrors, to the op's store."""
        s = slice(first, last)
        recs = np.empty(last - first, dtype=self.account_log.dtype)
        recs["id_lo"] = self.acc_key["lo"][s]
        recs["id_hi"] = self.acc_key["hi"][s]
        recs["user_data_128_lo"] = self.acc_user_data_128_lo[s]
        recs["user_data_128_hi"] = self.acc_user_data_128_hi[s]
        recs["user_data_64"] = self.acc_user_data_64[s]
        recs["user_data_32"] = self.acc_user_data_32[s]
        recs["ledger"] = self.acc_ledger[s]
        recs["code"] = self.acc_code[s]
        recs["flags"] = self.acc_flags[s]
        recs["timestamp"] = self.acc_timestamp[s]
        self._deferred_accounts = recs

    def restore_accounts(self, count: int) -> None:
        """Rebuild the account mirrors, the id → slot index and the
        device's per-slot ledger and flags from the first `count` records
        of the account log (a checkpoint restore; the log's blocks must all
        be present). Balances are written separately."""
        for base, recs in self.account_log.scan_range(0, count):
            s = slice(base, base + len(recs))
            self.acc_key["lo"][s] = recs["id_lo"]
            self.acc_key["hi"][s] = recs["id_hi"]
            self.acc_user_data_128_lo[s] = recs["user_data_128_lo"]
            self.acc_user_data_128_hi[s] = recs["user_data_128_hi"]
            self.acc_user_data_64[s] = recs["user_data_64"]
            self.acc_user_data_32[s] = recs["user_data_32"]
            self.acc_ledger[s] = recs["ledger"]
            self.acc_code[s] = recs["code"]
            self.acc_flags[s] = recs["flags"]
            self.acc_timestamp[s] = recs["timestamp"]
        slots = np.arange(count, dtype=np.int32)
        self.account_index.insert_batch(self.acc_key[:count], slots.astype(np.uint32))
        self._register_accounts(
            slots, self.acc_ledger[:count], self.acc_flags[:count],
            np.ones(count, dtype=bool),
        )

    def _mark_balances(self, *slots) -> None:  # tidy: thread=commit
        """Mark the slots a committed create_transfers batch names, whatever
        each event's result: every event's debit and credit account, and a
        post/void's pending transfer's two as the store held them (-1 where
        an id names no account). The set is a function of the batch and the
        state before it, the same on every route the batch may take (and
        after a bail or a refire), so the next checkpoint appends the same
        balance-log rows on every replica."""
        for s in slots:
            self._balance_dirty[s] = True

    def take_touched_slots(self) -> np.ndarray:
        """The slots marked since the last call, ascending, and clear the
        marks (the checkpoint's)."""
        touched = np.flatnonzero(self._balance_dirty[: self.account_count])
        self._balance_dirty[:] = False
        return touched

    def balance_read_chunk(self) -> int:
        """The slots of one of the checkpoint's reads (BALANCE_READ_CHUNK,
        or the whole table's bucket where it is smaller)."""
        return min(BALANCE_READ_CHUNK, 1 << (self.config.accounts_max - 1).bit_length())

    def balance_rows(self, slots: np.ndarray) -> np.ndarray:
        """The balance-log rows of at most balance_read_chunk() slots, read
        from the table in one call of that one shape."""
        k = len(slots)
        read = slots
        if self._ops is not None:
            read = np.zeros(self.balance_read_chunk(), dtype=np.int64)
            read[:k] = slots  # the padding reads slot 0, sliced away below
        dp, dpo, cp, cpo = self._read_balances(read)
        rows = np.empty(k, dtype=self.balance_log.dtype)
        u = rows.view(np.uint64).reshape(k, 9)
        u[:, 0] = slots
        for i, limbs in enumerate((dp, dpo, cp, cpo)):
            # (k, 4) little-endian u32 limbs are (lo, hi) u64 pairs.
            u[:, 1 + 2 * i : 3 + 2 * i] = np.ascontiguousarray(
                limbs[:k], dtype="<u4"
            ).view("<u8")
        return rows

    def restore_balances(self, count: int) -> None:
        """Write the balances of the first `count` slots from the balance
        log: zero, then every row in log order, a later row of a slot over
        an earlier one (a checkpoint restore; the log's blocks must all be
        present)."""
        if count == 0:
            return
        table = np.zeros((count, 8), dtype=np.uint64)
        for _base, rows in self.balance_log.scan_range(0, self.balance_log.count):
            u = rows.view(np.uint64).reshape(len(rows), 9)
            slots = u[:, 0].astype(np.int64)
            # One checkpoint's rows ascend by slot: a slot repeats inside
            # a window only across two checkpoints' rows, so the window is
            # written run by run, each run's slots distinct.
            cuts = np.flatnonzero(slots[1:] <= slots[:-1]) + 1
            for s, e in zip(np.r_[0, cuts], np.r_[cuts, len(rows)]):
                table[slots[s:e]] = u[s:e, 1:]
        self._write_balances(
            np.arange(count, dtype=np.int32),
            *(np.ascontiguousarray(table[:, i : i + 2]).view(np.uint32)
              for i in range(0, 8, 2)),
        )

    def _defer_store(self, recs: np.ndarray, ts=None) -> None:  # tidy: thread=commit
        """Schedule the batch's store work for _finish_commit (inline or
        the async stage). Bloom membership is published NOW, on the
        commit thread, so the next batch's duplicate-id pre-filter is
        accurate without a store barrier — the only store state the hot
        path consults ahead of the queued writes."""
        tidy_runtime.assert_role("commit", "loop")
        self.transfer_seen.add(recs["id_lo"], recs["id_hi"])
        self._deferred_store = (recs, ts)

    def take_deferred_store(self):  # tidy: thread=commit
        """Pop the deferred batch for an async store job (replica
        _finish_commit). None when the op stored inline (the serial
        path, an exact batch behind its barrier) or wrote nothing."""
        tidy_runtime.assert_role("commit", "loop")
        d = self._deferred_store
        self._deferred_store = None
        return d

    def _confirm_maybe_ids(self, flagged_keys: np.ndarray) -> bool:  # tidy: thread=commit
        """Duplicate confirm for bloom maybe-hits WITHOUT draining the
        async store stage: the PENDING WRITE BUFFER (queued + in-flight
        store jobs) is consulted first, then the durable id index — which
        at that instant is missing at most the batches still in the
        buffer, so every committed id is visible in at least one of the
        two. Safe to read concurrently with the store thread because the
        id index's memtable batches are always insert-time sorted (no
        lazy re-sort mutation) and flush/compaction publish-then-retire
        (lsm/tree.py). Conservative on id_lo alone for the buffer probe:
        a false positive only routes the batch to the byte-exact serial
        path, never mis-answers."""
        stage = self._store_stage
        if stage is not None:
            for recs, _ts in stage.unapplied_stores():
                if bool(np.isin(flagged_keys["lo"], recs["id_lo"]).any()):
                    return True
        return self.transfer_index.contains_any(flagged_keys)

    # tidy: thread=commit|store
    def _store_new_transfers(
        self, recs: np.ndarray, ts=None, add_bloom: bool = True
    ) -> None:
        """Append committed transfers to the object log and both indexes
        (reference groove insert: object tree + id tree + secondary
        indexes, groove.zig:138). `ts` optionally overrides the stored
        timestamp column during the log's copy (zero-copy path: the
        caller's event array is not mutated)."""
        tracer.count("sm.stored_transfers", len(recs))
        with tracer.span("sm.store.log"):
            rows = self.transfer_log.append_batch(recs, ts=ts)
            if add_bloom:
                self.transfer_seen.add(recs["id_lo"], recs["id_hi"])
        if not self._store_native(recs, int(rows[0]) if len(rows) else 0):
            with tracer.span("sm.store.idx"):
                self.transfer_index.insert_batch(
                    pack_keys(recs["id_lo"], recs["id_hi"]), rows
                )
            with tracer.span("sm.store.rows"):
                # One coalesced unsorted append (like the native path):
                # account_rows is non-unique and write-heavy — the flush
                # re-sorts the whole memtable, so a per-commit radix pass
                # here is pure waste, and the stable flush sort makes the
                # table bytes identical either way.
                acct_keys = np.concatenate([
                    pack_keys(recs["debit_account_id_lo"], recs["debit_account_id_hi"]),
                    pack_keys(recs["credit_account_id_lo"], recs["credit_account_id_hi"]),
                ])
                self.account_rows.insert_unsorted(
                    acct_keys, np.concatenate([rows, rows])
                )
        self._store_query_index(recs, rows, ts)

    def _store_query_index(self, recs: np.ndarray, rows: np.ndarray, ts) -> None:
        """One batched append of the secondary-index entries for the
        committed rows (tagged composite keys — lsm/scan.py).

        Exactly the QueryFilter-queryable fields are indexed (ud128/64/32,
        ledger, code). The reference also indexes amount, pending_id, and
        timeout (state_machine.zig:207-212) for internal scans this build
        answers elsewhere: pending expiry via the posted groove,
        pending_id resolution via the transfer-id index. Index entries are
        the dominant ingest write-amplification, so unqueryable tags are
        deliberately not maintained."""
        from tigerbeetle_tpu.lsm import scan

        with tracer.span("sm.store.query"):
            tstamp = (
                np.asarray(ts, dtype=np.uint64)
                if ts is not None else recs["timestamp"]
            )
            # One preallocated key block filled slice-wise, one slice a
            # tag of scan.QUERY_TAG_FIELDS: key.lo = tag << 56 |
            # fold56(field), key.hi = timestamp, value = object-log row.
            with tracer.span("sm.store.query.keys"):
                tags = tuple(
                    (tag, scan.fold56(
                        recs[f_lo], None if f_hi is None else recs[f_hi]
                    ))
                    for tag, f_lo, f_hi in scan.QUERY_TAG_FIELDS
                )
                n = len(recs)
                keys = np.empty(len(tags) * n, dtype=scan.KEY_DTYPE)
                klo, khi = keys["lo"], keys["hi"]
                for i, (tag, folded) in enumerate(tags):
                    klo[i * n : (i + 1) * n] = (
                        np.uint64(tag) << np.uint64(56)
                    ) | folded
                    khi[i * n : (i + 1) * n] = tstamp
                vals = np.tile(rows, len(tags))
            if scan.query_columns_constant(recs):
                # Constant queryable columns (fixed ledger/code, unset
                # user_data — the common ingest shape): each tag block
                # holds ONE repeated lo, blocks ascend by tag, so the
                # batch is already lo-major sorted in insertion order.
                # Flagging it sorted routes the flush through the
                # galloping k-way merge (≈ memcpy on dup runs) instead
                # of the full radix re-sort — identical bytes (stable
                # merge of per-batch stable order == stable sort of the
                # concatenation, property-tested).
                self.query_rows.insert_sorted(keys, vals)
            else:
                self.query_rows.insert_unsorted(keys, vals)

    def _store_native(self, recs: np.ndarray, row_base: int) -> bool:
        """C-fused index staging (hostops_build_sorted_kv): builds the
        lo-major sorted (key, row) arrays for both the transfer-id index
        and the account secondary index straight from the wire records —
        one pass each instead of pack/concat/argsort/gather numpy passes.
        Sorted-batch order is bit-identical to the numpy path (same stable
        radix order, same dr-then-cr concat order)."""
        from tigerbeetle_tpu.lsm.store import _hostops

        lib = _hostops()
        n = len(recs)
        if (
            lib is None or n <= 256
            or recs.strides[0] != recs.dtype.itemsize
        ):
            return False
        import ctypes

        u32p = ctypes.POINTER(ctypes.c_uint32)
        rec_ptr = ctypes.c_char_p(recs.ctypes.data)
        stride = recs.strides[0]
        with tracer.span("sm.store.idx"):
            id_keys = np.empty(n, dtype=KEY_DTYPE)
            id_vals = np.empty(n, dtype=np.uint32)
            rc = lib.hostops_build_sorted_kv(
                rec_ptr, n, stride, 0, 8, -1, -1, row_base,
                ctypes.c_char_p(id_keys.ctypes.data),
                id_vals.ctypes.data_as(u32p),
            )
            if rc != 0:
                return False
            self.transfer_index.insert_sorted(id_keys, id_vals)
        with tracer.span("sm.store.rows"):
            # Unsorted extraction: account_rows is non-unique and
            # write-heavy — lookup_range scans memtable batches with a
            # mask and the flush re-sorts, so the per-commit radix pass
            # is pure waste here.
            acct_keys = np.empty(2 * n, dtype=KEY_DTYPE)
            acct_vals = np.empty(2 * n, dtype=np.uint32)
            rc = lib.hostops_extract_kv(
                rec_ptr, n, stride, 16, 24, 32, 40, row_base,
                ctypes.c_char_p(acct_keys.ctypes.data),
                acct_vals.ctypes.data_as(u32p),
            )
            if rc != 0:
                # The id insert already landed; finish the account index via
                # the numpy path to stay consistent.
                rows = row_base + np.arange(n, dtype=np.uint32)
                ak = np.concatenate([
                    pack_keys(recs["debit_account_id_lo"], recs["debit_account_id_hi"]),
                    pack_keys(recs["credit_account_id_lo"], recs["credit_account_id_hi"]),
                ])
                self.account_rows.insert_batch(ak, np.concatenate([rows, rows]))
                return True
            self.account_rows.insert_unsorted(acct_keys, acct_vals)
        return True

    # ------------------------------------------------------------------
    # prepare (timestamp assignment, reference state_machine.zig:503-511)

    def prepare(self, operation: str, event_count: int) -> int:
        if operation in ("create_accounts", "create_transfers"):
            self.prepare_timestamp += event_count
        return self.prepare_timestamp

    # ------------------------------------------------------------------
    # compaction beat (reference forest.compact, forest.zig:319): bounded
    # background storage work interleaved between commits, so the commit →
    # reply path itself performs no grid IO.

    def compact_beat(self, max_blocks: int = 8, flush: bool = True) -> None:  # tidy: thread=commit|store
        """One beat of deferred storage work: flush up to `max_blocks` of
        the object log's pending blocks and run one bounded compaction
        step on each durable index. Driven once per committed op from
        inside the commit apply path — WAL replay re-runs the identical
        beat sequence, so grid allocation order (and therefore checkpoint
        bytes) stays deterministic across replicas and restarts.

        flush=False (async store jobs, which apply their op's store
        explicitly before the beat): _deferred_store belongs to the
        COMMIT thread — reading it from the store thread would race the
        next op's defer (stealing or double-applying its batch)."""
        if flush:
            self.flush_deferred()  # the op's store precedes its beat, always
        # Stage-resumable: a GridReadFault mid-beat (corrupt compaction
        # input) aborts that stage atomically (tree-level abort_block) and
        # the RETRY after repair resumes at the faulted stage — re-running
        # completed stages would give their trees extra beats for this op
        # and diverge the deterministic allocation order from peers.
        with tracer.span("sm.beat"):
            quota = self._compact_quota()
            tracer.gauge("sm.compact.quota", quota)
            stages = (
                lambda: self.transfer_log.flush_pending(max_blocks),
                lambda: self.account_log.flush_pending(max_blocks),
                lambda: self.history.flush_pending(max_blocks),
                lambda: self.transfer_index.compact_step(quota),
                lambda: self.account_rows.compact_step(quota),
                lambda: self.query_rows.compact_step(quota),
                lambda: self.posted.compact_step(quota),
                lambda: self.history.compact_step(quota),
            )
            while self._beat_stage < len(stages):
                stages[self._beat_stage]()
                self._beat_stage += 1
            self._beat_stage = 0

    def _compact_quota(self) -> int:
        """Adaptive beat quota: scale the per-op compaction allowance by
        committed-state signals only — the last batch's fill fraction
        (commits stalling on store.wait arrive as full batches; idle
        trickle arrives small) and the trees' compaction backlog. Both
        inputs are pure functions of the committed op stream, so every
        replica (and WAL replay) computes the identical quota per op and
        grid allocation order stays byte-deterministic — the reason the
        quota must NOT read wall-clock queue depth, which differs per
        machine."""
        base = self.config.compact_quota_entries
        backlog = self._compact_backlog()
        if backlog == 0:
            return base
        if backlog >= base << 3:
            # Far behind (a storm, or a stalled stretch): catch up hard —
            # commits momentarily pay more per op, which is cheaper than
            # the read-amplification of an over-deep tree.
            return base << 2
        fill = self._beat_events / self.config.batch_max
        if fill >= 0.5:
            # Saturated ingest: halve the allowance so the beat stays off
            # the commit path's critical section (backlog above bounds
            # how long the back-off can run).
            return base >> 1
        if fill <= 0.125:
            return base << 2  # mostly idle: drain the backlog
        return base

    def _compact_backlog(self) -> int:
        return (
            self.transfer_index.compact_backlog()
            + self.account_rows.compact_backlog()
            + self.query_rows.compact_backlog()
            + self.posted.compact_backlog()
            + self.history.compact_backlog()
        )

    def request_major_compaction(self) -> int:
        """Queue a forced all-level major compaction (storm) on every
        content tree; returns total rows queued. The storms then run
        incrementally through the normal per-op beats while the machine
        keeps serving. Maintenance/single-node API — see
        DurableIndex.request_major for the cluster caveat."""
        self.store_barrier()
        self.flush_deferred()
        return (
            self.transfer_index.request_major()
            + self.account_rows.request_major()
            + self.query_rows.request_major()
            + self.posted.request_major()
            + self.history.request_major()
        )

    def compaction_storm_active(self) -> bool:
        return (
            self.transfer_index.storm_active()
            or self.account_rows.storm_active()
            or self.query_rows.storm_active()
            or self.posted.storm_active()
            or self.history.storm_active()
        )

    def compact_prefetch_one(self) -> bool:
        """Warm one upcoming compaction-input block (idle-slot read-ahead
        driven by the store stage; content-neutral, see
        DurableIndex.compact_prefetch_one)."""
        for tree in (
            self.transfer_index, self.account_rows, self.query_rows,
            self.posted, self.history,
        ):
            if tree.compact_prefetch_one():
                return True
        return False

    # ------------------------------------------------------------------
    # balances access (device or host backend)

    @staticmethod
    def _pad_slots(arrs, k: int, fills) -> list:
        """Pad per-slot arrays to a power-of-two bucket (≥16) so the
        balance-access jit entries compile once per bucket, not once per
        lookup/registration size — found by the tidy retrace pass: every
        distinct `len(slots)` used to be a fresh XLA compile (more
        wall-clock than the gather it served). Fill values must be inert
        for the kernel (an out-of-range slot under mode="drop", a False
        mask)."""
        n_pad = 1 << max(4, (max(k, 1) - 1).bit_length())
        out = []
        for a, fill in zip(arrs, fills):
            a = np.atleast_1d(np.asarray(a))
            if len(a) == n_pad:
                out.append(a)
                continue
            p = np.full((n_pad, *a.shape[1:]), fill, dtype=a.dtype)
            p[:k] = a
            out.append(p)
        return out

    def _read_balances(self, slots: np.ndarray):
        if self._ops is not None:
            k = len(np.atleast_1d(slots))
            # Pad slot 0 (clipped gather rows are sliced away below).
            slots_p, = self._pad_slots(
                [np.asarray(slots, dtype=np.int32)], k, [0]
            )
            devicestats.note_call("read_balances", (self.state, slots_p))
            with tracer.device_step("read_balances"):
                dp, dpo, cp, cpo = self._ops.read_balances(self.state, slots_p)
                # Materialize the FULL padded arrays first: the sliced
                # views undercount the actual device→host volume.
                full = (
                    np.asarray(dp), np.asarray(dpo),
                    np.asarray(cp), np.asarray(cpo),
                )
            tracer.device_bytes(
                h2d=slots_p.nbytes, d2h=sum(a.nbytes for a in full)
            )
            return tuple(a[:k] for a in full)
        s = np.asarray(slots, dtype=np.int64)
        hb = self._host_bal
        return (
            hb["debits_pending"][s], hb["debits_posted"][s],
            hb["credits_pending"][s], hb["credits_posted"][s],
        )

    def _write_balances(self, slots, dp, dpo, cp, cpo) -> None:
        if self._ops is not None:
            k = len(np.atleast_1d(slots))
            # Pad rows scatter at slot=accounts_max → dropped (mode="drop").
            oob = self.config.accounts_max
            slots_p, dp_p, dpo_p, cp_p, cpo_p = self._pad_slots(
                [np.asarray(slots, dtype=np.int32), dp, dpo, cp, cpo],
                k, [oob, 0, 0, 0, 0],
            )
            devicestats.note_call(
                "write_balances",
                (self.state, slots_p, dp_p, dpo_p, cp_p, cpo_p),
            )
            with tracer.device_step("write_balances"):
                self.state = self._ops.write_balances(
                    self.state, slots_p, dp_p, dpo_p, cp_p, cpo_p
                )
            tracer.device_bytes(
                h2d=_staged_nbytes((slots_p, dp_p, dpo_p, cp_p), cpo_p)
            )
        else:
            s = np.asarray(slots, dtype=np.int64)
            hb = self._host_bal
            hb["debits_pending"][s] = dp
            hb["debits_posted"][s] = dpo
            hb["credits_pending"][s] = cp
            hb["credits_posted"][s] = cpo

    def _register_accounts(self, slots, ledger, flags, mask) -> None:
        if self._ops is not None:
            k = len(np.atleast_1d(slots))
            # Pad rows carry mask=False → never installed.
            slots_p, ledger_p, flags_p, mask_p = self._pad_slots(
                [
                    np.asarray(slots, dtype=np.int32),
                    np.asarray(ledger, dtype=np.uint32),
                    np.asarray(flags, dtype=np.uint32),
                    np.asarray(mask),
                ],
                k, [-1, 0, 0, False],
            )
            devicestats.note_call(
                "register_accounts",
                (self.state, slots_p, ledger_p, flags_p, mask_p),
            )
            with tracer.device_step("register_accounts"):
                self.state = self._ops.register_accounts(
                    self.state, slots_p, ledger_p, flags_p, mask_p
                )
            tracer.device_bytes(
                h2d=_staged_nbytes((slots_p, ledger_p, flags_p), mask_p)
            )

    # ------------------------------------------------------------------
    # create_accounts

    def create_accounts(self, events: np.ndarray, timestamp: Optional[int] = None) -> np.ndarray:
        self.flush_deferred()
        events = np.atleast_1d(events)
        n = len(events)
        self._beat_events = n
        if timestamp is None:
            timestamp = self.prepare("create_accounts", n)
        if n == 0:
            return np.zeros(0, dtype=types.EVENT_RESULT_DTYPE)
        ts = np.uint64(timestamp) - np.uint64(n) + 1 + np.arange(n, dtype=np.uint64)

        flags = events["flags"].astype(np.uint32)
        keys = pack_keys(events["id_lo"], events["id_hi"])

        hard = bool(np.any(flags & np.uint32(AccountFlags.LINKED)))
        if not hard:
            order = np.lexsort((keys["lo"], keys["hi"]))
            sk = keys[order]
            hard = bool(np.any(sk[1:] == sk[:-1])) if n > 1 else False
        if hard:
            return self._create_accounts_serial(events, timestamp)

        code = np.zeros(n, dtype=np.uint32)

        def ladder(cond, result):
            np.copyto(code, np.uint32(int(result)), where=(code == 0) & cond)

        ladder(events["timestamp"] != 0, AR.TIMESTAMP_MUST_BE_ZERO)
        ladder(events["reserved"] != 0, AR.RESERVED_FIELD)
        ladder((flags & np.uint32(AccountFlags.padding_mask())) != 0, AR.RESERVED_FLAG)
        id_zero = (events["id_lo"] == 0) & (events["id_hi"] == 0)
        id_max = (events["id_lo"] == U64_MAX) & (events["id_hi"] == U64_MAX)
        ladder(id_zero, AR.ID_MUST_NOT_BE_ZERO)
        ladder(id_max, AR.ID_MUST_NOT_BE_INT_MAX)
        both = np.uint32(
            AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS
            | AccountFlags.CREDITS_MUST_NOT_EXCEED_DEBITS
        )
        ladder((flags & both) == both, AR.FLAGS_ARE_MUTUALLY_EXCLUSIVE)
        ladder(
            (events["debits_pending_lo"] != 0) | (events["debits_pending_hi"] != 0),
            AR.DEBITS_PENDING_MUST_BE_ZERO,
        )
        ladder(
            (events["debits_posted_lo"] != 0) | (events["debits_posted_hi"] != 0),
            AR.DEBITS_POSTED_MUST_BE_ZERO,
        )
        ladder(
            (events["credits_pending_lo"] != 0) | (events["credits_pending_hi"] != 0),
            AR.CREDITS_PENDING_MUST_BE_ZERO,
        )
        ladder(
            (events["credits_posted_lo"] != 0) | (events["credits_posted_hi"] != 0),
            AR.CREDITS_POSTED_MUST_BE_ZERO,
        )
        ladder(events["ledger"] == 0, AR.LEDGER_MUST_NOT_BE_ZERO)
        ladder(events["code"] == 0, AR.CODE_MUST_NOT_BE_ZERO)

        # exists ladder (reference state_machine.zig _create_account_exists)
        slots = self.account_index.lookup_batch(keys)
        found = (slots != NOT_FOUND) & (code == 0)
        if np.any(found):
            s = slots[found].astype(np.int64)
            fcode = np.zeros(len(s), dtype=np.uint32)

            def fladder(cond, result):
                np.copyto(fcode, np.uint32(int(result)), where=(fcode == 0) & cond)

            fladder(flags[found] != self.acc_flags[s], AR.EXISTS_WITH_DIFFERENT_FLAGS)
            fladder(
                (events["user_data_128_lo"][found] != self.acc_user_data_128_lo[s])
                | (events["user_data_128_hi"][found] != self.acc_user_data_128_hi[s]),
                AR.EXISTS_WITH_DIFFERENT_USER_DATA_128,
            )
            fladder(
                events["user_data_64"][found] != self.acc_user_data_64[s],
                AR.EXISTS_WITH_DIFFERENT_USER_DATA_64,
            )
            fladder(
                events["user_data_32"][found] != self.acc_user_data_32[s],
                AR.EXISTS_WITH_DIFFERENT_USER_DATA_32,
            )
            fladder(events["ledger"][found] != self.acc_ledger[s], AR.EXISTS_WITH_DIFFERENT_LEDGER)
            fladder(events["code"][found] != self.acc_code[s], AR.EXISTS_WITH_DIFFERENT_CODE)
            fladder(np.ones(len(s), dtype=bool), AR.EXISTS)
            code[found] = fcode

        ok = code == 0
        k = int(ok.sum())
        if self.account_count + k > self.config.accounts_max:
            raise RuntimeError("accounts table full (accounts_max exceeded)")
        if k:
            new_slots = np.arange(self.account_count, self.account_count + k, dtype=np.int64)
            s_all = np.full(n, -1, dtype=np.int32)
            s_all[ok] = new_slots
            self.acc_key[new_slots] = keys[ok]
            self.acc_user_data_128_lo[new_slots] = events["user_data_128_lo"][ok]
            self.acc_user_data_128_hi[new_slots] = events["user_data_128_hi"][ok]
            self.acc_user_data_64[new_slots] = events["user_data_64"][ok]
            self.acc_user_data_32[new_slots] = events["user_data_32"][ok]
            self.acc_ledger[new_slots] = events["ledger"][ok]
            self.acc_code[new_slots] = events["code"][ok]
            self.acc_flags[new_slots] = flags[ok]
            self.acc_timestamp[new_slots] = ts[ok]
            self._log_new_accounts(self.account_count, self.account_count + k)
            self.account_count += k
            self.account_index.insert_batch(keys[ok], new_slots.astype(np.uint32))
            self._register_accounts(s_all, events["ledger"].astype(np.uint32), flags, ok)
            self.commit_timestamp = int(ts[ok][-1])
        return _codes_to_results(code)

    # ------------------------------------------------------------------
    # create_transfers

    def _ct_stage_native(self, events: np.ndarray, timestamp: int):
        """One C pass (csrc/hostops.c hostops_ct_stage) replacing the
        dispatcher's five numpy staging passes: duplicate-id set, bloom
        pre-filter, slot lookups, the merged fast-path validation ladder,
        and exact-kernel routing bits. None when the shim or the native
        account map is unavailable (numpy fallback below)."""
        from tigerbeetle_tpu.lsm.store import NativeU128Map, _hostops

        lib = _hostops()
        if (
            lib is None
            or not isinstance(self.account_index, NativeU128Map)
            or events.strides[0] != events.dtype.itemsize
        ):
            return None
        import ctypes

        n = len(events)
        code = np.empty(n, dtype=np.uint32)
        host_code = np.empty(n, dtype=np.uint32)
        dr_slots = np.empty(n, dtype=np.int64)
        cr_slots = np.empty(n, dtype=np.int64)
        amt_lo = np.empty(n, dtype=np.uint64)
        amt_hi = np.empty(n, dtype=np.uint64)
        pend = np.empty(n, dtype=np.uint8)
        maybe = np.empty(n, dtype=np.uint8)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        bloom = self.transfer_seen
        bloom_ptr = (
            bloom.words.ctypes.data_as(u64p) if bloom.count else None
        )
        acc_ledger = self.acc_ledger
        acc_flags = self.acc_flags
        bits = lib.hostops_ct_stage(
            ctypes.c_char_p(events.ctypes.data), n, events.strides[0],
            int(timestamp) - n + 1,
            self.account_index._h,
            acc_ledger.ctypes.data_as(u32p), acc_flags.ctypes.data_as(u32p),
            bloom_ptr, int(bloom._mask),
            code.ctypes.data_as(u32p), host_code.ctypes.data_as(u32p),
            dr_slots.ctypes.data_as(i64p), cr_slots.ctypes.data_as(i64p),
            amt_lo.ctypes.data_as(u64p), amt_hi.ctypes.data_as(u64p),
            pend.ctypes.data_as(u8p), maybe.ctypes.data_as(u8p),
        )
        if bits < 0:
            return None
        return (code, host_code, dr_slots, cr_slots, amt_lo, amt_hi,
                pend, maybe, bits)

    def create_transfers(self, events: np.ndarray, timestamp: Optional[int] = None) -> np.ndarray:
        # The overlapped pipeline must finish (or abandon) its dispatched
        # handles before any op takes the single-phase path — interleaving
        # would reorder stores against the kernel chain. (The stale-gen
        # refire inside create_transfers_finish is the one sanctioned
        # exception: it gen-fences every outstanding handle first and
        # enters through _create_transfers_impl.)
        assert not self._ct_pending, "unfinished split-phase dispatch"
        return self._create_transfers_impl(events, timestamp)

    def _create_transfers_impl(
        self, events: np.ndarray, timestamp: Optional[int] = None
    ) -> np.ndarray:
        self.flush_deferred()
        events = np.atleast_1d(events)
        n = len(events)
        self._beat_events = n
        if timestamp is None:
            timestamp = self.prepare("create_transfers", n)
        if n == 0:
            return np.zeros(0, dtype=types.EVENT_RESULT_DTYPE)

        with tracer.span("sm.ct.stage"):
            staged = self._ct_stage_native(events, timestamp)
            hard = None if staged is None else self._staged_hard(events, staged)
        if staged is not None:
            return self._create_transfers_staged(events, timestamp, staged, hard)
        # No C staging shim: the numpy passes below keep their own finer
        # spans (sm.ct.dupcheck, sm.ct.slots) and are not tiled.
        ts = np.uint64(timestamp) - np.uint64(n) + 1 + np.arange(n, dtype=np.uint64)

        flags16 = events["flags"]
        keys = pack_keys(events["id_lo"], events["id_hi"])
        is_pv = (flags16 & _PV_FLAGS) != 0

        # Serial-only cases (the exists ladders and same-batch pending
        # resolution need the store's view of this very batch): duplicate ids
        # within the batch, ids already stored, or a post/void whose
        # pending_id is an id created in this batch.
        hard = False
        with tracer.span("sm.ct.dupcheck"):
            if n > 1:
                hard = _batch_has_dup(events)
            if not hard and self.transfer_seen.count:
                # Bloom pre-filter: only keys the filter flags (stored ids
                # plus ~2% false positives) hit the real index. The bloom
                # is published at defer time (commit-thread-side), so the
                # stage barrier is only paid on a maybe-hit.
                maybe = self.transfer_seen.maybe(events["id_lo"], events["id_hi"])
                if maybe.any():
                    hard = self._confirm_maybe_ids(keys[maybe])
        pv_keys = None
        if not hard and bool(np.any(is_pv)):
            # lo-major sort with hi tiebreak so the in-batch pending_id
            # probe below sees equal-lo keys adjacent.
            sorted_ids = keys[np.lexsort((keys["hi"], keys["lo"]))]
            pv_keys = pack_keys(
                events["pending_id_lo"][is_pv], events["pending_id_hi"][is_pv]
            )
            hit = np.full(len(pv_keys), NOT_FOUND, dtype=np.uint32)
            search_run(
                sorted_ids, np.zeros(n, dtype=np.uint32), pv_keys,
                hit, np.ones(len(pv_keys), dtype=bool),
            )
            hard = bool(np.any(hit == 0))
        if hard:
            self._count_route("serial_batches")
            with tracer.span("sm.create_transfers.serial"):
                return self._create_transfers_serial(events, timestamp)

        with tracer.span("sm.ct.slots"):
            both_keys = np.concatenate([
                pack_keys(events["debit_account_id_lo"], events["debit_account_id_hi"]),
                pack_keys(events["credit_account_id_lo"], events["credit_account_id_hi"]),
            ])
            both_slots = self.account_index.lookup_batch(both_keys).astype(np.int64)
            both_slots[both_slots == int(NOT_FOUND)] = -1
            dr_slots, cr_slots = both_slots[:n], both_slots[n:]

        # Order-dependent batches (balancing clamps, limit/history accounts)
        # run the fixed-point exact kernel; the rest the cheaper simple one.
        touched = np.concatenate([dr_slots[dr_slots >= 0], cr_slots[cr_slots >= 0]])
        exact_needed = bool(np.any(flags16 & _EXACT_TRANSFER_FLAGS)) or (
            len(touched) > 0
            and bool(np.any(self.acc_flags[touched] & _EXACT_ACCOUNT_FLAGS))
        )
        if exact_needed and self._ops is None:
            # numpy backend has no sweep kernel; exact semantics go serial.
            self._count_route("serial_batches")
            return self._create_transfers_serial(events, timestamp)

        # Host-side rungs the device cannot evaluate (raw-id shape checks).
        host_code = np.zeros(n, dtype=np.uint32)

        def ladder(cond, result):
            np.copyto(host_code, np.uint32(int(result)), where=(host_code == 0) & cond)

        ladder(events["timestamp"] != 0, TR.TIMESTAMP_MUST_BE_ZERO)
        dr_zero = (events["debit_account_id_lo"] == 0) & (events["debit_account_id_hi"] == 0)
        dr_max = (events["debit_account_id_lo"] == U64_MAX) & (
            events["debit_account_id_hi"] == U64_MAX
        )
        cr_zero = (events["credit_account_id_lo"] == 0) & (events["credit_account_id_hi"] == 0)
        cr_max = (events["credit_account_id_lo"] == U64_MAX) & (
            events["credit_account_id_hi"] == U64_MAX
        )
        same = (events["debit_account_id_lo"] == events["credit_account_id_lo"]) & (
            events["debit_account_id_hi"] == events["credit_account_id_hi"]
        )
        # The device ladder checks RESERVED_FLAG/ID zero/max first; these
        # rungs sit between them and the rest — the nonzero-minimum merge in
        # the kernel puts every rung at its exact precedence position.
        # Post/void events branch to their own ladder before any of these
        # rungs (state_machine.zig:1255), so they are masked out.
        reg = ~is_pv
        ladder(reg & dr_zero, TR.DEBIT_ACCOUNT_ID_MUST_NOT_BE_ZERO)
        ladder(reg & dr_max, TR.DEBIT_ACCOUNT_ID_MUST_NOT_BE_INT_MAX)
        ladder(reg & cr_zero, TR.CREDIT_ACCOUNT_ID_MUST_NOT_BE_ZERO)
        ladder(reg & cr_max, TR.CREDIT_ACCOUNT_ID_MUST_NOT_BE_INT_MAX)
        ladder(reg & same, TR.ACCOUNTS_MUST_BE_DIFFERENT)

        if self._ops is None:
            return self._create_transfers_numpy_fast(
                events, ts, keys, dr_slots, cr_slots, host_code
            )

        if exact_needed:
            with tracer.span("sm.create_transfers.exact"):
                return self._create_transfers_exact(
                    events, ts, dr_slots, cr_slots, host_code, timestamp, is_pv, pv_keys
                )
        return self._commit_fast_device(
            events, ts, dr_slots, cr_slots, host_code, timestamp
        )

    def _commit_fast_device(
        self, events, ts, dr_slots, cr_slots, host_code, timestamp
    ) -> np.ndarray:
        """Shared tail of the device fast path (both the C-staged and the
        numpy-staged dispatchers land here): pack, run the fast kernel,
        bail to serial on overflow, store OK rows."""
        n = len(events)
        with tracer.span("sm.ct.stage"):
            b, host_code_p = self._device_batch(events, ts, dr_slots, cr_slots, host_code)
            devicestats.note_call(
                _FAST_KERNEL, (self.state, b, host_code_p),
                bucket=len(host_code_p),
            )
        with tracer.span("sm.ct.dispatch"):
            t_disp = tracer.device_dispatch(
                _FAST_KERNEL, h2d_bytes=_staged_nbytes(b, host_code_p)
            )
            new_state, codes_dev, bail = self._ops.create_transfers_fast(
                self.state, b, host_code_p
            )
        with tracer.span("sm.ct.sync"):
            # The bail sync ends the device step too: the window closes
            # here either way, or the dispatch/step counters diverge on
            # bail-heavy loads.
            bailed = bool(bail)
            codes_h = None if bailed else np.asarray(codes_dev)
            tracer.device_finish(
                _FAST_KERNEL, t_disp,
                d2h_bytes=0 if bailed else codes_h.nbytes,
            )
        if bailed:
            self._count_route("bail_batches")
            return self._create_transfers_serial(events, timestamp)
        self.state = new_state
        self._count_route("fast_batches")
        with tracer.span("sm.ct.post"):
            return self._ct_post_fast(events, ts, codes_h[:n], dr_slots, cr_slots)

    def _ct_post_fast(self, events, ts, codes, dr_slots, cr_slots) -> np.ndarray:
        """The fast kernel's codes → the reply's result records, with the
        OK rows deferred to the store and the batch's slots marked."""
        self._mark_balances(dr_slots, cr_slots)
        ok = codes == 0
        if np.any(ok):
            if ok.all():
                # Zero-copy defer: the log's append stamps timestamps
                # during its own copy (same contract as the numpy path).
                self._defer_store(events, ts)
            else:
                recs = events[ok].copy()
                recs["timestamp"] = ts[ok]
                self._defer_store(recs)
            self.commit_timestamp = int(ts[ok][-1])
        return _codes_to_results(codes)

    # --- split-phase device dispatch (double-buffered commit pipeline) --
    #
    # The serial device path strictly alternates: pack batch N, dispatch
    # its kernel, BLOCK on np.asarray(codes) device→host sync, store, then
    # batch N+1. The split-phase pair lets the commit pipeline dispatch
    # batch N+1's validate/balance kernel while batch N's sync is still in
    # flight — TPU compute overlaps host post-processing. Determinism:
    # results are byte-identical to the serial path because (a) only
    # batches whose routing is INDEPENDENT of the outstanding batch are
    # dispatched ahead (id-disjointness guard below — the dup check of
    # batch N+1 must see batch N's stored ids), and (b) stores still land
    # strictly in op order (dispatch writes nothing; finish stores).

    def _ct_dispatch_stage(self, events: np.ndarray, timestamp: int):
        """The routing half of a dispatch-ahead: the C staging pass, the
        routing bits, the overlap probes against the outstanding handles,
        the bloom confirm, the exact route's kind. The C-staged tuple, or
        None when the batch cannot be dispatched ahead; every refusal
        counts under `sm.ct.dispatch_refused.<why>`."""
        staged = self._ct_stage_native(events, timestamp)
        if staged is None:
            return None  # no C staging shim: keep the single-phase path
        maybe_u8, bits = staged[-2:]
        # bit 1: in-batch duplicate ids → serial; bit 8: a post/void
        # event, which reads the store (exact route behind its barrier, or
        # serial where it names an id of this batch); bit 2: exact kernel
        # route, dispatched ahead unless the batch is on a history account
        # (the caller's test, from the slots).
        if bits & 1:
            return self._ct_refuse("dup")
        if bits & 8:
            return self._ct_refuse("pv")
        if self._ct_pending:
            # An outstanding batch's OK ids are not in the bloom/index yet
            # (its store happens at finish): any id overlap (or a
            # post/void naming one) would mis-validate — refuse to
            # dispatch ahead. Conservative on id_lo alone: false positives
            # only cost the overlap, never correctness. One concatenated
            # membership probe over every outstanding handle (two scans
            # total), not two scans per handle — this runs per dispatch
            # on the hot commit path at window depth up to 8.
            outstanding = (
                self._ct_pending[0]["id_lo"] if len(self._ct_pending) == 1
                else np.concatenate([p["id_lo"] for p in self._ct_pending])
            )
            if bool(np.isin(events["id_lo"], outstanding).any()) or bool(
                np.isin(events["pending_id_lo"], outstanding).any()
            ):
                return self._ct_refuse("overlap")
        if bits & 4:
            # Bloom maybe-hits: confirm against the pending write buffer
            # + durable index (drain-free — reads the LSM, so a
            # GridReadFault here aborts the dispatch cleanly; nothing
            # was mutated).
            with tracer.span("sm.ct.dupcheck"):
                m = maybe_u8.astype(bool)
                hard = self._confirm_maybe_ids(
                    pack_keys(events["id_lo"][m], events["id_hi"][m])
                )
            if hard:
                return self._ct_refuse("stored_id")
        return staged

    @staticmethod
    def _ct_refuse(why: str) -> None:
        """A batch the dispatch-ahead turns away: it runs whole at its own
        turn, behind a settled window. Counted where the decision is
        taken (pv, history, overlap, dup, stored_id, window_full,
        behind_bail)."""
        tracer.count(f"sm.ct.dispatch_refused.{why}")
        return None

    def exact_window_full(self) -> bool:
        """EXACT_DISPATCH_MAX exact handles are out: the commit stage
        settles its oldest batch before it offers the next one."""
        return sum(
            h["kernel"] == _EXACT_KERNEL for h in self._ct_pending
        ) >= EXACT_DISPATCH_MAX

    def create_transfers_dispatch(self, events: np.ndarray, timestamp: int):
        """Stage + dispatch the batch's device kernel WITHOUT syncing.
        Returns a handle for create_transfers_finish, or None when the
        batch cannot run ahead of the outstanding ones (duplicates,
        post/void events, history accounts, id overlap with an
        outstanding handle, a full window, no device backend) — the
        caller then runs the ordinary create_transfers at its op's turn.
        A fast-route batch takes the fast kernel; an exact-route batch
        that reads nothing from the store (_create_transfers_exact's
        deferring kind) takes the exact kernel under the same argument:
        its inputs are the batch, the host's account tables (changed only
        by create_accounts, which drains the window) and the state token,
        which the device orders."""
        if self._ops is None or self.mesh is not None:
            return None
        if len(self._ct_pending) >= DISPATCH_WINDOW_MAX:
            # Window full: refuse — the caller settles the oldest batch
            # first (a pipeline stall, never corruption). Also keeps the
            # scratch ring's slot-reuse distance ≥ the in-flight count.
            return self._ct_refuse("window_full")
        if any(h["gen"] != self._state_gen for h in self._ct_pending):
            # A bail rolled the state token back under the window: the
            # handles still out refire at their finish, on the state as it
            # stands then, so nothing may run on the device ahead of them.
            return self._ct_refuse("behind_bail")
        events = np.atleast_1d(events)
        n = len(events)
        if n == 0:
            return None
        self.flush_deferred()
        with tracer.span("sm.ct.stage"):
            staged = self._ct_dispatch_stage(events, timestamp)
            if staged is None:
                return None
            (_code, host_code, dr_slots, cr_slots, _alo, _ahi,
             _pend, _maybe_u8, bits) = staged
            ts = np.uint64(timestamp) - np.uint64(n) + 1 + np.arange(n, dtype=np.uint64)
            exact = bool(bits & 2)
            if exact:
                hist = self._exact_history_sides(dr_slots, cr_slots)
                if hist[0].any() or hist[1].any():
                    return self._ct_refuse("history")
                if self.exact_window_full():
                    return self._ct_refuse("window_full")
            else:
                b, host_code_p = self._device_batch(
                    events, ts, dr_slots, cr_slots, host_code
                )
                devicestats.note_call(
                    _FAST_KERNEL, (self.state, b, host_code_p),
                    bucket=len(host_code_p),
                )
        if exact:
            # (_exact_stage brings its own sm.ct.prefetch / sm.ct.stage
            # leaves: it runs beside the span above, not inside it.)
            handle = self._exact_stage(
                events, ts, dr_slots, cr_slots, host_code,
                np.zeros(n, dtype=bool), None, hist, defer=True,
            )
            new_state = self._exact_dispatch(handle)
            tracer.count("sm.exact.dispatched_ahead")
            handle["kernel"] = _EXACT_KERNEL
        else:
            with tracer.span("sm.ct.dispatch"):
                # Device-step profiler: the window opens before the call, as
                # on the single-phase and exact paths (the device may start at
                # once), and the finish seam closes it. (No materialization
                # here: this function is deliberately OUTSIDE the jaxlint sync
                # seam.)
                t_disp = tracer.device_dispatch(
                    _FAST_KERNEL, h2d_bytes=_staged_nbytes(b, host_code_p)
                )
                new_state, codes_dev, bail_dev = self._ops.create_transfers_fast(
                    self.state, b, host_code_p
                )
            handle = {
                "kernel": _FAST_KERNEL, "events": events, "ts": ts, "n": n,
                "codes": codes_dev, "bail": bail_dev, "t_disp": t_disp,
                "dr_slots": dr_slots, "cr_slots": cr_slots,
            }
        handle.update(
            timestamp=timestamp, prev_state=self.state, gen=self._state_gen,
            id_lo=events["id_lo"],
        )
        # Chain optimistically: batch N+1's kernel may consume this token
        # before N's sync lands (the device orders the data dependency).
        self.state = new_state
        self._ct_pending.append(handle)
        return handle

    def create_transfers_finish(self, handle) -> np.ndarray:
        """Sync + store the dispatched batch, by its kernel;
        byte-identical results to the single-phase path (bail falls back
        to serial exactly as _commit_fast_device and
        _create_transfers_exact do)."""
        assert self._ct_pending and handle is self._ct_pending[0], (
            "split-phase finish out of dispatch order"
        )
        self._ct_pending.pop(0)
        events, timestamp, n = handle["events"], handle["timestamp"], handle["n"]
        self._beat_events = n
        if handle["gen"] != self._state_gen:
            # An earlier batch in the chain bailed and rolled the state
            # token back: this kernel consumed a revoked token — discard
            # and re-execute from the current (correct) state. The refire
            # mutates state that any LATER outstanding handle's kernel
            # did not observe, so fence those too (they will refire in
            # turn at their own finish).
            tracer.device_finish(handle["kernel"], handle.get("t_disp", 0))
            self._state_gen += 1
            return self._create_transfers_impl(events, timestamp)
        if handle["kernel"] == _EXACT_KERNEL:
            results = self._exact_finish(handle)
        else:
            results = None  # as _exact_finish: None where the kernel bailed
            with tracer.span("sm.ct.sync"):
                bailed = bool(handle["bail"])
                codes_h = None if bailed else np.asarray(handle["codes"])
                tracer.device_finish(
                    _FAST_KERNEL, handle.get("t_disp", 0),
                    d2h_bytes=0 if bailed else codes_h.nbytes,
                )
            if bailed:
                self._count_route("bail_batches")
            else:
                self._count_route("fast_batches")
                with tracer.span("sm.ct.post"):
                    results = self._ct_post_fast(
                        events, handle["ts"], codes_h[:n],
                        handle["dr_slots"], handle["cr_slots"],
                    )
                    # The handle holds the last references to the kernel's outputs
                    # and to the state before it: letting go of device buffers can
                    # take as long as the posting, and is part of it.
                    handle.clear()
        if results is not None:
            return results
        # A bailed handle is not cleared: the state before it is read here,
        # and not held in a local, whose release at the return would let go
        # of the old tables after `sm.ct.post`, outside every leaf.
        self.state = handle["prev_state"]
        self._state_gen += 1
        return self._create_transfers_serial(events, timestamp)

    def create_transfers_abandon_all(self) -> None:
        """Discard EVERY dispatched-but-unfinished handle (depth-N window
        reclaim behind a grid repair): roll the state token back to the
        oldest LIVE handle's pre-dispatch value — live handles form a
        suffix of the FIFO (gen only moves forward), and the oldest live
        base is the state before any abandoned kernel in the current
        chain ran. Stale handles' bases predate a rollback that already
        happened below them (a bail refire rebuilt state past their
        base), so restoring one would clobber the corrected state."""
        if not self._ct_pending:
            return
        live = next(
            (h for h in self._ct_pending if h["gen"] == self._state_gen),
            None,
        )
        for h in self._ct_pending:
            tracer.device_finish(h["kernel"], h.get("t_disp", 0))
        self._ct_pending.clear()
        if live is not None:
            self.state = live["prev_state"]
            self._state_gen += 1

    def dispatch_depth_default(self) -> int:
        """Adaptive cross-batch commit-window depth (vsr/replica.py
        commit_depth): min(pipeline_max, 4) where dispatch-ahead buys
        real overlap (an accelerator executes batch N+1 while the host
        drains batch N's store/reply), 1 where the serial single-phase
        path already wins (host-only backends, XLA-CPU — the "device"
        work shares the host cores — and mesh-sharded execution, whose
        kernels never take the split-phase path). --commit-depth /
        TIGERBEETLE_TPU_COMMIT_DEPTH force either way."""
        if self._ops is None or self.mesh is not None:
            return 1
        import jax

        # Anything that is not the XLA-CPU backend is an accelerator:
        # device compute genuinely overlaps the host's drain there.
        # XLA-CPU shares the host cores, so dispatch-ahead only reorders
        # work.
        if jax.default_backend() != "cpu":
            return min(self.config.pipeline_max, 4)
        return 1

    def _staged_hard(self, events: np.ndarray, staged):
        """(hard, is_pv, pv_keys): does the C-staged batch need the serial
        path (duplicate ids in the batch or in the store, a post/void of a
        pending created in this same batch)?"""
        maybe_u8, bits = staged[-2:]
        n = len(events)
        hard = bool(bits & 1)  # duplicate ids within the batch
        if not hard and (bits & 4):
            # Bloom hits: stored ids (or ~2% false positives) — confirm
            # against the pending write buffer + durable index for just
            # the flagged keys (drain-free: see _confirm_maybe_ids).
            with tracer.span("sm.ct.dupcheck"):
                m = maybe_u8.astype(bool)
                hard = self._confirm_maybe_ids(
                    pack_keys(events["id_lo"][m], events["id_hi"][m])
                )
        pv_keys = None
        is_pv = None
        if not hard and (bits & 8):
            # post/void of a pending created in this same batch → serial.
            flags16 = events["flags"]
            is_pv = (flags16 & _PV_FLAGS) != 0
            keys = pack_keys(events["id_lo"], events["id_hi"])
            sorted_ids = keys[np.lexsort((keys["hi"], keys["lo"]))]
            pv_keys = pack_keys(
                events["pending_id_lo"][is_pv], events["pending_id_hi"][is_pv]
            )
            hit = np.full(len(pv_keys), NOT_FOUND, dtype=np.uint32)
            search_run(
                sorted_ids, np.zeros(n, dtype=np.uint32), pv_keys,
                hit, np.ones(len(pv_keys), dtype=bool),
            )
            hard = bool(np.any(hit == 0))
        return hard, is_pv, pv_keys

    def _create_transfers_staged(
        self, events: np.ndarray, timestamp: int, staged, routed
    ) -> np.ndarray:
        """Routing + commit from the C-staged batch (same decisions as the
        numpy fallback path in create_transfers, same byte-exact results —
        the staged ladder IS host_kernel.validate's merged ladder).
        `routed` is `_staged_hard`'s answer for it."""
        (code, host_code, dr_slots, cr_slots, amt_lo, amt_hi,
         pend_u8, _maybe_u8, bits) = staged
        hard, is_pv, pv_keys = routed
        n = len(events)
        ts = np.uint64(timestamp) - np.uint64(n) + 1 + np.arange(n, dtype=np.uint64)
        if hard:
            self._count_route("serial_batches")
            with tracer.span("sm.create_transfers.serial"):
                return self._create_transfers_serial(events, timestamp)

        exact_needed = bool(bits & 2)
        if exact_needed and self._ops is None:
            self._count_route("serial_batches")
            return self._create_transfers_serial(events, timestamp)

        if self._ops is not None:
            if exact_needed:
                if is_pv is None:
                    is_pv = (events["flags"] & _PV_FLAGS) != 0
                with tracer.span("sm.create_transfers.exact"):
                    return self._create_transfers_exact(
                        events, ts, dr_slots, cr_slots, host_code,
                        timestamp, is_pv, pv_keys,
                    )
            return self._commit_fast_device(
                events, ts, dr_slots, cr_slots, host_code, timestamp
            )

        # numpy fast path: the staged merged ladder IS the validation result.
        return self._commit_fast_numpy(
            events, ts, code, dr_slots, cr_slots, amt_lo, amt_hi,
            pend_u8.astype(bool), timestamp,
        )

    def _commit_fast_numpy(
        self, events, ts, codes, dr_slots, cr_slots, amt_lo, amt_hi, pend,
        timestamp,
    ) -> np.ndarray:
        """Shared tail of the numpy fast path (C-staged and numpy-staged
        dispatchers): exact u128 posting, bail to serial on overflow,
        store OK rows."""
        from tigerbeetle_tpu.models import host_kernel

        ok = codes == 0
        with tracer.span("sm.ct.post"):
            overflow = host_kernel.post(
                self._host_bal, dr_slots, cr_slots, amt_lo, amt_hi,
                ok & pend, ok & ~pend,
            )
        if overflow:
            self._count_route("bail_batches")
            return self._create_transfers_serial(events, timestamp)
        self._count_route("fast_batches")
        # The store is deferred past the reply send (replica._finish_commit
        # flushes in op order): the reply is fully determined here.
        return self._ct_post_fast(events, ts, codes, dr_slots, cr_slots)

    def _device_batch(self, events, ts, dr_slots, cr_slots, host_code):
        """Pack events into the kernel's SoA form, padded to a power-of-two
        bucket so each kernel compiles once per bucket size, not per batch
        length. Padding events carry a nonzero host code (never applied) and
        are stripped from the results.

        The padded block is written into the dispatch scratch ring's next
        slot (one slot per in-flight generation, lazily allocated per
        bucket size): the depth-N commit window stages up to
        DISPATCH_WINDOW_MAX batches before the oldest finishes, and slot
        reuse only comes around after that many later dispatches — by
        which point the slot's previous occupant has synced. Bucket
        shapes are the only shape axis, so the ring adds no compiles."""
        n = len(events)
        n_pad = 1 << max(4, (n - 1).bit_length())
        scratch = self._disp_scratch[self._disp_seq % DISPATCH_WINDOW_MAX]
        self._disp_seq += 1

        def pad1(name, a, fill=0):
            if len(a) != n:
                return a
            out = scratch.get((name, n_pad))
            if out is None:
                out = scratch[(name, n_pad)] = np.empty(
                    (n_pad, *a.shape[1:]), dtype=a.dtype
                )
            if n_pad != n:
                out[n:] = fill  # padding rows stay inert under `fill`
            out[:n] = a
            return out

        host_code_p = pad1("host_code", host_code, fill=int(TR.ID_MUST_NOT_BE_ZERO))
        cols = self._decode_transfers_native(
            events, ts, dr_slots, cr_slots, scratch, n, n_pad
        )
        if cols is None:
            cols = dict(
                id=pad1(
                    "id",
                    types.u64_pair_to_limbs(events["id_lo"], events["id_hi"]),
                ),
                dr_slot=pad1("dr_slot", dr_slots.astype(np.int32), fill=-1),
                cr_slot=pad1("cr_slot", cr_slots.astype(np.int32), fill=-1),
                amount=pad1(
                    "amount",
                    types.u64_pair_to_limbs(events["amount_lo"], events["amount_hi"]),
                ),
                pending_id=pad1(
                    "pending_id",
                    types.u64_pair_to_limbs(events["pending_id_lo"], events["pending_id_hi"])
                ),
                timeout=pad1("timeout", events["timeout"].astype(np.uint32)),
                ledger=pad1("ledger", events["ledger"].astype(np.uint32)),
                code=pad1("code", events["code"].astype(np.uint32)),
                flags=pad1("flags", events["flags"].astype(np.uint32)),
                timestamp=pad1("timestamp", types.u64_to_limbs(ts)),
            )
        self._scratch_note(n_pad)
        b = self._ops.TransferBatch(**cols)
        return b, host_code_p

    # Dispatches a scratch bucket may sit idle before retirement. Large
    # enough that a bucket in ANY live dispatch window (≤ DISPATCH_
    # WINDOW_MAX old) can never be reclaimed under a kernel; small
    # enough that a workload shift frees the old buckets within one
    # bench section. Class attribute so tests can force fast churn.
    SCRATCH_STALE_AFTER = 512

    def _scratch_note(self, n_pad: int) -> None:
        """Device-memory-ledger upkeep per dispatch: stamp the bucket's
        last use, publish its live bytes (summed over every ring slot)
        as the `device.mem.scratch.b<n_pad>.bytes` gauge, and retire
        buckets the workload stopped using (satellite: the registry and
        the ring stay bounded under bucket churn)."""
        self._scratch_last_use[n_pad] = self._disp_seq
        if tracer.enabled():
            nbytes = sum(
                a.nbytes
                for slot in self._disp_scratch
                for (_, bkt), a in slot.items()
                if bkt == n_pad
            )
            tracer.device_mem_set(f"scratch.b{n_pad}", nbytes)
            tracer.device_mem_set("balances", self._balances_nbytes)
        if len(self._scratch_last_use) > 1:
            stale = [
                b for b, last in self._scratch_last_use.items()
                if self._disp_seq - last > self.SCRATCH_STALE_AFTER
            ]
            for b in stale:
                self._scratch_retire(b)

    def _scratch_retire(self, n_pad: int) -> None:
        """Free one stale bucket: its staging buffers in every ring
        slot, its owner gauge, and its devicestats shape/cost rows.
        Safe by construction — a bucket referenced by an in-flight
        handle was used within DISPATCH_WINDOW_MAX dispatches, far
        inside SCRATCH_STALE_AFTER."""
        for slot in self._disp_scratch:
            for key in [k for k in slot if k[1] == n_pad]:
                del slot[key]
        self._scratch_last_use.pop(n_pad, None)
        tracer.device_mem_retire_prefix(f"scratch.b{n_pad}")
        devicestats.retire_bucket(n_pad)

    # Device-batch SoA columns: (trailing shape, dtype, padding fill).
    _DISPATCH_COLS = {
        "id": ((4,), np.uint32, 0),
        "dr_slot": ((), np.int32, -1),
        "cr_slot": ((), np.int32, -1),
        "amount": ((4,), np.uint32, 0),
        "pending_id": ((4,), np.uint32, 0),
        "timeout": ((), np.uint32, 0),
        "ledger": ((), np.uint32, 0),
        "code": ((), np.uint32, 0),
        "flags": ((), np.uint32, 0),
        "timestamp": ((2,), np.uint32, 0),
    }

    def _decode_transfers_native(
        self, events, ts, dr_slots, cr_slots, scratch, n: int, n_pad: int
    ):
        """The native wire→SoA decode (csrc/busio.c busio_decode_transfers,
        docs/NATIVE_DATAPATH.md): one GIL-releasing C pass fills the
        dispatch scratch ring's columns straight from the wire AoS records
        — replacing ~10 strided numpy field reads + limb packs per batch.
        Byte-identical to the numpy packing (tests/test_native_bus.py);
        None routes the caller to the numpy path (codec off, strided
        events, or staging outputs in an unexpected layout)."""
        from tigerbeetle_tpu.vsr.header import _native_codec

        codec = _native_codec()
        if (
            codec is None
            or events.dtype != types.TRANSFER_DTYPE
            or events.strides[0] != events.dtype.itemsize
            or dr_slots.dtype != np.int64 or not dr_slots.flags["C_CONTIGUOUS"]
            or cr_slots.dtype != np.int64 or not cr_slots.flags["C_CONTIGUOUS"]
            # C derives row i's timestamp as ts[0] + i — both dispatchers
            # build exactly that arange, but a future caller with a
            # different shape must take the numpy path, not corrupt.
            or int(ts[-1]) - int(ts[0]) != n - 1
        ):
            return None
        cols = {}
        for name, (shape, dtype, fill) in self._DISPATCH_COLS.items():
            out = scratch.get((name, n_pad))
            if out is None:
                out = scratch[(name, n_pad)] = np.empty(
                    (n_pad, *shape), dtype=dtype
                )
            if n_pad != n:
                out[n:] = fill
            cols[name] = out
        with tracer.span("bus.decode"):
            codec.decode_transfers_into(
                events, int(ts[0]), dr_slots, cr_slots, cols, n
            )
        return cols

    def _exact_prefetch(self, events: np.ndarray, is_pv: np.ndarray, pv_keys):
        """Host prefetch for post/void events: resolve pending_id against the
        store and evaluate the store-dependent ladder rungs (codes 25-30)
        the device cannot (reference prefetch, state_machine.zig:560-655).

        Returns (pv_code, pinfo dict of per-event numpy arrays,
        pending_recs, p_rec_idx) where p_rec_idx maps each event to its row
        in pending_recs (-1 for non-post/void or not-found events)."""
        from tigerbeetle_tpu.ops import commit_exact as ce

        n = len(events)
        found = np.zeros(n, dtype=bool)
        amount = np.zeros((n, 4), dtype=np.uint32)
        p_dr = np.full(n, -1, dtype=np.int32)
        p_cr = np.full(n, -1, dtype=np.int32)
        p_ts = np.zeros(n, dtype=np.uint64)
        p_timeout = np.zeros(n, dtype=np.uint32)
        base = np.full(n, ce.FULFILL_NONE, dtype=np.int32)
        group = np.full(n, n, dtype=np.int32)
        pv_code = np.zeros(n, dtype=np.uint32)
        p_rec_idx = np.full(n, -1, dtype=np.int64)
        pending_recs = np.zeros(0, dtype=types.TRANSFER_DTYPE)
        if not np.any(is_pv):
            return pv_code, dict(
                found=found, amount=amount, dr_slot=p_dr, cr_slot=p_cr,
                timestamp=p_ts, timeout=p_timeout, base_fulfillment=base,
                group=group,
            ), pending_recs, p_rec_idx

        pv_ix = np.nonzero(is_pv)[0]
        assert pv_keys is not None  # dispatcher built it for the hard-check
        pkeys = pv_keys
        # Same referenced pending ⇒ same fulfillment group (first successful
        # post/void wins; ops/commit_exact.fulfillment_prefix).
        _, inv = np.unique(pkeys, return_inverse=True)
        group[pv_ix] = inv.astype(np.int32)
        rows = self.transfer_index.lookup_batch(pkeys)
        has = rows != NOT_FOUND
        pv_code[pv_ix[~has]] = np.uint32(int(TR.PENDING_TRANSFER_NOT_FOUND))
        if np.any(has):
            hit = pv_ix[has]
            urows, uinv = np.unique(rows[has].astype(np.int64), return_inverse=True)
            pending_recs = self.transfer_log.gather(urows)
            p_rec_idx[hit] = uinv
            prec = pending_recs[uinv]

            c = np.zeros(len(hit), dtype=np.uint32)

            def fl(cond, result):
                np.copyto(c, np.uint32(int(result)), where=(c == 0) & cond)

            not_pending = (prec["flags"] & np.uint16(TransferFlags.PENDING)) == 0
            fl(not_pending, TR.PENDING_TRANSFER_NOT_PENDING)
            t_dr_nz = (events["debit_account_id_lo"][hit] != 0) | (
                events["debit_account_id_hi"][hit] != 0
            )
            dr_diff = (events["debit_account_id_lo"][hit] != prec["debit_account_id_lo"]) | (
                events["debit_account_id_hi"][hit] != prec["debit_account_id_hi"]
            )
            fl(t_dr_nz & dr_diff, TR.PENDING_TRANSFER_HAS_DIFFERENT_DEBIT_ACCOUNT_ID)
            t_cr_nz = (events["credit_account_id_lo"][hit] != 0) | (
                events["credit_account_id_hi"][hit] != 0
            )
            cr_diff = (events["credit_account_id_lo"][hit] != prec["credit_account_id_lo"]) | (
                events["credit_account_id_hi"][hit] != prec["credit_account_id_hi"]
            )
            fl(t_cr_nz & cr_diff, TR.PENDING_TRANSFER_HAS_DIFFERENT_CREDIT_ACCOUNT_ID)
            fl(
                (events["ledger"][hit] != 0) & (events["ledger"][hit] != prec["ledger"]),
                TR.PENDING_TRANSFER_HAS_DIFFERENT_LEDGER,
            )
            fl(
                (events["code"][hit] != 0) & (events["code"][hit] != prec["code"]),
                TR.PENDING_TRANSFER_HAS_DIFFERENT_CODE,
            )
            pv_code[hit] = c

            found[hit] = True
            amount[hit] = types.u64_pair_to_limbs(prec["amount_lo"], prec["amount_hi"])
            pdr = self.account_index.lookup_batch(
                pack_keys(prec["debit_account_id_lo"], prec["debit_account_id_hi"])
            )
            pcr = self.account_index.lookup_batch(
                pack_keys(prec["credit_account_id_lo"], prec["credit_account_id_hi"])
            )
            p_dr[hit] = np.where(pdr == NOT_FOUND, -1, pdr.astype(np.int64)).astype(np.int32)
            p_cr[hit] = np.where(pcr == NOT_FOUND, -1, pcr.astype(np.int64)).astype(np.int32)
            p_ts[hit] = prec["timestamp"]
            p_timeout[hit] = prec["timeout"]
            base_u = self.posted.get_many(
                pending_recs["timestamp"], ce.FULFILL_NONE
            )
            base[hit] = base_u[uinv]
        return pv_code, dict(
            found=found, amount=amount, dr_slot=p_dr, cr_slot=p_cr,
            timestamp=p_ts, timeout=p_timeout, base_fulfillment=base, group=group,
        ), pending_recs, p_rec_idx

    def _exact_history_sides(self, dr_slots, cr_slots):
        """(dr_hist, cr_hist): which events debit or credit a history
        account, from the staged slots and the host's flag table."""
        n = len(dr_slots)
        hist_flag = np.uint32(AccountFlags.HISTORY)
        dr_hist = np.zeros(n, dtype=bool)
        cr_hist = np.zeros(n, dtype=bool)
        dr_valid = dr_slots >= 0
        cr_valid = cr_slots >= 0
        dr_hist[dr_valid] = (self.acc_flags[dr_slots[dr_valid]] & hist_flag) != 0
        cr_hist[cr_valid] = (self.acc_flags[cr_slots[cr_valid]] & hist_flag) != 0
        return dr_hist, cr_hist

    def _create_transfers_exact(
        self, events, ts, dr_slots, cr_slots, host_code, timestamp, is_pv, pv_keys=None
    ) -> np.ndarray:
        """Order-dependent batches via the fixed-point sweep kernel
        (ops/commit_exact.py): balancing clamps, limit flags, history,
        linked chains, and pending post/void. Stage, dispatch and finish
        in a row; the split-phase pair runs the same three with other
        batches' work between them."""
        # Which exact batches wait for the store. A batch with a post/void
        # event reads the id index, the object log and the posted groove
        # in its prefetch and writes the posted groove in its tail; a
        # batch on a history account writes history rows in its tail.
        # Both need every queued async store job landed first (the stage
        # is then idle for the inline writes too): they take the barrier
        # and store inline. A batch with neither reads nothing from the
        # store and writes nothing but its transfer rows, so it takes the
        # fast path's discipline: no barrier, its OK rows deferred to
        # _finish_commit (the store thread when the stage is attached),
        # and, from create_transfers_dispatch, a place in the window.
        hist = self._exact_history_sides(dr_slots, cr_slots)
        defer = not (bool(np.any(is_pv)) or hist[0].any() or hist[1].any())
        if not defer:
            self.store_barrier()
        x = self._exact_stage(
            events, ts, dr_slots, cr_slots, host_code, is_pv, pv_keys, hist, defer
        )
        prev_state = self.state
        self.state = self._exact_dispatch(x)
        results = self._exact_finish(x)
        if results is None:
            self.state = prev_state
            return self._create_transfers_serial(events, timestamp)
        return results

    def _exact_stage(
        self, events, ts, dr_slots, cr_slots, host_code, is_pv, pv_keys, hist, defer
    ) -> dict:
        """Everything the exact kernel's call needs and everything its
        finish needs, from the C-staged batch: the prefetch, the merged
        host codes, chain ids, the padded pending info, the sort plan, the
        counters' inputs. `defer` is the caller's decision (no post/void
        event, no history account in `hist`): where it is False the
        caller has taken the barrier and the prefetch reads the store."""
        from tigerbeetle_tpu.ops import commit_exact

        n = len(events)
        dr_hist, cr_hist = hist
        has_pv = bool(np.any(is_pv))
        with tracer.span("sm.ct.prefetch"):
            pv_code, pinfo_np, pending_recs, p_rec_idx = self._exact_prefetch(
                events, is_pv, pv_keys
            )

        with tracer.span("sm.ct.stage"):
            # Merge the post/void store rungs at their precedence (25-30 sit
            # between the host ladder's early rungs and the device's late ones).
            big = np.uint32(0xFFFFFFFF)
            merged = np.minimum(
                np.where(host_code == 0, big, host_code),
                np.where(pv_code == 0, big, pv_code),
            )
            host_code = np.where(merged == big, np.uint32(0), merged)

            # Linked-chain segments: contiguous, chain id = head index
            # (singleton chains for unlinked events). An unterminated trailing
            # chain fails with CHAIN_OPEN before any other rung (oracle._execute).
            linked = (events["flags"] & np.uint16(TransferFlags.LINKED)) != 0
            new_chain = np.ones(n, dtype=bool)
            if n > 1:
                new_chain[1:] = ~linked[:-1]
            chain_id = np.maximum.accumulate(
                np.where(new_chain, np.arange(n), 0)
            ).astype(np.int32)
            if linked[n - 1]:
                host_code[n - 1] = np.uint32(int(TR.LINKED_EVENT_CHAIN_OPEN))

            b, host_code_p = self._device_batch(events, ts, dr_slots, cr_slots, host_code)
            n_pad = int(b.flags.shape[0])

            def padp(a, fill):
                out = np.full((n_pad, *a.shape[1:]), fill, dtype=a.dtype)
                out[:n] = a
                return out

            pinfo = commit_exact.PendingInfo(
                found=padp(pinfo_np["found"], False),
                amount=padp(pinfo_np["amount"], 0),
                dr_slot=padp(pinfo_np["dr_slot"], -1),
                cr_slot=padp(pinfo_np["cr_slot"], -1),
                timestamp=padp(types.u64_to_limbs(pinfo_np["timestamp"]), 0),
                timeout=padp(pinfo_np["timeout"], 0),
                base_fulfillment=padp(pinfo_np["base_fulfillment"], commit_exact.FULFILL_NONE),
                group=padp(pinfo_np["group"], n_pad),
            )
            chain_id_p = np.arange(n_pad, dtype=np.int32)
            chain_id_p[:n] = chain_id

            # Host-side sort plan: a ~100 µs numpy lexsort here replaces ~ms of
            # device lax.sort inside the kernel (SortPlan docstring).
            has_chains = bool(np.any(linked))
            with tracer.span("sm.ct.plan"):
                flags_p, dr_p, cr_p = (
                    np.asarray(b.flags), np.asarray(b.dr_slot), np.asarray(b.cr_slot)
                )
                plan = commit_exact.build_sort_plan(
                    flags_p, dr_p, cr_p,
                    pinfo.dr_slot, pinfo.cr_slot, chain_id_p, pinfo.group,
                    int(self.state.ledger.shape[0]),
                )
                # The events that read a limited or balancing account are
                # walked level by level before the sweeps where the walk is
                # as deep as the sweeps' budget, however long their lines
                # (a plain batch only: no post/void, no chain).
                lines = None
                if not (has_pv or has_chains):
                    lim = lambda s, f: (s >= 0) & ((self.acc_flags[s] & int(f)) != 0)  # noqa: E731
                    lines = commit_exact.build_line_plan(
                        plan, flags_p, dr_p, cr_p,
                        lim(dr_p, AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS),
                        lim(cr_p, AccountFlags.CREDITS_MUST_NOT_EXCEED_DEBITS),
                    )
            x = {
                "events": events, "ts": ts, "n": n, "is_pv": is_pv,
                "defer": defer,
                "dr_hist": dr_hist, "cr_hist": cr_hist,
                "dr_slots": dr_slots, "cr_slots": cr_slots,
                "p_dr": pinfo_np["dr_slot"], "p_cr": pinfo_np["cr_slot"],
                "pending_recs": pending_recs, "p_rec_idx": p_rec_idx,
                "args": (b, host_code_p, pinfo, chain_id_p, plan, lines),
                "has_pv": has_pv, "has_chains": has_chains,
                # The steps of one walk of the lines (0: no walk).
                "line": 0 if lines is None else int(np.count_nonzero(lines.count)),
            }
            if tracer.enabled():
                # What the batch brings the kernel: chains of two or more
                # events (by their heads) and how its 2n postings fall on
                # slots. Counted at the sync, for batches that stay on
                # this route.
                pv_p = padp(is_pv, False)
                posted = int(np.count_nonzero(np.concatenate([
                    np.where(pv_p, pinfo.dr_slot, np.asarray(b.dr_slot)),
                    np.where(pv_p, pinfo.cr_slot, np.asarray(b.cr_slot)),
                ]) >= 0))
                x["chain_heads"] = new_chain & linked
                x["slot_counts"] = commit_exact.plan_slot_segments(plan, posted)
            devicestats.note_call(
                _EXACT_KERNEL,
                (self.state, b, host_code_p, pinfo, chain_id_p, plan),
                kwargs=dict(has_pv=has_pv, has_chains=has_chains, lines=lines),
                bucket=n_pad,
            )
        return x

    def _exact_dispatch(self, x: dict):
        """The jitted call on the current state token, nothing taken back
        (this half stays OUTSIDE the jaxlint sync seam). The kernel's
        outputs ride in `x` to _exact_finish; the new token is returned
        for the caller to chain."""
        b, host_code_p, pinfo, chain_id_p, plan, lines = x.pop("args")
        with tracer.span("sm.ct.dispatch"):
            x["t_disp"] = tracer.device_dispatch(
                _EXACT_KERNEL,
                h2d_bytes=_staged_nbytes(b, host_code_p)
                + _staged_nbytes(pinfo, chain_id_p) + _staged_nbytes(plan, 0)
                + _staged_nbytes(lines or (), 0),
            )
            (new_state, x["codes"], x["amounts"], x["dr_after"], x["cr_after"],
             x["bail"], x["sweeps"]) = self._ops.create_transfers_exact(
                self.state, b, host_code_p, pinfo, chain_id_p, plan, lines=lines,
                # tidy: allow=retrace-static-arg — deliberate bounded specialization: two bools → at most 4 kernel variants, each skipping a whole sweep phase; the line walk (plain batches only) adds one more, its shapes following the batch bucket alone
                has_pv=x["has_pv"], has_chains=x["has_chains"],
            )
        return new_state

    def _exact_finish(self, x: dict) -> Optional[np.ndarray]:
        """Take the exact kernel's results back and post them: the sync,
        the `sm.exact.*` counters, the OK rows stored (deferred, or inline
        behind the barrier the batch took), the posted groove, the history
        rows. None when the kernel bailed: nothing was posted and the
        caller rolls the state token back and runs the serial path."""
        events, ts, n, is_pv = x["events"], x["ts"], x["n"], x["is_pv"]
        with tracer.span("sm.ct.sync"):
            # The bail sync ends the device step too (same close-on-bail
            # rule as _commit_fast_device).
            bailed = bool(x["bail"])
            d2h = 0
            if not bailed:
                # Materialize the FULL padded arrays: sliced views would
                # undercount the device→host volume (same rule as
                # _read_balances).
                codes_h = np.asarray(x["codes"])
                amounts_h = np.asarray(x["amounts"])
                d2h = codes_h.nbytes + amounts_h.nbytes
                if "slot_counts" in x:
                    # (staged with the tracer on.) The while_loop's own
                    # carry, 4 bytes, at the seam the kernel's results are
                    # taken at anyway.
                    chain_heads = x["chain_heads"]
                    slots_touched, slot_postings_max = x["slot_counts"]
                    sweeps = int(x["sweeps"])
                    tracer.count("sm.exact.sweeps", sweeps)
                    # One walk before the first sweep and one after each sweep
                    # that was not yet stable: as many walks as sweeps.
                    tracer.count("sm.exact.line_steps", x["line"] * sweeps)
                    tracer.count("sm.exact.chains", int(np.count_nonzero(chain_heads)))
                    tracer.count(
                        "sm.exact.chains_rolled_back",
                        int(np.count_nonzero(chain_heads & (codes_h[:n] != 0))),
                    )
                    tracer.count("sm.exact.slots_touched", slots_touched)
                    tracer.count("sm.exact.slot_postings_max", slot_postings_max)
            tracer.device_finish(_EXACT_KERNEL, x["t_disp"], d2h_bytes=d2h)
        if bailed:
            self._count_route("bail_batches")
            return None
        defer = x["defer"]
        self._count_route("exact_batches")
        self._mark_balances(x["dr_slots"], x["cr_slots"], x["p_dr"], x["p_cr"])
        tracer.count("sm.exact.store_deferred", int(defer))
        with tracer.span("sm.ct.post"):
            codes = codes_h[:n]
            amounts = amounts_h[:n]
            amt_lo, amt_hi = types.limbs_to_u64_pair(amounts)

            ok = codes == 0
            if np.any(ok):
                pending_recs, p_rec_idx = x["pending_recs"], x["p_rec_idx"]
                # Transfers are stored with their POST-CLAMP amounts
                # (state_machine.zig:1330 stores t2.amount = clamped); post/void
                # records derive their account/ledger/code/user_data fields from
                # the pending (state_machine.zig:1462-1480, oracle 563-579).
                recs = events[ok].copy()
                recs["timestamp"] = ts[ok]
                recs["amount_lo"] = amt_lo[ok]
                recs["amount_hi"] = amt_hi[ok]
                sel = is_pv[ok]
                if np.any(sel):
                    pi = p_rec_idx[ok][sel]
                    assert np.all(pi >= 0), "ok post/void must have resolved its pending"
                    prec = pending_recs[pi]
                    for f in (
                        "debit_account_id_lo", "debit_account_id_hi",
                        "credit_account_id_lo", "credit_account_id_hi",
                    ):
                        recs[f][sel] = prec[f]
                    recs["ledger"][sel] = prec["ledger"]
                    recs["code"][sel] = prec["code"]
                    recs["timeout"][sel] = 0
                    ud128_zero = (recs["user_data_128_lo"][sel] == 0) & (
                        recs["user_data_128_hi"][sel] == 0
                    )
                    recs["user_data_128_lo"][sel] = np.where(
                        ud128_zero, prec["user_data_128_lo"], recs["user_data_128_lo"][sel]
                    )
                    recs["user_data_128_hi"][sel] = np.where(
                        ud128_zero, prec["user_data_128_hi"], recs["user_data_128_hi"][sel]
                    )
                    recs["user_data_64"][sel] = np.where(
                        recs["user_data_64"][sel] == 0,
                        prec["user_data_64"], recs["user_data_64"][sel],
                    )
                    recs["user_data_32"][sel] = np.where(
                        recs["user_data_32"][sel] == 0,
                        prec["user_data_32"], recs["user_data_32"][sel],
                    )
                if defer:
                    # Nothing below finds a row to write: no post/void, no
                    # history account.
                    self._defer_store(recs)
                else:
                    self._store_new_transfers(recs)
                self.commit_timestamp = int(ts[ok][-1])

                # Posted-groove updates (reference PostedGroove insert) —
                # fully vectorized into the durable index.
                pv_ok_ix = np.nonzero(ok & is_pv)[0]
                if len(pv_ok_ix):
                    p_ts_ok = pending_recs["timestamp"][p_rec_idx[pv_ok_ix]]
                    posted_ok = (
                        events["flags"][pv_ok_ix]
                        & np.uint16(TransferFlags.POST_PENDING_TRANSFER)
                    ) != 0
                    self.posted.insert_arrays(
                        p_ts_ok,
                        np.where(
                            posted_ok,
                            np.uint32(oracle_mod.FULFILLMENT_POSTED),
                            np.uint32(oracle_mod.FULFILLMENT_VOIDED),
                        ),
                    )

                # History rows from the kernel's post-event balances
                # (state_machine.zig:1342-1364), in event order; post/void
                # writes no history row (mirroring the oracle). Vectorized:
                # limb→u64-pair conversions + key gathers, no per-row Python
                # (VERDICT r3 weak #6 closed).
                dr_hist, cr_hist = x["dr_hist"], x["cr_hist"]
                need = ok & (dr_hist | cr_hist) & ~is_pv
                if np.any(need):
                    from tigerbeetle_tpu.lsm.groove import HISTORY_DTYPE

                    ix = np.nonzero(need)[0]
                    rows = np.zeros(len(ix), dtype=HISTORY_DTYPE)
                    rows["timestamp"] = ts[ix]
                    for side, side_hist, slots_all, after in (
                        ("dr", dr_hist, x["dr_slots"], x["dr_after"]),
                        ("cr", cr_hist, x["cr_slots"], x["cr_after"]),
                    ):
                        m = side_hist[ix]
                        if not m.any():
                            continue
                        s = slots_all[ix[m]]
                        rows[f"{side}_account_id_lo"][m] = self.acc_key["lo"][s]
                        rows[f"{side}_account_id_hi"][m] = self.acc_key["hi"][s]
                        for fld, limbs in zip(
                            ("debits_pending", "debits_posted",
                             "credits_pending", "credits_posted"),
                            after,
                        ):
                            lo_c, hi_c = types.limbs_to_u64_pair(
                                np.asarray(limbs)[:n][ix[m]]
                            )
                            rows[f"{side}_{fld}_lo"][m] = lo_c
                            rows[f"{side}_{fld}_hi"][m] = hi_c
                    self.history.append_batch(rows)
            results = _codes_to_results(codes)
            # `x` holds the last references to the kernel's outputs (and,
            # as a handle, to the state before it): letting go of device
            # buffers can take as long as the posting, and is part of it.
            x.clear()
            return results

    def _create_transfers_numpy_fast(
        self, events, ts, keys, dr_slots, cr_slots, host_code
    ) -> np.ndarray:
        """CPU-fallback fast path (models/host_kernel.py) — same contract as
        the device kernel, operating on the host balance mirrors."""
        from tigerbeetle_tpu.models import host_kernel

        timestamp = int(ts[-1])
        codes = host_kernel.validate(
            events, ts, dr_slots, cr_slots, self.acc_ledger, host_code
        )
        pend = (events["flags"].astype(np.uint32) & np.uint32(TransferFlags.PENDING)) != 0
        return self._commit_fast_numpy(
            events, ts, codes, dr_slots, cr_slots,
            events["amount_lo"].astype(np.uint64),
            events["amount_hi"].astype(np.uint64),
            pend, timestamp,
        )

    # ------------------------------------------------------------------
    # serial (exact) path — runs the oracle over lazily-prefetched state

    def _account_by_slot(self, slot: int, bal: Tuple) -> oracle_mod.Account:
        key = self.acc_key[slot]
        return oracle_mod.Account(
            id=int(key["lo"]) | (int(key["hi"]) << 64),
            debits_pending=bal[0],
            debits_posted=bal[1],
            credits_pending=bal[2],
            credits_posted=bal[3],
            user_data_128=int(self.acc_user_data_128_lo[slot])
            | (int(self.acc_user_data_128_hi[slot]) << 64),
            user_data_64=int(self.acc_user_data_64[slot]),
            user_data_32=int(self.acc_user_data_32[slot]),
            ledger=int(self.acc_ledger[slot]),
            code=int(self.acc_code[slot]),
            flags=int(self.acc_flags[slot]),
            timestamp=int(self.acc_timestamp[slot]),
        )

    def _slot_of_id(self, ident: int) -> int:
        keys = pack_keys(
            np.array([ident & U64_MAX], dtype=np.uint64),
            np.array([ident >> 64], dtype=np.uint64),
        )
        slot = self.account_index.lookup_batch(keys)[0]
        return -1 if slot == NOT_FOUND else int(slot)

    def _fetch_account(self, ident: int) -> Optional[oracle_mod.Account]:
        slot = self._slot_of_id(ident)
        if slot < 0:
            return None
        dp, dpo, cp, cpo = self._read_balances(np.array([slot]))
        bal = (
            types.limbs_to_int(dp[0]), types.limbs_to_int(dpo[0]),
            types.limbs_to_int(cp[0]), types.limbs_to_int(cpo[0]),
        )
        return self._account_by_slot(slot, bal)

    def _fetch_transfer(self, ident: int) -> Optional[oracle_mod.Transfer]:
        keys = pack_keys(
            np.array([ident & U64_MAX], dtype=np.uint64),
            np.array([ident >> 64], dtype=np.uint64),
        )
        row = self.transfer_index.lookup_batch(keys)[0]
        if row == NOT_FOUND:
            return None
        rec = self.transfer_log.gather(np.array([row]))[0]
        return oracle_mod.transfer_from_numpy(rec)

    def _preload_accounts(self, orc: Oracle, keys: np.ndarray) -> None:
        """Batch-prefetch accounts by packed keys into the oracle's lazy dict."""
        if len(keys) == 0:
            return
        slots = self.account_index.lookup_batch(keys)
        found = slots != NOT_FOUND
        if not np.any(found):
            return
        s = slots[found].astype(np.int64)
        s_unique = np.unique(s)
        dp, dpo, cp, cpo = self._read_balances(s_unique)
        for i, slot in enumerate(s_unique):
            bal = (
                types.limbs_to_int(dp[i]), types.limbs_to_int(dpo[i]),
                types.limbs_to_int(cp[i]), types.limbs_to_int(cpo[i]),
            )
            acct = self._account_by_slot(int(slot), bal)
            orc.accounts.preload(acct.id, acct)

    def _make_oracle(self) -> Oracle:
        from tigerbeetle_tpu.lsm.groove import _PostedView

        orc = Oracle()
        orc.accounts = _LazyDict(self._fetch_account)
        orc.transfers = _LazyDict(self._fetch_transfer)
        # Batch-scoped views over the durable grooves: oracle writes land
        # in overlays (rollback-able), reads fall through; the serial
        # paths drain them into the grooves after the batch commits.
        orc.posted = _PostedView(self.posted)
        orc.history = []
        orc.prepare_timestamp = self.prepare_timestamp
        orc.commit_timestamp = self.commit_timestamp
        return orc

    def _drain_oracle_grooves(self, orc: Oracle) -> None:
        orc.posted.drain()
        if orc.history:
            from tigerbeetle_tpu.lsm.groove import HISTORY_DTYPE

            rows = np.zeros(len(orc.history), dtype=HISTORY_DTYPE)
            for i, r in enumerate(orc.history):
                rec = rows[i]
                rec["timestamp"] = r.timestamp
                for side in ("dr", "cr"):
                    for f in (
                        "account_id",
                        "debits_pending", "debits_posted",
                        "credits_pending", "credits_posted",
                    ):
                        v = getattr(r, f"{side}_{f}")
                        rec[f"{side}_{f}_lo"] = v & U64_MAX
                        rec[f"{side}_{f}_hi"] = v >> 64
            self.history.append_batch(rows)

    def _writeback_accounts(self, orc: Oracle) -> None:
        ids = list(dict.keys(orc.accounts))
        if not ids:
            return
        keys = pack_keys(
            np.array([i & U64_MAX for i in ids], dtype=np.uint64),
            np.array([i >> 64 for i in ids], dtype=np.uint64),
        )
        slots = self.account_index.lookup_batch(keys)
        assert not np.any(slots == NOT_FOUND), "serial path cannot touch unknown accounts"
        dps, dpos, cps, cpos = [], [], [], []
        for ident in ids:
            a = dict.__getitem__(orc.accounts, ident)
            dps.append(types.int_to_limbs(a.debits_pending))
            dpos.append(types.int_to_limbs(a.debits_posted))
            cps.append(types.int_to_limbs(a.credits_pending))
            cpos.append(types.int_to_limbs(a.credits_posted))
        self._write_balances(
            slots.astype(np.int32),
            np.stack(dps), np.stack(dpos), np.stack(cps), np.stack(cpos),
        )

    def _create_transfers_serial(self, events: np.ndarray, timestamp: int) -> np.ndarray:
        # The oracle reads (and its writeback writes) the whole store
        # tier: the async stage must be idle.
        self.store_barrier()
        with tracer.span("sm.ct.serial"):
            return self._create_transfers_oracle(events, timestamp)

    def _create_transfers_oracle(self, events: np.ndarray, timestamp: int) -> np.ndarray:
        orc = self._make_oracle()
        # Prefetch round 1: dr/cr accounts, existing transfers by event id
        # and by pending_id (reference prefetch, state_machine.zig:560-655).
        acct_keys = np.concatenate([
            pack_keys(events["debit_account_id_lo"], events["debit_account_id_hi"]),
            pack_keys(events["credit_account_id_lo"], events["credit_account_id_hi"]),
        ])
        xfer_keys = np.concatenate([
            pack_keys(events["id_lo"], events["id_hi"]),
            pack_keys(events["pending_id_lo"], events["pending_id_hi"]),
        ])
        rows = self.transfer_index.lookup_batch(xfer_keys)
        found_rows = np.unique(rows[rows != NOT_FOUND])
        pend_acct_keys = np.zeros(0, dtype=acct_keys.dtype)
        # The batch's marks (_mark_balances): the events' accounts, and a
        # post/void's pending transfer's as the store holds it.
        marked = [acct_keys]
        if len(found_rows):
            recs = self.transfer_log.gather(found_rows)
            prow = rows[len(events):]
            hit = ((events["flags"] & _PV_FLAGS) != 0) & (prow != NOT_FOUND)
            if hit.any():
                prec = recs[np.searchsorted(found_rows, prow[hit])]
                marked += [
                    pack_keys(prec["debit_account_id_lo"], prec["debit_account_id_hi"]),
                    pack_keys(prec["credit_account_id_lo"], prec["credit_account_id_hi"]),
                ]
            for rec in recs:
                orc.transfers.preload(
                    types.u128_of(rec, "id"), oracle_mod.transfer_from_numpy(rec)
                )
            # Prefetch round 2: accounts referenced by prefetched (pending)
            # transfers — post/void resolves p.debit/credit_account_id.
            pend_acct_keys = np.concatenate([
                pack_keys(recs["debit_account_id_lo"], recs["debit_account_id_hi"]),
                pack_keys(recs["credit_account_id_lo"], recs["credit_account_id_hi"]),
            ])
        self._preload_accounts(orc, np.concatenate([acct_keys, pend_acct_keys]))

        ev_objs = [oracle_mod.transfer_from_numpy(events[i]) for i in range(len(events))]
        pairs = orc.create_transfers(ev_objs, timestamp)

        # Writeback: balances to the device, new transfers to the log,
        # groove overlays into the durable grooves.
        self._writeback_accounts(orc)
        slots = self.account_index.lookup_batch(np.concatenate(marked))
        self._mark_balances(np.where(slots == NOT_FOUND, -1, slots.astype(np.int64)))
        new_ids = [
            i for i in dict.keys(orc.transfers) if i not in orc.transfers.fetched_keys
        ]
        if new_ids:
            new_ts = sorted(new_ids, key=lambda i: dict.__getitem__(orc.transfers, i).timestamp)
            recs = np.concatenate([
                np.atleast_1d(oracle_mod.transfer_to_numpy(dict.__getitem__(orc.transfers, i)))
                for i in new_ts
            ])
            self._store_new_transfers(recs)
        self._drain_oracle_grooves(orc)
        self.commit_timestamp = orc.commit_timestamp
        return _results_array(pairs)

    def _create_accounts_serial(self, events: np.ndarray, timestamp: int) -> np.ndarray:
        self.store_barrier()
        orc = self._make_oracle()
        self._preload_accounts(orc, pack_keys(events["id_lo"], events["id_hi"]))
        ev_objs = [oracle_mod.account_from_numpy(events[i]) for i in range(len(events))]
        pairs = orc.create_accounts(ev_objs, timestamp)

        new_ids = [
            i for i in dict.keys(orc.accounts) if i not in orc.accounts.fetched_keys
        ]
        if new_ids:
            new_sorted = sorted(
                new_ids, key=lambda i: dict.__getitem__(orc.accounts, i).timestamp
            )
            k = len(new_sorted)
            if self.account_count + k > self.config.accounts_max:
                raise RuntimeError("accounts table full (accounts_max exceeded)")
            slots = np.arange(self.account_count, self.account_count + k, dtype=np.int64)
            ledgers = np.zeros(k, dtype=np.uint32)
            aflags = np.zeros(k, dtype=np.uint32)
            lo = np.zeros(k, dtype=np.uint64)
            hi = np.zeros(k, dtype=np.uint64)
            for j, ident in enumerate(new_sorted):
                a = dict.__getitem__(orc.accounts, ident)
                slot = int(slots[j])
                lo[j] = a.id & U64_MAX
                hi[j] = a.id >> 64
                self.acc_user_data_128_lo[slot] = a.user_data_128 & U64_MAX
                self.acc_user_data_128_hi[slot] = a.user_data_128 >> 64
                self.acc_user_data_64[slot] = a.user_data_64
                self.acc_user_data_32[slot] = a.user_data_32
                self.acc_ledger[slot] = a.ledger
                self.acc_code[slot] = a.code
                self.acc_flags[slot] = a.flags
                self.acc_timestamp[slot] = a.timestamp
                ledgers[j] = a.ledger
                aflags[j] = a.flags
            keys = pack_keys(lo, hi)
            self.acc_key[slots] = keys
            self._log_new_accounts(self.account_count, self.account_count + k)
            self.account_count += k
            self.account_index.insert_batch(keys, slots.astype(np.uint32))
            self._register_accounts(
                slots.astype(np.int32), ledgers, aflags, np.ones(k, dtype=bool)
            )
        # Existing accounts are never mutated by create_accounts; only new
        # ones appear — nothing else to write back.
        self.commit_timestamp = orc.commit_timestamp
        return _results_array(pairs)

    # ------------------------------------------------------------------
    # read operations

    def lookup_accounts(self, ids_lo: np.ndarray, ids_hi: np.ndarray) -> np.ndarray:
        keys = pack_keys(
            np.asarray(ids_lo, dtype=np.uint64), np.asarray(ids_hi, dtype=np.uint64)
        )
        slots = self.account_index.lookup_batch(keys)
        found = slots != NOT_FOUND
        s = slots[found].astype(np.int64)
        return self._accounts_at(s)

    def _accounts_at(self, s: np.ndarray) -> np.ndarray:
        """Pack wire ACCOUNT records for an array of slots."""
        out = np.zeros(len(s), dtype=types.ACCOUNT_DTYPE)
        if len(s) == 0:
            return out
        dp, dpo, cp, cpo = self._read_balances(s)
        dp_lo, dp_hi = types.limbs_to_u64_pair(dp)
        dpo_lo, dpo_hi = types.limbs_to_u64_pair(dpo)
        cp_lo, cp_hi = types.limbs_to_u64_pair(cp)
        cpo_lo, cpo_hi = types.limbs_to_u64_pair(cpo)
        out["id_lo"] = self.acc_key["lo"][s]
        out["id_hi"] = self.acc_key["hi"][s]
        out["debits_pending_lo"], out["debits_pending_hi"] = dp_lo, dp_hi
        out["debits_posted_lo"], out["debits_posted_hi"] = dpo_lo, dpo_hi
        out["credits_pending_lo"], out["credits_pending_hi"] = cp_lo, cp_hi
        out["credits_posted_lo"], out["credits_posted_hi"] = cpo_lo, cpo_hi
        out["user_data_128_lo"] = self.acc_user_data_128_lo[s]
        out["user_data_128_hi"] = self.acc_user_data_128_hi[s]
        out["user_data_64"] = self.acc_user_data_64[s]
        out["user_data_32"] = self.acc_user_data_32[s]
        out["ledger"] = self.acc_ledger[s]
        out["code"] = self.acc_code[s]
        out["flags"] = self.acc_flags[s]
        out["timestamp"] = self.acc_timestamp[s]
        return out

    def query_transfers(self, f: np.void) -> np.ndarray:
        """Multi-predicate equality query over transfers via the scan
        engine (reference ScanBuilder range scans per index + boolean
        merge, scan_builder.zig:454, scan_merge.zig:252): nonzero filter
        fields become predicates over the combined query index (field
        tags) and the exact-key account index (v2 debit/credit
        predicates), the planner orders them by fence-estimated
        cardinality, the cheapest drives a galloping probe of the rest
        (lsm/scan.ScanBuilder), and the gathered rows are re-verified
        exactly (fold56 collisions and account side-blindness
        over-select, never mis-answer). The sm.query.* spans feed the
        gated query_p50_ms/query_p99_ms lifecycle keys."""
        from tigerbeetle_tpu.lsm import scan

        with tracer.span("sm.query"):
            return self._query_transfers_inner(f, scan)

    def _query_transfers_inner(self, f: np.void, scan) -> np.ndarray:
        self.store_barrier()
        names = f.dtype.names
        ud128_lo = int(f["user_data_128_lo"])
        ud128_hi = int(f["user_data_128_hi"])
        ud64 = int(f["user_data_64"])
        ud32 = int(f["user_data_32"])
        ledger = int(f["ledger"])
        code = int(f["code"])
        limit = int(f["limit"])
        flags = int(f["flags"])
        # v2 filter shape (size-discriminated at decode): account-id
        # equality predicates, absent fields read as 0 (= unset).
        dr_lo = int(f["debit_account_id_lo"]) if "debit_account_id_lo" in names else 0
        dr_hi = int(f["debit_account_id_hi"]) if "debit_account_id_hi" in names else 0
        cr_lo = int(f["credit_account_id_lo"]) if "credit_account_id_lo" in names else 0
        cr_hi = int(f["credit_account_id_hi"]) if "credit_account_id_hi" in names else 0
        ts_min_raw, ts_max_raw = int(f["timestamp_min"]), int(f["timestamp_max"])
        if not Oracle._query_filter_valid(ts_min_raw, ts_max_raw, limit, flags):
            return np.zeros(0, dtype=types.TRANSFER_DTYPE)
        ts_min = ts_min_raw if ts_min_raw else 1
        ts_max = ts_max_raw if ts_max_raw else U64_MAX - 1

        builder = scan.ScanBuilder(
            self.query_rows, self.account_rows, ts_min, ts_max,
            log_stats=(
                self.transfer_log.count,
                len(self.transfer_log.blocks),
                self.transfer_log.resident_fraction(),
            ),
        )
        if ud128_lo or ud128_hi:
            builder.where_field(scan.TAG_UD128, ud128_lo, ud128_hi)
        if ud64:
            builder.where_field(scan.TAG_UD64, ud64)
        if ud32:
            builder.where_field(scan.TAG_UD32, ud32)
        if ledger:
            builder.where_field(scan.TAG_LEDGER, ledger)
        if code:
            builder.where_field(scan.TAG_CODE, code)
        if dr_lo or dr_hi:
            builder.where_account(dr_lo, dr_hi)
        if cr_lo or cr_hi:
            builder.where_account(cr_lo, cr_hi)

        def verify(t: np.ndarray) -> np.ndarray:
            keep = (t["timestamp"] >= np.uint64(ts_min)) & (
                t["timestamp"] <= np.uint64(ts_max)
            )
            if ud128_lo or ud128_hi:
                keep &= (t["user_data_128_lo"] == np.uint64(ud128_lo)) & (
                    t["user_data_128_hi"] == np.uint64(ud128_hi)
                )
            if ud64:
                keep &= t["user_data_64"] == np.uint64(ud64)
            if ud32:
                keep &= t["user_data_32"] == np.uint32(ud32)
            if ledger:
                keep &= t["ledger"] == np.uint32(ledger)
            if code:
                keep &= t["code"] == np.uint16(code)
            if dr_lo or dr_hi:
                keep &= (t["debit_account_id_lo"] == np.uint64(dr_lo)) & (
                    t["debit_account_id_hi"] == np.uint64(dr_hi)
                )
            if cr_lo or cr_hi:
                keep &= (t["credit_account_id_lo"] == np.uint64(cr_lo)) & (
                    t["credit_account_id_hi"] == np.uint64(cr_hi)
                )
            return keep

        if not builder._preds:
            # No equality predicate: bounded walk of the timestamp-ordered
            # object log (newest-first under REVERSED), stopping at limit.
            t = self._log_window(ts_min, ts_max, limit, bool(flags & 1))
            ix = np.nonzero(verify(t))[0]  # row order IS timestamp order
            if flags & 1:
                ix = ix[::-1]
            return t[ix[:limit]]

        # The engine: fence-estimated plan, driver scan, galloping
        # probes. `rows` is an ascending candidate SUPERSET; the chunked
        # gather below re-verifies every predicate exactly.
        with tracer.span("sm.query.plan"):
            plan = builder.plan()
        with tracer.span("sm.query.scan"):
            cand = np.ascontiguousarray(
                builder._materialize(plan[0]), dtype=np.uint32
            )
        with tracer.span("sm.query.probe"):
            # Probes exist only to shrink the gather: each runs while
            # its index walk costs less than the block reads + row
            # copies it saves (builder._probe_pays, buffer-aware), and
            # verify() re-checks every predicate exactly either way.
            for p in plan[1:]:
                if not builder._probe_pays(p, len(cand)):
                    break
                hit = np.zeros(len(cand), dtype=np.uint8)
                builder._probe(p, cand, hit)
                cand = cand[hit.view(bool)]
        rows = cand

        # Limit-aware chunked gather: candidates are timestamp-ordered, so
        # walk them from the answering end in chunks, verify, and stop as
        # soon as `limit` rows survive — a limit-100 query gathers ~100
        # candidates' blocks, not the full candidate set (whose scattered
        # rows could touch most of the log).
        reversed_ = bool(flags & 1)
        chunk = max(256, 4 * limit)
        parts: list = []
        got = 0
        pos = len(rows) if reversed_ else 0
        with tracer.span("sm.query.gather"):
            while got < limit and (pos > 0 if reversed_ else pos < len(rows)):
                if reversed_:
                    lo_ix = max(0, pos - chunk)
                    sel_rows = rows[lo_ix:pos]
                    pos = lo_ix
                else:
                    sel_rows = rows[pos : pos + chunk]
                    pos += chunk
                t = self.transfer_log.gather(sel_rows)
                hit = t[verify(t)]
                if len(hit):
                    parts.append(hit)
                    got += len(hit)
        if not parts:
            return np.zeros(0, dtype=types.TRANSFER_DTYPE)
        if reversed_:
            out = np.concatenate(parts[::-1])
            return out[::-1][:limit]
        out = np.concatenate(parts)
        return out[:limit]

    def _log_window(
        self, ts_min: int, ts_max: int, limit: int, reversed_: bool
    ) -> np.ndarray:
        """≤limit log records inside [ts_min, ts_max], walking whole blocks
        lazily from the matching end (timestamps are monotone with row) —
        a limit-10 newest-first query touches one block, never the log."""
        log = self.transfer_log
        count = log.count
        if count == 0:
            return np.zeros(0, dtype=types.TRANSFER_DTYPE)
        rpb = log.records_per_block
        out: list = []
        got = 0
        blocks = range((count - 1) // rpb, -1, -1) if reversed_ else range(
            0, (count - 1) // rpb + 1
        )
        for b in blocks:
            base = b * rpb
            for _base2, recs in log.scan_range(base, min(base + rpb, count)):
                sel = recs[
                    (recs["timestamp"] >= np.uint64(ts_min))
                    & (recs["timestamp"] <= np.uint64(ts_max))
                ]
                if len(sel):
                    out.append(sel)
                    got += len(sel)
            if got >= limit:
                break
        if not out:
            return np.zeros(0, dtype=types.TRANSFER_DTYPE)
        # Ascending row order either way (the caller applies limit and
        # direction); a superset is fine — it only re-verifies and trims.
        return np.concatenate(out[::-1] if reversed_ else out)

    def query_accounts(self, f: np.void) -> np.ndarray:
        """Equality query over accounts. The accounts table is bounded
        (accounts_max) and RAM/device-resident, so the TPU-first answer is
        a vectorized column filter — no index trees needed (the reference
        builds 5 LSM index trees because its account table is
        disk-resident; ours is the batch-parallel axis)."""
        self.flush_deferred()
        limit = int(f["limit"])
        flags = int(f["flags"])
        ts_min_raw, ts_max_raw = int(f["timestamp_min"]), int(f["timestamp_max"])
        if not Oracle._query_filter_valid(ts_min_raw, ts_max_raw, limit, flags):
            return np.zeros(0, dtype=types.ACCOUNT_DTYPE)
        ts_min = ts_min_raw if ts_min_raw else 1
        ts_max = ts_max_raw if ts_max_raw else U64_MAX - 1
        n = self.account_count
        keep = (self.acc_timestamp[:n] >= np.uint64(ts_min)) & (
            self.acc_timestamp[:n] <= np.uint64(ts_max)
        )
        if int(f["user_data_128_lo"]) or int(f["user_data_128_hi"]):
            keep &= (
                self.acc_user_data_128_lo[:n] == f["user_data_128_lo"]
            ) & (self.acc_user_data_128_hi[:n] == f["user_data_128_hi"])
        if int(f["user_data_64"]):
            keep &= self.acc_user_data_64[:n] == f["user_data_64"]
        if int(f["user_data_32"]):
            keep &= self.acc_user_data_32[:n] == f["user_data_32"]
        if int(f["ledger"]):
            keep &= self.acc_ledger[:n] == f["ledger"]
        if int(f["code"]):
            keep &= self.acc_code[:n] == f["code"]
        s = np.nonzero(keep)[0]  # slot order IS creation-timestamp order
        if flags & 1:
            s = s[::-1]
        return self._accounts_at(s[:limit].astype(np.int64))

    def lookup_transfers(self, ids_lo: np.ndarray, ids_hi: np.ndarray) -> np.ndarray:
        self.store_barrier()
        keys = pack_keys(
            np.asarray(ids_lo, dtype=np.uint64), np.asarray(ids_hi, dtype=np.uint64)
        )
        rows = self.transfer_index.lookup_batch(keys)
        found = rows != NOT_FOUND
        return self.transfer_log.gather(rows[found])

    def _account_records(self, account_id: int) -> np.ndarray:
        """All transfers touching the account, in commit (timestamp) order —
        an account-index range read + gather, O(account's transfers), not
        O(history) (reference ScanTree over the secondary index,
        scan_tree.zig:31)."""
        self.store_barrier()
        key = pack_keys(
            np.array([account_id & U64_MAX], dtype=np.uint64),
            np.array([account_id >> 64], dtype=np.uint64),
        )[0]
        rows = self.account_rows.lookup_range(key)
        return self.transfer_log.gather(rows)

    def get_account_transfers(
        self,
        account_id: int,
        timestamp_min: int = 0,
        timestamp_max: int = 0,
        limit: int = 8190,
        flags: int = 0x3,
    ) -> np.ndarray:
        from tigerbeetle_tpu.flags import AccountFilterFlags as FF

        if not Oracle._filter_valid(account_id, timestamp_min, timestamp_max, limit, flags):
            return np.zeros(0, dtype=types.TRANSFER_DTYPE)
        t = self._account_records(account_id)
        ts_min = np.uint64(timestamp_min if timestamp_min else 1)
        ts_max = np.uint64(timestamp_max if timestamp_max else U64_MAX - 1)
        lo = np.uint64(account_id & U64_MAX)
        hi = np.uint64(account_id >> 64)
        mask = (t["timestamp"] >= ts_min) & (t["timestamp"] <= ts_max)
        m_dr = (t["debit_account_id_lo"] == lo) & (t["debit_account_id_hi"] == hi)
        m_cr = (t["credit_account_id_lo"] == lo) & (t["credit_account_id_hi"] == hi)
        side = np.zeros(len(t), dtype=bool)
        if flags & FF.DEBITS:
            side |= m_dr
        if flags & FF.CREDITS:
            side |= m_cr
        rows = np.nonzero(mask & side)[0]
        if flags & FF.REVERSED:
            rows = rows[::-1]
        return t[rows[:limit]]

    def get_account_history(
        self,
        account_id: int,
        timestamp_min: int = 0,
        timestamp_max: int = 0,
        limit: int = 8190,
        flags: int = 0x3,
    ) -> List[Tuple[int, int, int, int, int]]:
        """Balance history of a HISTORY-flagged account: an index
        range-read over the history groove + vectorized side selection —
        no oracle join, no per-row Python (reference ScanLookup over the
        account_history groove, state_machine.zig get_account_history)."""
        from tigerbeetle_tpu.flags import AccountFilterFlags as FF

        if not Oracle._filter_valid(account_id, timestamp_min, timestamp_max, limit, flags):
            return []
        slot = self._slot_of_id(account_id)
        if slot < 0 or not (int(self.acc_flags[slot]) & int(AccountFlags.HISTORY)):
            return []
        self.store_barrier()  # history groove rows may still be queued
        recs = self.history.account_rows(account_id)
        if len(recs) == 0:
            return []
        lo = np.uint64(account_id & U64_MAX)
        hi = np.uint64(account_id >> 64)
        ts_min = np.uint64(timestamp_min if timestamp_min else 1)
        ts_max = np.uint64(timestamp_max if timestamp_max else U64_MAX - 1)
        keep = (recs["timestamp"] >= ts_min) & (recs["timestamp"] <= ts_max)
        # Side filter (oracle semantics: DEBITS selects rows where this
        # account is the transfer's debit side — which is exactly the rows
        # whose dr side carries it, and symmetrically for CREDITS).
        is_dr = (recs["dr_account_id_lo"] == lo) & (recs["dr_account_id_hi"] == hi)
        is_cr = (recs["cr_account_id_lo"] == lo) & (recs["cr_account_id_hi"] == hi)
        side = np.zeros(len(recs), dtype=bool)
        if flags & FF.DEBITS:
            side |= is_dr
        if flags & FF.CREDITS:
            side |= is_cr
        ix = np.nonzero(keep & side)[0]
        if flags & FF.REVERSED:
            ix = ix[::-1]
        ix = ix[:limit]
        r = recs[ix]
        use_dr = is_dr[ix]

        def u128(field):
            l = np.where(use_dr, r[f"dr_{field}_lo"], r[f"cr_{field}_lo"])
            h = np.where(use_dr, r[f"dr_{field}_hi"], r[f"cr_{field}_hi"])
            return l, h

        cols = [u128(f) for f in (
            "debits_pending", "debits_posted", "credits_pending", "credits_posted"
        )]
        return [
            (
                int(r["timestamp"][j]),
                *(int(l[j]) | (int(h[j]) << 64) for l, h in cols),
            )
            for j in range(len(r))
        ]
