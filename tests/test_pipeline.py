"""Unit tests for the overlapped commit pipeline pieces: the
CommitExecutor stage (vsr/pipeline.py), the coalesced ReplyBuilder, the
vectorized header parse, and the split-phase (double-buffered) device
dispatch in the state machine."""

import threading
import time

import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.vsr import header as hdr
from tigerbeetle_tpu.vsr.header import Command, Header, Message, ReplyBuilder
from tigerbeetle_tpu.vsr.pipeline import CommitExecutor, StoreExecutor
from tigerbeetle_tpu.vsr.replica import _parse_headers


def _wait(cond, timeout=5.0):
    deadline = time.time() + timeout
    while not cond():
        assert time.time() < deadline, "condition not reached"
        time.sleep(0.002)


class TestCommitExecutor:
    def _posts(self):
        posts = []
        return posts, posts.append

    def test_in_order_processing_and_completion(self):
        done_order = []
        posts, post = self._posts()
        ex = None

        def process(job):
            done_order.append(job["op"])
            ex.complete(job)
            return None, [], True

        ex = CommitExecutor(process=process, post=post)
        for op in range(1, 9):
            ex.submit({"op": op})
        ex.drain()
        assert done_order == list(range(1, 9))
        out = []
        while True:
            j = ex.pop_done()
            if j is None:
                break
            out.append(j["op"])
        assert out == list(range(1, 9))
        ex.stop()

    def test_park_requeues_unprocessed_jobs(self):
        posts, post = self._posts()
        ex = None

        def process(job):
            if job["op"] == 2:
                job["fault"] = "boom"
                return job, [], False  # park: op 3+ must never run
            job["ran"] = True
            ex.complete(job)
            return None, [], True

        ex = CommitExecutor(process=process, post=post)
        for op in (1, 2, 3, 4):
            ex.submit({"op": op})
        ex.drain()
        assert ex.parked
        got = []
        while True:
            j = ex.pop_done()
            if j is None:
                break
            got.append(j)
        assert [j["op"] for j in got] == [1, 2]
        leftovers = ex.reset()
        assert [j["op"] for j in leftovers] == [3, 4]
        assert not ex.parked
        assert all("ran" not in j for j in leftovers)
        ex.stop()

    def test_park_leftovers_precede_rest_of_run(self):
        """A fault while settling a HELD op pushes the current (never
        executed) job back ahead of the remainder of the run."""
        posts, post = self._posts()
        ex = None
        state = {"held": None}

        def process(job):
            held, state["held"] = state["held"], None
            if held is not None:
                held["fault"] = "boom"
                return held, [job], False  # current job back to the head
            state["held"] = job
            return None, [], True

        ex = CommitExecutor(process=process, post=post)
        for op in (1, 2, 3):
            ex.submit({"op": op})
        ex.drain()
        assert ex.parked
        published = ex.pop_done()
        assert published["op"] == 1 and published["fault"] == "boom"
        assert [j["op"] for j in ex.reset()] == [2, 3]
        ex.stop()

    def test_flush_completes_held_job(self):
        held = {}
        posts, post = self._posts()
        ex = None

        def process(job):
            held["job"] = job
            return None, [], True  # hold (dispatch-window device shape)

        def flush():
            j = held.pop("job")
            j["flushed"] = True
            ex.complete(j)
            return None, [], True

        ex = CommitExecutor(process=process, post=post, flush=flush)
        ex.submit({"op": 1})
        ex.drain()
        j = ex.pop_done()
        assert j is not None and j["flushed"]
        ex.stop()

    def test_flush_fault_parks_with_leftovers_requeued(self):
        """A mid-window fault during flush: the faulted job publishes,
        the unexecuted window jobs come back as leftovers at the queue
        head, and the stage parks until reset()."""
        held = []
        posts, post = self._posts()
        ex = None

        def process(job):
            held.append(job)
            return None, [], True  # every job held in the window

        def flush():
            if len(held) < 3:
                # The queue drained mid-submission: keep holding until
                # the whole window is resident (deterministic fault
                # point regardless of worker scheduling).
                return None, [], True
            bad, rest = held[0], held[1:]
            held.clear()
            bad["fault"] = "boom"
            return bad, rest, False

        ex = CommitExecutor(process=process, post=post, flush=flush)
        for op in (1, 2, 3):
            ex.submit({"op": op})
        ex.drain()
        assert ex.parked
        pub = ex.pop_done()
        assert pub is not None and pub["op"] == 1 and pub["fault"] == "boom"
        leftovers = ex.reset()
        assert [j["op"] for j in leftovers] == [2, 3]
        ex.stop()

    def test_poison_on_unexpected_exception(self):
        posts = []
        event = threading.Event()

        def post(cb):
            posts.append(cb)
            event.set()

        def process(job):
            raise ValueError("unexpected")

        ex = CommitExecutor(process=process, post=post)
        ex.submit({"op": 1})
        assert event.wait(5.0)
        with pytest.raises(RuntimeError, match="commit executor stage failed"):
            posts[0]()


class TestStoreExecutor:
    """Unit tests for the async LSM store stage (vsr/pipeline.py
    StoreExecutor): strict in-order drain, the pending-write-buffer
    snapshot, park/resume on faults, and submit backpressure."""

    def test_in_order_drain_and_buffer_visibility(self):
        applied = []

        def process(job):
            # The in-flight job must still be visible as an unapplied
            # store until its store phase lands.
            assert job["store"] in se.unapplied_stores()
            applied.append(job["op"])
            job["stored"] = True
            assert job["store"] not in se.unapplied_stores()
            return None

        se = StoreExecutor(process=process, post=lambda cb: cb())
        for op in range(1, 9):
            se.submit({"op": op, "store": (f"recs{op}", None)})
        se.drain()
        assert applied == list(range(1, 9))
        assert se.unapplied_stores() == []
        assert se.idle
        se.stop()

    def test_park_resume_preserves_order(self):
        applied = []
        notified = threading.Event()
        fail_once = [True]

        def process(job):
            if job["op"] == 2 and fail_once[0]:
                fail_once[0] = False
                job["fault"] = IOError("corrupt block")
                return job
            applied.append(job["op"])
            job["stored"] = True
            return None

        posts = []

        def post(cb):
            posts.append(cb)
            notified.set()

        se = StoreExecutor(process=process, post=post, notify=lambda: None)
        for op in (1, 2, 3, 4):
            se.submit({"op": op, "store": ((op,), None)})
        assert notified.wait(5.0)
        _wait(lambda: se.parked)
        assert applied == [1]
        assert isinstance(se.fault, IOError)
        # Jobs 3, 4 are still queued (and still in the write buffer).
        assert [s for s, _ in se.unapplied_stores()] == [(3,), (4,)]
        faulted = se.pop_done()
        assert faulted["op"] == 2
        se.resume(faulted)  # repaired: back at the queue head
        se.drain()
        assert applied == [1, 2, 3, 4]
        se.stop()

    def test_exact_batch_that_reads_no_store_commits_past_a_parked_stage(self):
        """The stage parks on a beat's fault (the job's rows have landed by
        then) while the commit path goes on: a linked-chain exact batch
        takes no barrier, so it commits, hands its rows on and queues
        behind the park as a fast batch does; a batch with a post in it
        takes the barrier and meets the fault there, unexecuted. After the
        resume the jobs drain in op order and every id reads back."""
        from tigerbeetle_tpu.constants import TEST_MIN
        from tigerbeetle_tpu.flags import TransferFlags
        from tigerbeetle_tpu.io.grid import GridReadFault
        from tigerbeetle_tpu.models.state_machine import StateMachine

        sm = StateMachine(TEST_MIN, backend="jax")
        acc = np.zeros(4, dtype=types.ACCOUNT_DTYPE)
        acc["id_lo"] = np.arange(1, 5)
        acc["ledger"] = 1
        acc["code"] = 10
        assert len(sm.create_accounts(acc, timestamp=4)) == 0
        applied, fail_once = [], [True]

        def process(job):  # replica._store_process: the rows, then the beat
            if not job.get("stored"):
                recs, ts = job["store"]
                sm._store_new_transfers(recs, ts=ts, add_bloom=False)
                job["stored"] = True
            if job["op"] == 1 and fail_once[0]:
                fail_once[0] = False
                job["fault"] = GridReadFault(7, None)
                return job
            sm.compact_beat(flush=False)
            applied.append(job["op"])
            return None

        se = StoreExecutor(process=process, post=lambda cb: None, notify=lambda: None)
        sm.attach_store_stage(se)

        def chains(op, post_of=0):
            t = np.zeros(6, dtype=types.TRANSFER_DTYPE)
            t["id_lo"] = 10 * op + np.arange(6)
            t["debit_account_id_lo"] = [1, 2, 3, 1, 2, 3]
            t["credit_account_id_lo"] = [2, 3, 4, 4, 1, 2]
            t["amount_lo"] = op
            t["ledger"] = 1
            t["code"] = 7
            t["flags"] = [1, 1, 0, 1, 1, 0]  # linked, linked, closed
            if post_of:
                t["flags"][5] = int(TransferFlags.POST_PENDING_TRANSFER)
                t["pending_id_lo"][5] = post_of
                t["amount_lo"][5] = 0  # the pending's own
            return t

        def commit(op, events):  # execute, then replica._finish_commit's submit
            results = sm.create_transfers(events, timestamp=100 * op)
            se.submit({"op": op, "store": sm.take_deferred_store()})
            return results

        try:
            first = chains(1)
            first["flags"][5] = int(TransferFlags.PENDING)
            assert len(commit(1, first)) == 0
            _wait(lambda: se.parked)
            assert len(commit(2, chains(2))) == 0 and len(commit(3, chains(3))) == 0  # no barrier, no fault
            assert sm.stats["exact_batches"] == 3 and applied == []
            assert [int(r["id_lo"][0]) for r, _ts in se.unapplied_stores()] == [20, 30]
            with pytest.raises(GridReadFault):  # the post reads the store: today's route
                sm.create_transfers(chains(4, post_of=15), timestamp=400)
            assert sm.stats["exact_batches"] == 3 and not sm.stats["serial_batches"]
            se.resume(se.pop_done())
            assert len(commit(4, chains(4, post_of=15))) == 0  # drains 1, 2, 3, then stores inline
            assert applied == [1, 2, 3] and se.unapplied_stores() == []
            ids = np.concatenate([chains(op)["id_lo"] for op in (1, 2, 3, 4)])
            got = sm.lookup_transfers(ids, np.zeros(len(ids), np.uint64))
            assert got["id_lo"].tolist() == ids.tolist()
            assert got["timestamp"].tolist() == sorted(got["timestamp"].tolist())
        finally:
            se.stop()

    def test_submit_backpressure_bounds_queue(self):
        release = threading.Event()

        def process(job):
            release.wait(10.0)
            return None

        se = StoreExecutor(process=process, post=lambda cb: cb(), depth_max=2)
        se.submit({"op": 1})  # picked up by the worker (blocks in process)
        _wait(lambda: not se.idle)
        se.submit({"op": 2})
        se.submit({"op": 3})  # queue now at depth_max

        blocked = threading.Event()

        def producer():
            se.submit({"op": 4})  # must wait for a slot
            blocked.set()

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        assert not blocked.wait(0.2), "submit must block at depth_max"
        release.set()
        assert blocked.wait(5.0)
        se.drain()
        se.stop()

    def test_reset_discards_queue_and_waits_for_inflight(self):
        started = threading.Event()
        release = threading.Event()
        applied = []

        def process(job):
            started.set()
            release.wait(10.0)
            applied.append(job["op"])
            return None

        se = StoreExecutor(process=process, post=lambda cb: cb())
        se.submit({"op": 1, "store": ((1,), None)})
        se.submit({"op": 2, "store": ((2,), None)})
        assert started.wait(5.0)

        def releaser():
            time.sleep(0.05)
            release.set()

        threading.Thread(target=releaser, daemon=True).start()
        out = se.reset()  # waits for op 1, discards op 2
        assert applied == [1]
        assert [j["op"] for j in out] == [2]
        assert se.unapplied_stores() == []
        se.stop()

    def test_poison_on_unexpected_exception(self):
        posts = []
        event = threading.Event()

        def post(cb):
            posts.append(cb)
            event.set()

        def process(job):
            raise ValueError("unexpected")

        se = StoreExecutor(process=process, post=post)
        se.submit({"op": 1})
        assert event.wait(5.0)
        with pytest.raises(RuntimeError, match="store executor stage failed"):
            posts[0]()


class TestReplyBuilder:
    def test_byte_identical_to_per_op_seal(self):
        rb = ReplyBuilder()
        specs = [
            dict(view=3, op=5 + i, timestamp=100 + i, request=2 + i,
                 replica=1, operation=129, cluster=7,
                 client=(1 << 80) | (9 + i), body=b"xy" * i)
            for i in range(5)
        ]
        for s in specs:
            m = rb.build_one(s)
            rh = hdr.make(
                Command.REPLY, s["cluster"], view=s["view"], op=s["op"],
                commit=s["op"], timestamp=s["timestamp"], client=s["client"],
                request=s["request"], replica=s["replica"],
                operation=s["operation"],
            )
            assert m.to_bytes() == Message(rh, s["body"]).seal().to_bytes()
            assert m.verify()

    def test_scratch_reuse_does_not_corrupt_prior_replies(self):
        rb = ReplyBuilder()
        first = rb.build_one(
            dict(view=1, op=9, timestamp=5, request=1, replica=0,
                 operation=128, cluster=0, client=3, body=b"abc")
        )
        rb.build_one(
            dict(view=2, op=10, timestamp=6, request=2, replica=0,
                 operation=129, cluster=0, client=4, body=b"")
        )
        assert first.header["op"] == 9 and first.verify()


class TestParseHeaders:
    def test_vectorized_matches_per_header_parse(self):
        headers = []
        for i in range(5):
            h = hdr.make(
                Command.PREPARE, 3, view=2, op=10 + i, commit=9 + i,
                timestamp=1000 + i, replica=1, operation=129,
            )
            Message(h).seal()
            headers.append(h)
        body = b"".join(h.to_bytes() for h in headers)
        out = _parse_headers(body)
        assert len(out) == 5
        for want, got in zip(headers, out):
            assert got.to_bytes() == want.to_bytes()
            assert got["op"] == want["op"] and got.valid_checksum()
        # Trailing partial header bytes are ignored, as before.
        assert len(_parse_headers(body + b"\x01" * 7)) == 5
        assert _parse_headers(b"") == []


def _small_state_machine():
    from tigerbeetle_tpu.constants import Config
    from tigerbeetle_tpu.models.state_machine import StateMachine

    config = Config(
        name="t", accounts_max=1 << 10, transfers_max=1 << 12,
        lsm_block_size=1 << 12, grid_block_count=1 << 10,
        grid_cache_blocks=16, index_memtable_rows=512,
    )
    return StateMachine(config, backend="jax")


class TestSplitPhaseDispatch:
    """create_transfers_dispatch/finish must be byte-identical to the
    single-phase path, including the bail→serial fallback and the
    id-overlap refusal."""

    def _sm(self):
        sm = _small_state_machine()
        n = 16
        ev = np.zeros(n, dtype=types.ACCOUNT_DTYPE)
        ev["id_lo"] = np.arange(1, n + 1)
        ev["ledger"] = 1
        ev["code"] = 10
        res = sm.create_accounts(ev, timestamp=n)
        assert len(res) == 0
        return sm

    @staticmethod
    def _batch(ids, amount=5):
        ev = np.zeros(len(ids), dtype=types.TRANSFER_DTYPE)
        ev["id_lo"] = ids
        ev["debit_account_id_lo"] = 1
        ev["credit_account_id_lo"] = 2
        ev["amount_lo"] = amount
        ev["ledger"] = 1
        ev["code"] = 7
        return ev

    def test_dispatch_finish_matches_single_phase(self):
        sm_a, sm_b = self._sm(), self._sm()
        ts = 100
        b1 = self._batch(np.arange(100, 104))
        b2 = self._batch(np.arange(200, 204))
        # Single-phase reference.
        ref1 = sm_a.create_transfers(b1, timestamp=ts)
        ref2 = sm_a.create_transfers(b2, timestamp=ts + 10)
        # Split-phase: dispatch both before finishing the first.
        h1 = sm_b.create_transfers_dispatch(b1, ts)
        assert h1 is not None
        h2 = sm_b.create_transfers_dispatch(b2, ts + 10)
        assert h2 is not None
        out1 = sm_b.create_transfers_finish(h1)
        out2 = sm_b.create_transfers_finish(h2)
        assert out1.tobytes() == ref1.tobytes()
        assert out2.tobytes() == ref2.tobytes()
        # Stored state identical: lookups agree.
        la = sm_a.lookup_accounts(np.array([1], np.uint64), np.array([0], np.uint64))
        lb = sm_b.lookup_accounts(np.array([1], np.uint64), np.array([0], np.uint64))
        assert la.tobytes() == lb.tobytes()

    def test_id_overlap_refuses_dispatch_ahead(self):
        sm = self._sm()
        b1 = self._batch(np.arange(300, 310))
        h1 = sm.create_transfers_dispatch(b1, 500)
        assert h1 is not None
        # Overlapping id 305: the dup check cannot see batch 1's store yet.
        b2 = self._batch(np.array([305, 900]))
        assert sm.create_transfers_dispatch(b2, 510) is None
        out1 = sm.create_transfers_finish(h1)
        assert len(out1) == 0  # all OK
        # Single-phase now reports the duplicate.
        out2 = sm.create_transfers(b2, timestamp=510)
        assert len(out2) == 1 and out2[0]["index"] == 0

    def test_stale_gen_refire_fences_later_handles(self):
        """A refire after a chain break mutates state the LATER outstanding
        kernel never observed: finishing it must refire too (gen fenced by
        the earlier refire), and every result must match a serial run."""
        sm, ref = self._sm(), self._sm()
        ts = 700
        b1 = self._batch(np.arange(500, 504))
        b2 = self._batch(np.arange(600, 604))
        h1 = sm.create_transfers_dispatch(b1, ts)
        h2 = sm.create_transfers_dispatch(b2, ts + 10)
        assert h1 is not None and h2 is not None
        # Simulate a chain break discovered before h1's finish (what a
        # device bail does): the breaker restores the state token to its
        # pre-dispatch value and bumps the generation, so h1 refires
        # single-phase from the correct base.
        sm.state = h1["prev_state"]
        sm._state_gen += 1
        out1 = sm.create_transfers_finish(h1)
        out2 = sm.create_transfers_finish(h2)  # must refire, not accept
        ref1 = ref.create_transfers(b1, timestamp=ts)
        ref2 = ref.create_transfers(b2, timestamp=ts + 10)
        assert out1.tobytes() == ref1.tobytes()
        assert out2.tobytes() == ref2.tobytes()
        assert not sm._ct_pending
        la = sm.lookup_accounts(np.array([1], np.uint64), np.array([0], np.uint64))
        lb = ref.lookup_accounts(np.array([1], np.uint64), np.array([0], np.uint64))
        assert la.tobytes() == lb.tobytes()

    def test_abandon_rolls_back_state_token(self):
        sm = self._sm()
        before = np.asarray(sm.state.debits_posted).copy()
        h = sm.create_transfers_dispatch(self._batch(np.arange(400, 404)), 600)
        assert h is not None
        sm.create_transfers_abandon_all()
        after = np.asarray(sm.state.debits_posted)
        assert np.array_equal(before, after)
        # The same batch re-executes cleanly through the single-phase path.
        out = sm.create_transfers(self._batch(np.arange(400, 404)), timestamp=600)
        assert len(out) == 0


class TestDispatchWindow:
    """Depth-N split-phase window (cross-batch commit pipelining): up to
    DISPATCH_WINDOW_MAX outstanding handles, a scratch ring that must not
    corrupt in-flight batches, and a whole-window abandon that restores
    the state token to the oldest live base."""

    _sm = TestSplitPhaseDispatch._sm
    _batch = staticmethod(TestSplitPhaseDispatch._batch)

    @pytest.mark.parametrize("depth", [2, 4, 8])
    def test_deep_window_matches_serial(self, depth):
        """`depth` batches dispatched before the first finish: every
        result and the stored state must be byte-identical to the
        single-phase run. Distinct amounts per batch make scratch-ring
        aliasing (a later dispatch overwriting an in-flight batch's
        staged columns) visible as result/balance divergence."""
        sm, ref = self._sm(), self._sm()
        batches = [
            self._batch(np.arange(1000 + 100 * i, 1000 + 100 * i + 4),
                        amount=1 + i)
            for i in range(depth)
        ]
        handles = []
        for i, b in enumerate(batches):
            h = sm.create_transfers_dispatch(b, 900 + 10 * i)
            assert h is not None, f"batch {i} refused below the window cap"
            handles.append(h)
        outs = [sm.create_transfers_finish(h) for h in handles]
        refs = [
            ref.create_transfers(b, timestamp=900 + 10 * i)
            for i, b in enumerate(batches)
        ]
        for out, r in zip(outs, refs):
            assert out.tobytes() == r.tobytes()
        for ident in (1, 2):
            la = sm.lookup_accounts(
                np.array([ident], np.uint64), np.array([0], np.uint64)
            )
            lb = ref.lookup_accounts(
                np.array([ident], np.uint64), np.array([0], np.uint64)
            )
            assert la.tobytes() == lb.tobytes()

    def test_window_cap_refuses_not_corrupts(self):
        """Dispatch past DISPATCH_WINDOW_MAX refuses (a pipeline stall);
        after finishing one batch the window accepts again."""
        from tigerbeetle_tpu.models.state_machine import DISPATCH_WINDOW_MAX

        sm = self._sm()
        handles = []
        for i in range(DISPATCH_WINDOW_MAX):
            h = sm.create_transfers_dispatch(
                self._batch(np.arange(2000 + 10 * i, 2000 + 10 * i + 2)),
                700 + 10 * i,
            )
            assert h is not None
            handles.append(h)
        full = sm.create_transfers_dispatch(
            self._batch(np.array([3000, 3001])), 900
        )
        assert full is None, "window-full dispatch must refuse"
        out0 = sm.create_transfers_finish(handles[0])
        assert len(out0) == 0
        h = sm.create_transfers_dispatch(
            self._batch(np.array([3000, 3001])), 900
        )
        assert h is not None
        for hh in handles[1:] + [h]:
            assert len(sm.create_transfers_finish(hh)) == 0

    def test_abandon_all_restores_oldest_live_base(self):
        """A whole-window reclaim (grid-repair park) rolls the state
        token back past every dispatched kernel in one step; the same
        batches then re-execute cleanly with identical results."""
        sm, ref = self._sm(), self._sm()
        before = np.asarray(sm.state.debits_posted).copy()
        batches = [
            self._batch(np.arange(4000 + 100 * i, 4000 + 100 * i + 3))
            for i in range(4)
        ]
        for i, b in enumerate(batches):
            assert sm.create_transfers_dispatch(b, 500 + 10 * i) is not None
        sm.create_transfers_abandon_all()
        assert not sm._ct_pending
        assert np.array_equal(before, np.asarray(sm.state.debits_posted))
        for i, b in enumerate(batches):
            out = sm.create_transfers(b, timestamp=500 + 10 * i)
            r = ref.create_transfers(b, timestamp=500 + 10 * i)
            assert out.tobytes() == r.tobytes()

    def test_abandon_all_after_mid_window_bail_keeps_refired_state(self):
        """A gen-fence mid-window (bail refire) makes the remaining
        handles stale: abandon_all must NOT restore a stale base — the
        refire already rebuilt the correct state below it."""
        sm, ref = self._sm(), self._sm()
        b1 = self._batch(np.arange(5000, 5004))
        b2 = self._batch(np.arange(5100, 5104))
        b3 = self._batch(np.arange(5200, 5204))
        h1 = sm.create_transfers_dispatch(b1, 600)
        h2 = sm.create_transfers_dispatch(b2, 610)
        h3 = sm.create_transfers_dispatch(b3, 620)
        assert None not in (h1, h2, h3)
        # Simulate a chain break at h1's finish (what a device bail
        # does): rollback + gen bump, then the refire applies b1 via the
        # single-phase path. h2/h3 are now stale.
        sm.state = h1["prev_state"]
        sm._state_gen += 1
        out1 = sm.create_transfers_finish(h1)  # refires single-phase
        sm.create_transfers_abandon_all()  # h2, h3: stale — no restore
        assert not sm._ct_pending
        ref1 = ref.create_transfers(b1, timestamp=600)
        assert out1.tobytes() == ref1.tobytes()
        # b1's effects must survive the abandon; b2/b3 re-execute clean.
        for i, b in enumerate((b2, b3)):
            out = sm.create_transfers(b, timestamp=610 + 10 * i)
            r = ref.create_transfers(b, timestamp=610 + 10 * i)
            assert out.tobytes() == r.tobytes()
        la = sm.lookup_accounts(np.array([1], np.uint64), np.array([0], np.uint64))
        lb = ref.lookup_accounts(np.array([1], np.uint64), np.array([0], np.uint64))
        assert la.tobytes() == lb.tobytes()


# --- exact-kernel handles in the window ----------------------------------

_LINKED = 1
_PENDING = 2
_POST = 4
_BALANCING_DEBIT = 16
_LIMIT = 2  # AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS
_HISTORY = 8
_EXCEEDS_CREDITS = 54
_EXACT = "create_transfers_exact"
_FAST = "create_transfers_fast"


def _exact_sm():
    """Accounts 1-8 plain, 9-12 may not be overdrawn, 13 keeps its history."""
    sm = _small_state_machine()
    acc = np.zeros(13, dtype=types.ACCOUNT_DTYPE)
    acc["id_lo"] = np.arange(1, 14)
    acc["ledger"] = 1
    acc["code"] = 10
    acc["flags"][8:12] = _LIMIT
    acc["flags"][12] = _HISTORY
    assert len(sm.create_accounts(acc, timestamp=13)) == 0
    return sm


def _transfers(first_id, rows):
    """rows: (debit, credit, amount, flags) each."""
    t = np.zeros(len(rows), dtype=types.TRANSFER_DTYPE)
    t["id_lo"] = first_id + np.arange(len(rows))
    t["ledger"] = 1
    t["code"] = 7
    for i, (dr, cr, amount, flags) in enumerate(rows):
        t["debit_account_id_lo"][i] = dr
        t["credit_account_id_lo"][i] = cr
        t["amount_lo"][i] = amount
        t["flags"][i] = flags
    return t


def _exact_batch(kind: str, k: int) -> np.ndarray:
    """Batch k of its kind; every kind but `fast` takes the exact kernel
    and none reads the store."""
    first = 10_000 + 100 * k
    if kind == "chains":  # chains of three; the second has a link that fails
        rows = []
        for c in range(3):
            a = 1 + (k + c) % 8
            b = 1 + (a % 8)
            rows += [(a, b, 5 + k, _LINKED), (b, a, 0 if c == 1 else 3, _LINKED), (a, b, 2, 0)]
        return _transfers(first, rows)
    if kind == "limits":  # a deposit, then a line of debits that outruns it
        return _transfers(first, [(1, 9, 100, 0)] + [(9, 2, 30 + k, 0)] * 6 + [(2, 9, 40, 0), (9, 3, 35, 0)])
    if kind == "balancing":  # what account 10 holds, and not the amount asked
        return _transfers(first, [(1, 10, 50 + k, 0), (10, 2, 1000, _BALANCING_DEBIT), (10, 3, 7, 0)])
    if kind == "pendings":  # holds against a limit; nothing posts or voids them
        return _transfers(first, [(1, 11, 60, 0), (11, 2, 25, _PENDING), (11, 3, 25, _PENDING), (11, 4, 25, _PENDING)])
    assert kind == "fast"
    return _transfers(first, [(1 + (k + i) % 8, 1 + (k + i + 1) % 8, 1 + k, 0) for i in range(4)])


EXACT_KINDS = ("chains", "limits", "balancing", "pendings")


def _same_ledger(sm, ref, batches) -> None:
    ids = np.arange(1, 14, dtype=np.uint64)
    zeros = np.zeros(len(ids), np.uint64)
    assert sm.lookup_accounts(ids, zeros).tobytes() == ref.lookup_accounts(ids, zeros).tobytes()
    tid = np.concatenate([b["id_lo"] for b in batches])
    tz = np.zeros(len(tid), np.uint64)
    assert sm.lookup_transfers(tid, tz).tobytes() == ref.lookup_transfers(tid, tz).tobytes()
    assert sm.commit_timestamp == ref.commit_timestamp


def _finish(sm, handle):
    """A finish as the commit stage makes it: the batch's deferred rows are
    stored before the next finish defers its own (replica._finish_commit)."""
    out = sm.create_transfers_finish(handle)
    sm.flush_deferred()
    return out


def _count(tracer, name: str) -> int:
    return tracer.snapshot().get(name, {}).get("count", 0)


class TestExactDispatch:
    """The split-phase pair on the exact kernel: a batch of the deferring
    kind (no post/void event, no history account) is dispatched ahead and
    finished by its kind, byte for byte the single-phase path; every other
    exact batch is refused, by a counter that says why."""

    @pytest.mark.parametrize("kind", EXACT_KINDS)
    def test_dispatch_finish_matches_single_phase(self, kind):
        """Two batches of one kind out before the first finish."""
        from tigerbeetle_tpu.models.state_machine import EXACT_DISPATCH_MAX

        assert EXACT_DISPATCH_MAX >= 2
        sm, ref = _exact_sm(), _exact_sm()
        batches = [_exact_batch(kind, k) for k in range(4)]
        outs = []
        for i in (0, 2):
            handles = [sm.create_transfers_dispatch(b, 1000 + 100 * (i + j))
                       for j, b in enumerate(batches[i:i + 2])]
            assert all(h is not None and h["kernel"] == _EXACT for h in handles)
            assert sm.exact_window_full()
            outs += [_finish(sm, h) for h in handles]
        refs = [ref.create_transfers(b, timestamp=1000 + 100 * i) for i, b in enumerate(batches)]
        assert [o.tobytes() for o in outs] == [r.tobytes() for r in refs]
        assert sm.stats["exact_batches"] == ref.stats["exact_batches"] == 4
        assert not sm.stats["bail_batches"] and not sm.stats["serial_batches"]
        if kind == "limits":  # the balance check did refuse, on both paths alike
            assert any((o["result"] == _EXCEEDS_CREDITS).any() for o in outs)
        if kind == "chains":  # and a chain did roll back
            assert all(len(o) == 3 for o in outs)
        _same_ledger(sm, ref, batches)

    def test_more_than_one_sweep_is_counted_at_the_finish(self, traced):
        """The limits line, seven debits deep: shallower than the sweeps'
        budget, so the line walk is not taken and the sweeps settle it,
        more than one of them."""
        sm = _exact_sm()
        h = sm.create_transfers_dispatch(_exact_batch("limits", 0), 1000)
        assert _count(traced, "sm.exact.sweeps") == 0  # nothing taken back yet
        _finish(sm, h)
        assert _count(traced, "sm.exact.sweeps") > 1
        assert _count(traced, "sm.exact.line_steps") == 0
        assert _count(traced, "sm.exact.dispatched_ahead") == 1
        assert _count(traced, "sm.exact.store_deferred") == 1

    def test_the_line_walk_is_counted_at_the_finish(self, traced):
        """One line of 100 balancing debits, each funded by the one before
        it (a level each: deeper than the sweeps' budget): one walk of its
        100 levels settles it, and one sweep confirms it."""
        sm, ref = _exact_sm(), _exact_sm()
        line = _transfers(20_000, [(1, 9, 1000, 0)] + [
            (9 + i % 4, 9 + (i + 1) % 4, 1000, _BALANCING_DEBIT) for i in range(100)
        ])
        h = sm.create_transfers_dispatch(line, 1000)
        assert _count(traced, "sm.exact.line_steps") == 0  # nothing taken back yet
        out = _finish(sm, h)
        assert _count(traced, "sm.exact.sweeps") == 1
        assert _count(traced, "sm.exact.line_steps") == 100
        assert sm.stats.get("bail_batches", 0) == 0
        assert out.tobytes() == ref.create_transfers(line, timestamp=1000).tobytes()
        _same_ledger(sm, ref, [line])

    def test_window_of_fast_and_exact_handles_mixed(self):
        sm, ref = _exact_sm(), _exact_sm()
        kinds = ("fast", "limits", "fast", "chains", "fast")
        batches = [_exact_batch(kind, k) for k, kind in enumerate(kinds)]
        handles = [sm.create_transfers_dispatch(b, 1000 + 100 * i) for i, b in enumerate(batches)]
        assert [h["kernel"] for h in handles] == [_FAST, _EXACT, _FAST, _EXACT, _FAST]
        outs = [_finish(sm, h) for h in handles]
        refs = [ref.create_transfers(b, timestamp=1000 + 100 * i) for i, b in enumerate(batches)]
        assert [o.tobytes() for o in outs] == [r.tobytes() for r in refs]
        assert sm.stats["fast_batches"] == 3 and sm.stats["exact_batches"] == 2
        _same_ledger(sm, ref, batches)

    @pytest.mark.parametrize("why", ["pv", "history", "overlap", "dup", "stored_id", "window_full"])
    def test_refusal_is_counted_and_the_batch_stays_correct(self, traced, why):
        """Each refused batch runs whole at its turn, behind a settled
        window (the barrier, where it takes one, with nothing outstanding),
        and answers as the single-phase path does."""
        sm, ref = _exact_sm(), _exact_sm()
        first, second = _exact_batch("pendings", 0), _exact_batch("limits", 1)
        barrier = sm.store_barrier

        def barrier_behind_a_settled_window():
            assert not sm._ct_pending
            barrier()

        sm.store_barrier = barrier_behind_a_settled_window
        if why == "pv":  # posts a pending of the batch in flight
            refused = _exact_batch("chains", 2)
            refused["flags"][8] = _POST
            refused["pending_id_lo"][8] = first["id_lo"][1]
            refused["amount_lo"][8] = 0
        elif why == "history":
            refused = _transfers(10_200, [(1, 13, 5, 0), (13, 2, 3, 0)])
        elif why == "overlap":  # an id of the batch in flight
            refused = _exact_batch("limits", 2)
            refused[3] = second[3]
        elif why == "dup":
            refused = _exact_batch("chains", 2)
            refused["id_lo"][4] = refused["id_lo"][0]
        else:
            refused = _exact_batch("chains", 2)
        if why == "stored_id":  # an id that is stored by the time it is offered
            ran = sm.create_transfers(first, timestamp=1000)
            refused[2] = first[2]
            handles = [sm.create_transfers_dispatch(second, 1100)]
        else:
            handles = [sm.create_transfers_dispatch(first, 1000),
                       sm.create_transfers_dispatch(second, 1100)]
        assert None not in handles
        assert sm.create_transfers_dispatch(refused, 1200) is None
        refusals = {k[len("sm.ct.dispatch_refused."):]: v["count"]
                    for k, v in traced.snapshot().items() if k.startswith("sm.ct.dispatch_refused.")}
        assert refusals == {why: 1}
        assert _count(traced, "sm.exact.dispatched_ahead") == len(handles)
        outs = [_finish(sm, h) for h in handles]
        outs.append(sm.create_transfers(refused, timestamp=1200))
        batches = [first, second, refused]
        refs = [ref.create_transfers(b, timestamp=1000 + 100 * i) for i, b in enumerate(batches)]
        if why == "stored_id":
            outs.insert(0, ran)
        assert [o.tobytes() for o in outs] == [r.tobytes() for r in refs]
        _same_ledger(sm, ref, batches)

    def test_exact_bail_mid_window_rolls_back_and_later_handles_refire(self, traced):
        """The first of three batches is one line of 100 dependent events
        (each balancing debit funded by the one before it) behind a linked
        pair, so the line walk does not take it and the sweeps alone need
        more than their 64: its kernel bails at the finish, the state
        token goes back to the one it was given, the serial path answers,
        and the two kernels that ran on the revoked token are thrown away
        and refired by `gen`."""
        sm, ref = _exact_sm(), _exact_sm()
        line = _transfers(20_000, [(2, 3, 5, _LINKED), (3, 2, 5, 0), (1, 9, 1000, 0)] + [
            (9 + i % 4, 9 + (i + 1) % 4, 1000, _BALANCING_DEBIT) for i in range(100)
        ])
        batches = [line, _exact_batch("limits", 1), _exact_batch("fast", 2)]
        handles = [sm.create_transfers_dispatch(b, 1000 + 100 * i) for i, b in enumerate(batches)]
        assert [h["kernel"] for h in handles] == [_EXACT, _EXACT, _FAST]
        gen, given = sm._state_gen, handles[0]["prev_state"]
        out0 = _finish(sm, handles[0])
        assert sm.stats["bail_batches"] == 1 and sm._state_gen == gen + 1
        assert given is not sm.state  # the serial path wrote its own balances over it
        outs = [out0] + [_finish(sm, h) for h in handles[1:]]
        assert sm._state_gen == gen + 3 and not sm._ct_pending
        # every device window opened was closed, each under its own kernel
        for kernel, n in ((_EXACT, 3), (_FAST, 2)):  # dispatched ahead, then refired
            assert _count(traced, f"device.{kernel}.dispatches") == n
            assert _count(traced, f"device.step.{kernel}") == n
        refs = [ref.create_transfers(b, timestamp=1000 + 100 * i) for i, b in enumerate(batches)]
        assert [o.tobytes() for o in outs] == [r.tobytes() for r in refs]
        assert len(out0) == 0  # every link of the line moved the 1000 on
        _same_ledger(sm, ref, batches)

    def test_no_dispatch_runs_ahead_of_a_handle_a_bail_revoked(self, traced):
        """The line behind a linked pair bails at the first finish while the
        next batch is still out on the revoked token: a batch offered then
        is refused (`behind_bail`), the handle refires at its finish and the
        refused batch runs after it, byte for byte the single-phase path.
        Dispatched on top of the refire, it would have run before it."""
        sm, ref = _exact_sm(), _exact_sm()
        line = _transfers(20_000, [(2, 3, 5, _LINKED), (3, 2, 5, 0), (1, 9, 1000, 0)] + [
            (9 + i % 4, 9 + (i + 1) % 4, 1000, _BALANCING_DEBIT) for i in range(100)
        ])
        batches = [line, _exact_batch("limits", 1), _exact_batch("limits", 2)]
        handles = [sm.create_transfers_dispatch(b, 1000 + 100 * i)
                   for i, b in enumerate(batches[:2])]
        outs = [_finish(sm, handles[0])]
        assert sm.stats["bail_batches"] == 1
        assert sm.create_transfers_dispatch(batches[2], 1200) is None
        assert _count(traced, "sm.ct.dispatch_refused.behind_bail") == 1
        outs += [_finish(sm, handles[1]), sm.create_transfers(batches[2], timestamp=1200)]
        refs = [ref.create_transfers(b, timestamp=1000 + 100 * i) for i, b in enumerate(batches)]
        assert [o.tobytes() for o in outs] == [r.tobytes() for r in refs]
        _same_ledger(sm, ref, batches)

    def test_abandon_all_closes_exact_windows_under_their_own_name(self, traced):
        sm, ref = _exact_sm(), _exact_sm()
        batches = [_exact_batch("chains", 0), _exact_batch("fast", 1), _exact_batch("limits", 2)]
        before = np.asarray(sm.state.debits_posted).copy()
        for i, b in enumerate(batches):
            assert sm.create_transfers_dispatch(b, 1000 + 100 * i) is not None
        sm.create_transfers_abandon_all()
        assert not sm._ct_pending and not sm.exact_window_full()
        assert np.array_equal(before, np.asarray(sm.state.debits_posted))
        assert _count(traced, f"device.step.{_EXACT}") == 2
        assert _count(traced, f"device.step.{_FAST}") == 1
        outs = [sm.create_transfers(b, timestamp=1000 + 100 * i) for i, b in enumerate(batches)]
        refs = [ref.create_transfers(b, timestamp=1000 + 100 * i) for i, b in enumerate(batches)]
        assert [o.tobytes() for o in outs] == [r.tobytes() for r in refs]
        _same_ledger(sm, ref, batches)
