"""Multi-predicate scan engine (lsm/scan.ScanBuilder) vs brute-force
numpy oracles: intersect/union/probe properties over duplicate keys,
empty predicates, and cross-run boundaries; plan determinism under
predicate reordering; the probe pay-rule pins; the merge-stream cut
regression (uint64 vs float64 searchsorted promotion); the object-log
gather grouping; and the AND-merge against np.intersect1d."""

import os

import numpy as np
import pytest

from tigerbeetle_tpu.io.grid import MemGrid
from tigerbeetle_tpu.lsm import scan
from tigerbeetle_tpu.lsm.log import DurableLog
from tigerbeetle_tpu.lsm.scan import (
    TAG_CODE,
    TAG_LEDGER,
    TAG_UD64,
    Pred,
    ScanBuilder,
    prefix,
)
from tigerbeetle_tpu.lsm.store import KEY_DTYPE, pack_keys
from tigerbeetle_tpu.lsm.tree import DurableIndex, _MergeStream, _mark_seg


def _query_tree(entries, memtable_max=256, flush_every=None):
    """A non-unique composite-key tree filled from (tag, folded, ts, row)
    tuples; `flush_every` forces cross-run boundaries (memtable + many
    level tables) so scans stitch segments across tables."""
    grid = MemGrid(block_count=8192, block_size=4096)
    tree = DurableIndex(grid, unique=False, memtable_max=memtable_max,
                        growth=4)
    step = flush_every or len(entries) or 1
    for i in range(0, len(entries), step):
        part = entries[i : i + step]
        if not len(part):
            continue
        keys = np.empty(len(part), dtype=KEY_DTYPE)
        keys["lo"] = [
            (np.uint64(t) << np.uint64(56)) | np.uint64(f) for t, f, _, _ in part
        ]
        keys["hi"] = [ts for _, _, ts, _ in part]
        vals = np.asarray([r for _, _, _, r in part], dtype=np.uint32)
        order = np.argsort(keys["lo"], kind="stable")
        tree.insert_batch(keys[order], vals[order])
        if flush_every:
            tree.flush_memtable()
    return tree


class TestBooleanMerges:
    def test_intersect_union_property_vs_numpy(self):
        rng = np.random.default_rng(5)
        for trial in range(30):
            k = int(rng.integers(1, 5))
            parts = [
                np.unique(rng.integers(0, 60, rng.integers(0, 40)))
                .astype(np.uint32)
                for _ in range(k)
            ]
            want_and = parts[0]
            for p in parts[1:]:
                want_and = np.intersect1d(want_and, p)
            got_and = scan.intersect_rows(list(parts))
            assert got_and.tolist() == want_and.astype(np.uint32).tolist()
            want_or = np.unique(np.concatenate(parts))
            assert scan.union_rows(list(parts)).tolist() == want_or.tolist()

    def test_empty_operands(self):
        e = np.zeros(0, dtype=np.uint32)
        a = np.array([2, 9], dtype=np.uint32)
        assert scan.intersect_rows([e, a]).tolist() == []
        assert scan.union_rows([e, a]).tolist() == [2, 9]
        assert scan.intersect_rows([]).tolist() == []


class TestMarkSeg:
    def test_ascending_segment_gallop(self):
        cand = np.array([3, 7, 10, 90], dtype=np.uint32)
        hit = np.zeros(4, dtype=np.uint8)
        seg = np.arange(5, 95, dtype=np.uint32)  # ascending → C gallop
        fresh = _mark_seg(cand, seg, hit)
        assert fresh == 3
        assert hit.tolist() == [0, 1, 1, 1]

    def test_non_ascending_segment_searchsorted(self):
        cand = np.array([3, 7, 10, 90], dtype=np.uint32)
        hit = np.zeros(4, dtype=np.uint8)
        seg = np.array([90, 4, 7, 4], dtype=np.uint32)  # merge-tied run
        fresh = _mark_seg(cand, seg, hit)
        assert fresh == 2
        assert hit.tolist() == [0, 1, 0, 1]

    def test_marks_accumulate_and_fresh_counts(self):
        cand = np.array([1, 2, 3], dtype=np.uint32)
        hit = np.zeros(3, dtype=np.uint8)
        assert _mark_seg(cand, np.array([2], dtype=np.uint32), hit) == 1
        # Re-marking 2 is not fresh; 3 is.
        assert _mark_seg(cand, np.array([3, 2], dtype=np.uint32), hit) == 1
        assert hit.tolist() == [0, 1, 1]

    def test_empty_inputs(self):
        hit = np.zeros(0, dtype=np.uint8)
        assert _mark_seg(np.zeros(0, np.uint32), np.zeros(3, np.uint32), hit) == 0
        hit = np.zeros(2, dtype=np.uint8)
        assert _mark_seg(np.array([1, 2], np.uint32),
                         np.zeros(0, np.uint32), hit) == 0


class TestMergeStreamCut:
    def test_take_bound_is_exact_above_2_53(self):
        """Regression: the chunk cut passed a PYTHON INT bound to
        searchsorted over uint64 keys; numpy promotes that pair to
        float64, whose 53-bit mantissa collapses composite keys (tag
        byte => every key >= 2^56) differing only in low bits — take()
        then overshot the bound and the k-way merge emitted disordered
        tables at bench scale."""
        s = _MergeStream.__new__(_MergeStream)
        s.readers = []
        s.keys = np.zeros(4, dtype=KEY_DTYPE)
        base = 0xA << 56
        s.keys["lo"] = np.array(
            [base | 1, base | 13, base | 14, base | 16], dtype=np.uint64
        )
        s.vals = np.arange(4, dtype=np.uint32)
        k, v = s.take(base | 13)  # python int on purpose
        assert k["lo"].tolist() == [base | 1, base | 13]
        assert len(s.keys) == 2

    def test_compact_all_stays_ordered_on_low_bit_keys(self):
        """End-to-end shape of the same regression: many flushed runs of
        low-cardinality composite keys (code-style: high tag byte, low
        value bits) fold into one table that must be globally lo-major
        ordered with exact scan counts."""
        rng = np.random.default_rng(11)
        n = 6000
        codes = rng.integers(1, 17, n)
        entries = [
            (TAG_CODE, int(c), ts + 1, ts) for ts, c in enumerate(codes)
        ]
        tree = _query_tree(entries, memtable_max=256, flush_every=250)
        tree.compact_all()
        [tables] = [lv for lv in tree.levels if lv]
        for t in tables:
            fences = tree._table_fences(t)
            lo = np.concatenate([
                tree._read_data_block(int(f["block"]), int(f["count"]))[0]
                for f in fences
            ])["lo"]
            assert bool(np.all(lo[1:] >= lo[:-1]))
        for c in range(1, 17):
            got = tree.scan_lo(prefix(TAG_CODE, c))
            assert len(got) == int((codes == c).sum())


class TestScanBuilderEngine:
    N_ROWS = 3000

    def _store(self, seed, flush_every=None):
        """Random (code, ledger, ud64) rows + an account-style exact-key
        index; duplicate folded keys are the norm (16 codes over 3000
        rows) and `flush_every` spreads them across run boundaries."""
        rng = np.random.default_rng(seed)
        n = self.N_ROWS
        codes = rng.integers(1, 17, n)
        ledgers = rng.integers(1, 3, n)
        ud64 = rng.integers(0, 4, n)
        accounts = rng.integers(1, 30, n)
        entries = []
        for ts in range(n):
            entries.append((TAG_CODE, int(codes[ts]), ts + 1, ts))
            entries.append((TAG_LEDGER, int(ledgers[ts]), ts + 1, ts))
            entries.append((TAG_UD64, int(ud64[ts]), ts + 1, ts))
        qt = _query_tree(entries, flush_every=flush_every)
        grid = MemGrid(block_count=8192, block_size=4096)
        at = DurableIndex(grid, unique=False, memtable_max=256, growth=4)
        step = flush_every or n
        for i in range(0, n, step):
            sl = slice(i, min(i + step, n))
            count = sl.stop - sl.start
            at.insert_batch(
                pack_keys(accounts[sl].astype(np.uint64),
                          np.zeros(count, dtype=np.uint64)),
                np.arange(sl.start, sl.stop, dtype=np.uint32),
            )
            if flush_every:
                at.flush_memtable()
        cols = dict(code=codes, ledger=ledgers, ud64=ud64, acct=accounts)
        return qt, at, cols

    def _brute(self, cols, code=None, ledger=None, ud64=None, acct=None,
               ts_min=0, ts_max=scan.U64_MAX):
        keep = np.ones(self.N_ROWS, dtype=bool)
        if code is not None:
            keep &= cols["code"] == code
        if ledger is not None:
            keep &= cols["ledger"] == ledger
        if ud64 is not None:
            keep &= cols["ud64"] == ud64
        if acct is not None:
            keep &= cols["acct"] == acct
        ts = np.arange(1, self.N_ROWS + 1)
        keep &= (ts >= ts_min) & (ts <= ts_max)
        return np.flatnonzero(keep).astype(np.uint32)

    @pytest.mark.parametrize("flush_every", [None, 111])
    def test_property_engine_matches_brute_force(self, flush_every):
        """Forced probes (row_cost=2**62): the engine's AND is EXACT here
        — fold56 is identity for these small values and the account index
        holds one side only — so execute("probe"), execute("materialize")
        and the numpy brute force agree on every random query."""
        qt, at, cols = self._store(seed=2, flush_every=flush_every)
        rng = np.random.default_rng(7)
        for trial in range(25):
            kw = {}
            if rng.random() < 0.8:
                kw["code"] = int(rng.integers(1, 18))  # 17 => empty pred
            if rng.random() < 0.6:
                kw["ledger"] = int(rng.integers(1, 3))
            if rng.random() < 0.4:
                kw["ud64"] = int(rng.integers(0, 4))
            if rng.random() < 0.5:
                kw["acct"] = int(rng.integers(1, 30))
            if not kw:
                kw["code"] = 1
            ts_min, ts_max = 0, scan.U64_MAX
            if rng.random() < 0.5:
                ts_min = int(rng.integers(1, self.N_ROWS))
                ts_max = min(ts_min + int(rng.integers(1, 1500)),
                             self.N_ROWS)
            b = ScanBuilder(qt, at, ts_min, ts_max, row_cost=2**62)
            if "code" in kw:
                b.where_field(TAG_CODE, kw["code"])
            if "ledger" in kw:
                b.where_field(TAG_LEDGER, kw["ledger"])
            if "ud64" in kw:
                b.where_field(TAG_UD64, kw["ud64"])
            if "acct" in kw:
                b.where_account(kw["acct"], 0)
            want = self._brute(cols, ts_min=ts_min, ts_max=ts_max, **kw)
            # account predicates ignore the ts window at the index level
            # (exact-key index has no ts dimension): compare the probed
            # result after the same ts mask the caller's verify applies.
            got = np.asarray(b.execute("probe"), dtype=np.uint32)
            ts = got.astype(np.int64) + 1
            got = got[(ts >= ts_min) & (ts <= ts_max)]
            assert got.tolist() == want.tolist(), (trial, kw)
            mat = np.asarray(b.execute("materialize"), dtype=np.uint32)
            ts = mat.astype(np.int64) + 1
            mat = mat[(ts >= ts_min) & (ts <= ts_max)]
            assert mat.tolist() == want.tolist(), (trial, kw)

    def test_reversed_predicate_order_plans_identically(self):
        qt, at, _cols = self._store(seed=3)
        fwd = ScanBuilder(qt, at).where_field(TAG_CODE, 5) \
            .where_field(TAG_LEDGER, 1)
        fwd.where_account(9, 0)
        rev = ScanBuilder(qt, at)
        rev.where_account(9, 0)
        rev.where_field(TAG_LEDGER, 1).where_field(TAG_CODE, 5)
        assert fwd.plan() == rev.plan()
        assert (fwd.execute("probe") == rev.execute("probe")).all()

    def test_plan_orders_by_estimated_cardinality(self):
        qt, at, cols = self._store(seed=4)
        b = ScanBuilder(qt, at)
        b.where_field(TAG_LEDGER, 1)   # ~half the rows
        b.where_field(TAG_CODE, 7)     # ~1/16 of the rows
        plan = b.plan()
        assert plan[0].tag == TAG_CODE
        assert plan[0].est <= plan[1].est

    def test_row_cost_zero_forbids_probes(self):
        qt, at, _cols = self._store(seed=5)
        b = ScanBuilder(qt, at, row_cost=0)
        b.where_field(TAG_CODE, 3).where_field(TAG_LEDGER, 1)
        driver_only = b.execute("probe")
        want = qt.scan_lo(prefix(TAG_CODE, 3))
        assert driver_only.tolist() == want.tolist()

    def test_probe_pays_skips_near_universal_predicate(self):
        """Buffer-aware pay rule: a predicate whose estimate covers the
        whole store keeps ~every candidate, so probing it never pays —
        regardless of the log's residency."""
        b = ScanBuilder(None, None, log_stats=(10_000_000, 5000, 0.2))
        universal = Pred("field", 1, 0, tag=TAG_LEDGER, est=10_000_000)
        selective = Pred("field", 7, 0, tag=TAG_CODE, est=600_000)
        assert not b._probe_pays(universal, 300_000)
        assert b._probe_pays(selective, 300_000)
        # Warm log: the block-miss term vanishes and the same selective
        # probe stops paying for a small candidate set.
        warm = ScanBuilder(None, None, log_stats=(10_000_000, 5000, 1.0))
        assert not warm._probe_pays(selective, 3_000)


class TestLogGather:
    def _log(self, n=3000):
        grid = MemGrid(block_count=8192, block_size=4096)
        dtype = np.dtype([("a", "<u8"), ("b", "<u4")])
        log = DurableLog(grid, dtype)
        recs = np.zeros(n, dtype=dtype)
        recs["a"] = np.arange(n, dtype=np.uint64) * 3 + 1
        recs["b"] = np.arange(n, dtype=np.uint32)
        log.append_batch(recs)
        return log, recs

    def test_gather_sorted_unsorted_and_tail(self):
        log, recs = self._log()
        log.flush_pending()
        rng = np.random.default_rng(9)
        for rows in (
            np.arange(0, 3000, 7),                       # ascending
            rng.permutation(3000)[:500],                 # unsorted
            np.array([2999, 0, 1500]),                   # reverse-ish
            np.zeros(0, dtype=np.int64),                 # empty
            np.array([5, 5, 5]),                         # duplicates
        ):
            got = log.gather(rows)
            assert got.tobytes() == recs[rows].tobytes()

    def test_gather_spans_flushed_and_tail_rows(self):
        log, recs = self._log(350)  # 340 rows/block: one flushed + tail
        rows = np.array([349, 3, 340, 339, 0])
        got = log.gather(rows)
        assert got.tobytes() == recs[rows].tobytes()


class _PagingAdapter:
    """Drives Client.query_transfers_paged's UNMODIFIED cursor loop
    against a local StateMachine — the loop only touches
    self.query_transfers, so the shipped paging logic runs verbatim."""

    def __init__(self, sm):
        self.sm = sm

    def query_transfers(self, timestamp_min=0, timestamp_max=0,
                        limit=8190, flags=0, **predicates):
        from tigerbeetle_tpu import types

        f = np.zeros(1, dtype=types.QUERY_FILTER_V2_DTYPE)
        f[0]["timestamp_min"] = timestamp_min
        f[0]["timestamp_max"] = timestamp_max
        f[0]["limit"], f[0]["flags"] = limit, flags
        for k, v in predicates.items():
            f[0][k] = v
        return self.sm.query_transfers(f[0])

    paged = __import__(
        "tigerbeetle_tpu.client", fromlist=["Client"]
    ).Client.query_transfers_paged


class TestPagingCursors:
    N = 700

    def _sm(self):
        from tigerbeetle_tpu import types
        from tigerbeetle_tpu.constants import TEST_MIN
        from tigerbeetle_tpu.models.state_machine import StateMachine

        sm = StateMachine(TEST_MIN, backend="numpy")
        accs = np.zeros(8, dtype=types.ACCOUNT_DTYPE)
        accs["id_lo"] = np.arange(1, 9)
        accs["ledger"], accs["code"] = 1, 10
        ts = sm.prepare("create_accounts", 8)
        assert len(sm.create_accounts(accs, timestamp=ts)) == 0
        self._next_id = 1
        return sm

    def _ingest(self, sm, n, seed):
        from tigerbeetle_tpu import types

        rng = np.random.default_rng(seed)
        ev = np.zeros(n, dtype=types.TRANSFER_DTYPE)
        ev["id_lo"] = np.arange(self._next_id, self._next_id + n,
                                dtype=np.uint64)
        self._next_id += n
        dr = rng.integers(1, 9, n).astype(np.uint64)
        cr = rng.integers(1, 9, n).astype(np.uint64)
        ev["debit_account_id_lo"] = dr
        ev["credit_account_id_lo"] = np.where(cr == dr, (cr % 8) + 1, cr)
        ev["amount_lo"] = 1
        ev["ledger"] = 1
        ev["code"] = rng.integers(1, 4, n)
        ts = sm.prepare("create_transfers", n)
        assert len(sm.create_transfers(ev, timestamp=ts)) == 0
        sm.flush_deferred()
        sm.compact_beat()

    @pytest.mark.parametrize("flags", [0, 1])
    def test_pages_partition_the_full_result(self, flags):
        sm = self._sm()
        self._ingest(sm, self.N, seed=21)
        c = _PagingAdapter(sm)
        full = c.query_transfers(code=2, limit=8190, flags=flags)
        pages = list(c.paged(page_limit=97, flags=flags, code=2))
        got = (np.concatenate(pages) if pages
               else np.zeros(0, dtype=full.dtype))
        assert got.tobytes() == full.tobytes()
        assert all(len(p) <= 97 for p in pages)
        assert all(len(p) == 97 for p in pages[:-1])

    def test_cursor_stable_across_concurrent_ingest(self):
        """Rows committed AFTER a page was served land strictly past the
        forward cursor: resumed pages pick them up exactly once, and
        already-served pages would be byte-identical if re-read."""
        sm = self._sm()
        self._ingest(sm, self.N, seed=22)
        c = _PagingAdapter(sm)
        it = c.paged(page_limit=50, code=1)
        first = next(it)
        self._ingest(sm, self.N, seed=23)  # concurrent writer
        rest = list(it)
        got = np.concatenate([first] + rest)
        full = c.query_transfers(code=1, limit=8190)
        assert got.tobytes() == full.tobytes()
        ids = got["id_lo"]
        assert len(np.unique(ids)) == len(ids)

    def test_reversed_cursor_ignores_new_tail(self):
        """Newest-first paging started before an ingest burst never sees
        the burst: its cursor window is capped at the start timestamp."""
        sm = self._sm()
        self._ingest(sm, self.N, seed=24)
        c = _PagingAdapter(sm)
        snapshot = c.query_transfers(code=3, limit=8190, flags=1)
        it = c.paged(page_limit=61, flags=1, code=3,
                     timestamp_max=int(snapshot["timestamp"][0]))
        first = next(it)
        self._ingest(sm, self.N, seed=25)
        got = np.concatenate([first] + list(it))
        assert got.tobytes() == snapshot.tobytes()


class TestIntersect:
    """The scan engine's AND-merge (C gallop, or np.intersect1d under its
    size gate) against numpy's own statement of a set intersection."""

    @pytest.mark.parametrize("trial", range(10))
    def test_intersect_matches_numpy(self, trial):
        from tigerbeetle_tpu.lsm.store import intersect_sorted_u32

        rng = np.random.default_rng([12, trial])
        # Trials 0-7: hundreds of rows a side (the C gallop); 8 and 9:
        # a short side under the shim's 32-row gate, and a disjoint pair.
        na, nb = ((800, 1200), (20, 1200))[trial == 8]
        a = np.unique(rng.integers(0, 5000, na)).astype(np.uint32)
        b = np.unique(rng.integers(0, 5000, nb)).astype(np.uint32)
        if trial == 9:
            b = b + np.uint32(5000)
        want = np.intersect1d(a, b).astype(np.uint32)
        got = intersect_sorted_u32(a, b)
        assert got.dtype == np.uint32
        assert got.tobytes() == want.tobytes()
        assert scan.intersect_rows([b, a]).tobytes() == want.tobytes()

    def test_intersect_rows_three_way_smallest_first(self):
        a = np.array([1, 5, 9, 1000], dtype=np.uint32)
        b = np.array([5, 9, 64], dtype=np.uint32)
        c = np.arange(0, 2000, dtype=np.uint32)
        assert scan.intersect_rows([c, a, b]).tolist() == [5, 9]
        assert scan.intersect_rows([a, np.zeros(0, np.uint32), c]).tolist() == []
