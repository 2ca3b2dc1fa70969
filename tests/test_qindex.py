"""Query-index tests: the key block `StateMachine._store_query_index`
builds against a per-record statement of it in Python ints (random
records and the fold56 boundaries), what the memtable flush makes of
sorted and unsorted batches (same table bytes, same flush cadence), and
the host k-way merge against the stable radix sort."""

import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.constants import TEST_MIN
from tigerbeetle_tpu.io.grid import MemGrid
from tigerbeetle_tpu.lsm import scan
from tigerbeetle_tpu.lsm.store import KEY_DTYPE, merge_host_kway, sort_kv
from tigerbeetle_tpu.lsm.tree import DurableIndex

MASK56 = (1 << 56) - 1


def fold56_int(lo: int, hi: int = 0) -> int:
    """fold56 as arithmetic on Python ints: the identity below 2^56, the
    bits above folded back in; a u128's hi word shifted by one."""
    out = (lo & MASK56) ^ (lo >> 56)
    out ^= (((hi & MASK56) << 1) & MASK56) ^ (hi >> 55)
    return out & MASK56


def stated_query_keys(recs, rows, tstamp):
    """The statement: five blocks in tag order, and in each one entry a
    record, key.lo = tag << 56 | fold56(field), key.hi = the timestamp,
    value = the record's object-log row."""
    lo, hi, vals = [], [], []
    for tag, f_lo, f_hi in (
        (5, "user_data_128_lo", "user_data_128_hi"), (6, "user_data_64", None),
        (7, "user_data_32", None), (9, "ledger", None), (10, "code", None),
    ):
        for i in range(len(recs)):
            field_hi = int(recs[f_hi][i]) if f_hi else 0
            lo.append(tag << 56 | fold56_int(int(recs[f_lo][i]), field_hi))
            hi.append(int(tstamp[i]))
            vals.append(int(rows[i]))
    keys = np.zeros(len(lo), dtype=KEY_DTYPE)
    keys["lo"] = np.array(lo, dtype=np.uint64)
    keys["hi"] = np.array(hi, dtype=np.uint64)
    return keys, np.array(vals, dtype=np.uint32)


def rand_recs(rng, n, constant=False):
    recs = np.zeros(n, dtype=types.TRANSFER_DTYPE)
    if constant:
        recs["ledger"] = 1
        recs["code"] = 7
    else:
        recs["user_data_128_lo"] = rng.integers(0, 1 << 64, n, dtype=np.uint64)
        recs["user_data_128_hi"] = rng.integers(0, 1 << 64, n, dtype=np.uint64)
        recs["user_data_64"] = rng.integers(0, 1 << 64, n, dtype=np.uint64)
        recs["user_data_32"] = rng.integers(0, 1 << 32, n, dtype=np.uint32)
        recs["ledger"] = rng.integers(1, 5, n)
        recs["code"] = rng.integers(1, 5, n)
    recs["timestamp"] = rng.integers(1, 1 << 63, n, dtype=np.uint64)
    return recs


class _Inserts:
    """Stands where the query tree does and keeps what it is handed."""

    def __init__(self):
        self.calls = []

    def insert_sorted(self, keys, vals):
        self.calls.append(("sorted", keys, vals))

    def insert_unsorted(self, keys, vals):
        self.calls.append(("unsorted", keys, vals))


@pytest.fixture(scope="module")
def sm():
    from tigerbeetle_tpu.models.state_machine import StateMachine

    return StateMachine(TEST_MIN, backend="numpy")


def built_by_state_machine(sm, recs, rows, ts=None):
    real, sm.query_rows = sm.query_rows, _Inserts()
    try:
        sm._store_query_index(recs, rows, ts)
        (call,) = sm.query_rows.calls
    finally:
        sm.query_rows = real
    return call


class TestKeyBlock:
    """`_store_query_index`'s numpy block is the statement, byte for byte."""

    @pytest.mark.parametrize("seed", [1, 2, 3])
    @pytest.mark.parametrize("n", [1, 255, 1000])
    def test_random_records_match_the_statement(self, sm, seed, n):
        rng = np.random.default_rng(seed)
        recs = rand_recs(rng, n)
        rows = rng.integers(0, 1 << 32, n).astype(np.uint32)
        how, keys, vals = built_by_state_machine(sm, recs, rows)
        want_k, want_v = stated_query_keys(recs, rows, recs["timestamp"])
        assert keys.tobytes() == want_k.tobytes()
        assert vals.dtype == np.uint32 and np.array_equal(vals, want_v)
        # Handed over as a sorted run only when the blocks ARE one.
        assert how == ("sorted" if n == 1 else "unsorted")
        # The commit's own timestamps, where the caller passes them.
        ts = rng.integers(1, 1 << 63, n, dtype=np.uint64)
        _how, keys, _vals = built_by_state_machine(sm, recs, rows, ts)
        assert keys.tobytes() == stated_query_keys(recs, rows, ts)[0].tobytes()

    def test_fold56_boundary_values(self, sm):
        """xor-fold edge cases: values straddling 2^56 in every queryable
        field, u128 hi words at the 55/56-bit fold boundaries."""
        edges = np.array(
            [0, 1, (1 << 56) - 1, 1 << 56, (1 << 56) + 1,
             (1 << 63), (1 << 64) - 1, (1 << 57) - 1],
            dtype=np.uint64,
        )
        n = len(edges)
        recs = np.zeros(n, dtype=types.TRANSFER_DTYPE)
        recs["user_data_64"] = edges
        recs["user_data_128_lo"] = edges[::-1].copy()
        # hi words exercising (hi & MASK56) << 1 and hi >> 55 carries.
        recs["user_data_128_hi"] = np.array(
            [0, 1, (1 << 55) - 1, 1 << 55, (1 << 56) - 1, 1 << 56,
             (1 << 64) - 1, (1 << 23) + 1],
            dtype=np.uint64,
        )
        recs["user_data_32"] = np.uint32((1 << 32) - 1)
        recs["ledger"] = np.uint32((1 << 32) - 1)
        recs["code"] = np.uint16((1 << 16) - 1)
        recs["timestamp"] = np.arange(1, n + 1, dtype=np.uint64)
        rows = np.arange(n, dtype=np.uint32)
        _how, keys, vals = built_by_state_machine(sm, recs, rows)
        want_k, want_v = stated_query_keys(recs, rows, recs["timestamp"])
        assert keys.tobytes() == want_k.tobytes()
        assert np.array_equal(vals, want_v)
        # What a query scans for is what the insert side stored.
        assert int(keys["lo"][n + 3]) == scan.prefix(scan.TAG_UD64, 1 << 56)
        assert fold56_int(1 << 56) == 1 and fold56_int((1 << 56) - 1) == MASK56


def table_bytes(idx):
    out = []
    for lvl in idx.levels:
        for t in lvl:
            for f in idx._table_fences(t):
                bk, bv = idx._read_data_block(int(f["block"]), int(f["count"]))
                out.append(bk.tobytes())
                out.append(bv.tobytes())
    return b"".join(out)


def query_tree(memtable_max=1 << 30):
    return DurableIndex(
        MemGrid(block_count=8192, block_size=4096), unique=False,
        memtable_max=memtable_max, merge_hint="dups",
    )


class TestMemtableFlush:
    """A batch handed over as a sorted run (constant columns) and the same
    batch handed over unsorted must leave the same tables, flushed at the
    same batch boundaries: grid allocation order is checkpoint bytes."""

    def _drive_pair(self, batches=6, n=400, memtable_max=None):
        """`flagged` takes each batch as `_store_query_index` would hand
        it over (every other one constant, so sorted); `plain` takes all
        of them unsorted."""
        rng = np.random.default_rng(11)
        memtable_max = memtable_max or 5 * n * batches // 2
        flagged, plain = query_tree(memtable_max), query_tree(memtable_max)
        row0 = 0
        for b in range(batches):
            recs = rand_recs(rng, n, constant=(b % 2 == 0))
            rows = np.arange(row0, row0 + n, dtype=np.uint32)
            row0 += n
            k, v = stated_query_keys(recs, rows, recs["timestamp"])
            if scan.query_columns_constant(recs):
                flagged.insert_sorted(k, v)
            else:
                flagged.insert_unsorted(k, v)
            plain.insert_unsorted(k.copy(), v.copy())
        flagged.flush_memtable()
        plain.flush_memtable()
        return flagged, plain

    def test_flush_tables_byte_identical(self):
        flagged, plain = self._drive_pair()
        assert table_bytes(flagged) == table_bytes(plain)
        assert flagged.count == plain.count

    def test_mid_run_flush_same_cadence(self):
        """memtable_max trips inside insert: both flush at the same
        batch boundaries."""
        flagged, plain = self._drive_pair(batches=10, n=137,
                                          memtable_max=137 * 5 * 3)
        assert len(flagged.levels[0]) == len(plain.levels[0]) > 1
        assert table_bytes(flagged) == table_bytes(plain)

    def test_constant_column_sorted_insert_same_bytes(self):
        """Constant-column batches inserted as SORTED runs (k-way merge
        flush) must build byte-identical tables to the unsorted-insert
        radix flush."""
        rng = np.random.default_rng(13)
        a, b = query_tree(), query_tree()
        for i in range(5):
            recs = rand_recs(rng, 300, constant=True)
            assert scan.query_columns_constant(recs)
            rows = np.arange(i * 300, (i + 1) * 300, dtype=np.uint32)
            k, v = stated_query_keys(recs, rows, recs["timestamp"])
            a.insert_sorted(k, v)
            b.insert_unsorted(k.copy(), v.copy())
        a.flush_memtable()
        b.flush_memtable()
        assert table_bytes(a) == table_bytes(b)


class TestKwayHostMerge:
    """merge_host_kway: byte-identical to the stable radix sort of the
    concatenation, for every run-count/shape the flush produces."""

    def _runs(self, rng, counts, dup_heavy=False):
        parts_k, parts_v = [], []
        base = 0
        for n in counts:
            k = np.zeros(n, dtype=KEY_DTYPE)
            space = 8 if dup_heavy else 1 << 50
            k["lo"] = np.sort(
                rng.integers(0, space, n).astype(np.uint64)
            )
            k["hi"] = rng.integers(0, 1 << 50, n).astype(np.uint64)
            parts_k.append(k)
            parts_v.append(np.arange(base, base + n, dtype=np.uint32))
            base += n
        return parts_k, parts_v

    @pytest.mark.parametrize("counts,dups", [
        ((100, 200, 50), False),
        ((1000,) * 8, True),
        ((64,) * 20, False),       # > 8 runs: grouped folding
        ((0, 10, 0, 5), False),    # empty runs skipped
        ((1,), False),
    ])
    def test_matches_radix_sort(self, counts, dups):
        rng = np.random.default_rng(sum(counts) + len(counts))
        parts_k, parts_v = self._runs(rng, counts, dups)
        mk, mv = merge_host_kway(parts_k, parts_v)
        sk, sv = sort_kv(
            np.concatenate(parts_k), np.concatenate(parts_v)
        )
        assert mk.tobytes() == sk.tobytes()
        assert np.array_equal(mv, sv)

    def test_stability_equal_keys_drain_oldest_first(self):
        # Two runs, all-equal lo: run 0's values must all precede run 1's.
        k = np.zeros(4, dtype=KEY_DTYPE)
        k["lo"] = 7
        mk, mv = merge_host_kway(
            [k.copy(), k.copy()],
            [np.arange(4, dtype=np.uint32), np.arange(4, 8, dtype=np.uint32)],
        )
        assert list(mv) == list(range(8))
