"""The store lives on the host.

Device = the balance tables and the `create_transfers_*` / `read_balances`
kernels; host = bus, VSR, WAL and the whole LSM store. Two statements of
it that a test can hold: on the jax backend the `store-executor` thread
enters no device step and moves no byte over the link, whatever the batch
brings it; and `lsm/` never loads jax (a fresh interpreter that imports a
module of it and runs a flush ends with `jax` out of `sys.modules`).
"""

import subprocess
import sys

import numpy as np
import pytest

from tests.test_cluster import do_request, setup_client
from tigerbeetle_tpu import tracer, types
from tigerbeetle_tpu.flags import TransferFlags
from tigerbeetle_tpu.testing.cluster import Cluster, account_batch, parse_results
from tigerbeetle_tpu.vsr.header import Operation

LINKED = int(TransferFlags.LINKED)
PENDING = int(TransferFlags.PENDING)
POST = int(TransferFlags.POST_PENDING_TRANSFER)
VOID = int(TransferFlags.VOID_PENDING_TRANSFER)
ACCOUNTS = 8
N = 60  # events a batch (test_min: 64 a message)
# The commit path's jit entries: the only device work of the process.
COMMIT_ENTRIES = {
    "create_transfers_fast", "create_transfers_exact", "register_accounts",
    "write_balances", "read_balances",
}


def _simple(rng, first_id: int, varied: bool = False) -> np.ndarray:
    t = np.zeros(N, dtype=types.TRANSFER_DTYPE)
    t["id_lo"] = first_id + np.arange(N)
    dr = rng.integers(1, ACCOUNTS + 1, N)
    t["debit_account_id_lo"] = dr
    t["credit_account_id_lo"] = 1 + (dr + rng.integers(0, ACCOUNTS - 1, N)) % ACCOUNTS
    t["amount_lo"] = rng.integers(1, 1000, N)
    t["ledger"] = 1
    t["code"] = 7
    if varied:  # queryable columns that differ: the batch arrives unsorted
        t["user_data_64"] = rng.integers(0, 1 << 64, N, dtype=np.uint64)
        t["user_data_32"] = rng.integers(0, 1 << 32, N, dtype=np.uint32)
        t["code"] = rng.integers(1, 5, N)
    return t


def _batches(shape: str) -> list:
    """The create_transfers batches of one shape, in order."""
    rng = np.random.default_rng(30)
    if shape == "simple":
        return [_simple(rng, 1000 + i * N) for i in range(4)]
    if shape == "flush_crossing":
        # 5 index rows a transfer against index_memtable_rows 512: the
        # query tree's memtable flushes twice, on the store thread.
        return [_simple(rng, 1000 + i * N, varied=True) for i in range(5)]
    if shape == "tpcb_chains":
        out = []
        for i in range(4):
            t = _simple(rng, 1000 + i * N)
            t["flags"] = np.tile([LINKED, LINKED, 0], N // 3)
            t["amount_lo"][4] = 0 if i == 1 else t["amount_lo"][4]  # one chain rolls back
            out.append(t)
        return out
    if shape == "two_phase":
        first = _simple(rng, 1000)
        first["flags"][: N // 2] = PENDING
        second = _simple(rng, 1000 + N)
        second["flags"][:20] = np.where(np.arange(20) % 2 == 0, POST, VOID)
        second["pending_id_lo"][:20] = first["id_lo"][:20]
        second["amount_lo"][:20] = 0
        second["debit_account_id_lo"][:20] = second["credit_account_id_lo"][:20] = 0
        second["flags"][21:24] = [LINKED, LINKED, 0]
        return [first, second, _simple(rng, 1000 + 2 * N)]
    assert shape == "query_read"
    return [_simple(rng, 1000 + i * N, varied=True) for i in range(3)]


@pytest.mark.parametrize(
    "shape", ["simple", "two_phase", "tpcb_chains", "flush_crossing", "query_read"])
def test_store_thread_stays_off_the_device(shape):
    was = tracer.enabled()
    tracer.enable()
    tracer.reset()
    cl = Cluster(replica_count=1, seed=30, sm_backend="jax", store_async=True)
    try:
        c = setup_client(cl)
        do_request(cl, c, Operation.CREATE_ACCOUNTS,
                   account_batch(range(1, ACCOUNTS + 1)))
        stored = 0
        for events in _batches(shape):
            reply = do_request(cl, c, Operation.CREATE_TRANSFERS, events.tobytes())
            stored += len(events) - len(parse_results(reply))
        if shape == "query_read":
            f = np.zeros(1, dtype=types.QUERY_FILTER_DTYPE)
            f[0]["ledger"], f[0]["code"], f[0]["limit"] = 1, 2, 8190
            got = do_request(cl, c, Operation.QUERY_TRANSFERS, f.tobytes())
            rows = np.frombuffer(bytearray(got.body), dtype=types.TRANSFER_DTYPE)
            assert len(rows) > 0 and (rows["code"] == 2).all()
        cl.quiesce()
        threads = tracer.by_thread()
        store = [st for st in tracer._states if st.name == "store-executor"]
        snap = tracer.snapshot()
    finally:
        cl.close()
        tracer.reset()
        if not was:
            tracer.disable()
    assert stored > N
    # The store thread did the store's work ...
    assert threads["store-executor"]["sm.beat"][0] > 0
    if shape != "two_phase":  # (a post/void batch applies inline, behind its barrier)
        assert threads["store-executor"]["sm.store.query"][0] > 0
    if shape == "flush_crossing":
        flushes = sum(st.counters.get("lsm.memtable_flushes", 0) for st in store)
        assert flushes >= 2
    # ... and none of the device's: no device span, no byte over the link.
    assert not [e for e in threads["store-executor"] if e.startswith("device.")]
    for st in store:
        assert not [e for e in st.counters if e.startswith("device.")], st.counters
    # Process-wide, the device ran the commit path's entries and no other.
    entries = {e.split(".")[2] for e in snap if e.startswith("device.step.")}
    assert entries and entries <= COMMIT_ENTRIES, entries
    assert snap["device.h2d_bytes"]["count"] > 0  # the commit batch did cross


FLUSH = {
    "tree": """
idx = DurableIndex(MemGrid(block_count=256, block_size=4096), unique=False,
                   memtable_max=64, merge_hint="dups")
k = np.zeros(100, dtype=KEY_DTYPE); k["lo"] = np.arange(100) % 7; k["hi"] = np.arange(100)
idx.insert_unsorted(k, np.arange(100, dtype=np.uint32))
idx.insert_sorted(*sort_kv(k, np.arange(100, dtype=np.uint32)))
idx.flush_memtable(); idx.drain_compaction()
assert len(idx.lookup_range(k[3])) == 2
""",
    "scan": """
from tigerbeetle_tpu.lsm import scan
a = np.arange(0, 400, 2, dtype=np.uint32); b = np.arange(0, 400, 3, dtype=np.uint32)
assert scan.intersect_rows([a, b]).tolist() == list(range(0, 400, 6))
assert int(scan.fold56(np.uint64(1 << 56))) == 1
""",
    "store": """
k = np.zeros(50, dtype=KEY_DTYPE); k["lo"] = np.arange(50)[::-1]
sk, sv = sort_kv(k, np.arange(50, dtype=np.uint32))
mk, mv = merge_host_kway([sk[:20], sk[20:]], [sv[:20], sv[20:]])
assert mk.tobytes() == sk.tobytes()
""",
    "groove": """
from tigerbeetle_tpu.lsm.groove import PostedGroove
g = PostedGroove(MemGrid(block_count=256, block_size=4096), memtable_max=16)
g.insert_arrays(np.arange(1, 41, dtype=np.uint64), np.ones(40, dtype=np.uint32))
g.index.flush_memtable()
assert g.get(7) == 1 and not g.contains(99)
""",
    "log": """
from tigerbeetle_tpu import types
from tigerbeetle_tpu.lsm.log import DurableLog
log = DurableLog(MemGrid(block_count=256, block_size=4096), types.TRANSFER_DTYPE)
rows = log.append_batch(np.zeros(100, dtype=types.TRANSFER_DTYPE))
log.flush_pending()
assert len(log.gather(rows[:5])) == 5
""",
}


@pytest.mark.parametrize("module", sorted(FLUSH))
def test_lsm_module_never_loads_jax(module):
    script = f"""
import sys
import tigerbeetle_tpu.lsm.{module}
import numpy as np
from tigerbeetle_tpu.io.grid import MemGrid
from tigerbeetle_tpu.lsm.store import KEY_DTYPE, merge_host_kway, sort_kv
from tigerbeetle_tpu.lsm.tree import DurableIndex
{FLUSH[module]}
loaded = sorted(m for m in sys.modules if m == "jax" or m.startswith(("jax.", "jaxlib")))
assert not loaded, loaded
assert not [m for m in sys.modules if m.startswith("tigerbeetle_tpu.ops")]
print("ok")
"""
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0 and p.stdout.strip() == "ok", p.stderr[-2000:]
