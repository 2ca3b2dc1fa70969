"""Multi-batch coalescing + result demux (reference client.zig:45 Batch,
state_machine.zig:126-165 Demuxer): N small logical batches ride ONE
request/prepare; demuxed results byte-equal N separate requests."""

import asyncio

import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.client import AsyncClient
from tigerbeetle_tpu.testing.cluster import (
    Cluster, account_batch, transfer_batch,
)
from tigerbeetle_tpu.vsr.header import Operation
from tests.test_cluster import do_request, setup_client


def _mk_batches():
    """5 small logical batches incl. per-batch failures (dup id within a
    batch, unknown account) so the demuxed result indices matter."""
    batches = []
    # batch 0: two OK transfers
    batches.append([dict(id=1, debit_account_id=1, credit_account_id=2,
                         amount=5, ledger=1, code=1),
                    dict(id=2, debit_account_id=2, credit_account_id=1,
                         amount=3, ledger=1, code=1)])
    # batch 1: second event fails (unknown debit account)
    batches.append([dict(id=3, debit_account_id=1, credit_account_id=2,
                         amount=1, ledger=1, code=1),
                    dict(id=4, debit_account_id=99, credit_account_id=2,
                         amount=1, ledger=1, code=1)])
    # batch 2: one OK
    batches.append([dict(id=5, debit_account_id=1, credit_account_id=2,
                         amount=2, ledger=1, code=1)])
    # batch 3: duplicate of batch 0's id -> exists
    batches.append([dict(id=1, debit_account_id=1, credit_account_id=2,
                         amount=5, ledger=1, code=1)])
    # batch 4: three OK
    batches.append([dict(id=6 + i, debit_account_id=1, credit_account_id=2,
                         amount=1, ledger=1, code=1) for i in range(3)])
    return [
        np.frombuffer(bytearray(transfer_batch(b)), dtype=types.TRANSFER_DTYPE)
        for b in batches
    ]


class TestPlanAndDemux:
    def test_plan_respects_batch_max_and_open_chains(self):
        LINKED = 0x1
        mk = lambda n, open_chain=False: (  # noqa: E731
            (lambda ev: (ev.__setitem__("flags", [0] * (n - 1) + [LINKED])
                         if open_chain else None, ev)[1])(
                np.zeros(n, dtype=types.TRANSFER_DTYPE))
        )
        batches = [mk(3), mk(4), mk(2, open_chain=True), mk(5), mk(6)]
        groups = AsyncClient.plan_coalesce(batches, batch_max=10)
        # 3+4 fit; the open-chain batch is ALONE; 5+6 > 10 splits.
        assert groups == [[0, 1], [2], [3], [4]]

    def test_demux_rebases_indices(self):
        res = np.zeros(3, dtype=types.EVENT_RESULT_DTYPE)
        res["index"] = [1, 3, 4]
        res["result"] = [7, 8, 9]
        parts = AsyncClient.demux_results(res, [2, 2, 1])
        assert parts[0]["index"].tolist() == [1]
        assert parts[0]["result"].tolist() == [7]
        assert parts[1]["index"].tolist() == [1]
        assert parts[1]["result"].tolist() == [8]
        assert parts[2]["index"].tolist() == [0]
        assert parts[2]["result"].tolist() == [9]


class TestCoalescedThroughCluster:
    def test_one_prepare_results_byte_equal(self):
        batches = _mk_batches()

        # Reference run: N separate requests on one cluster.
        cl1 = Cluster(replica_count=1, seed=41)
        c1 = setup_client(cl1)
        do_request(cl1, c1, Operation.CREATE_ACCOUNTS, account_batch([1, 2]))
        want = []
        for ev in batches:
            r = do_request(cl1, c1, Operation.CREATE_TRANSFERS, ev.tobytes())
            want.append(
                np.frombuffer(bytearray(r.body), dtype=types.EVENT_RESULT_DTYPE)
            )

        # Coalesced run: the same batches as ONE request on a fresh
        # cluster, demuxed.
        cl2 = Cluster(replica_count=1, seed=42)
        c2 = setup_client(cl2)
        do_request(cl2, c2, Operation.CREATE_ACCOUNTS, account_batch([1, 2]))
        ops_before = cl2.replicas[0].commit_min
        groups = AsyncClient.plan_coalesce(batches, batch_max=8190)
        assert groups == [[0, 1, 2, 3, 4]]  # all five coalesce
        joined = np.concatenate(batches)
        r = do_request(cl2, c2, Operation.CREATE_TRANSFERS, joined.tobytes())
        assert cl2.replicas[0].commit_min == ops_before + 1  # ONE prepare
        res = np.frombuffer(bytearray(r.body), dtype=types.EVENT_RESULT_DTYPE)
        got = AsyncClient.demux_results(res, [len(b) for b in batches])

        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert g.tobytes() == w.tobytes()


class TestCDemux:
    def test_c_demux_matches_python(self):
        import ctypes

        from tigerbeetle_tpu import native

        lib = native.tb_client()
        if lib is None:
            pytest.skip("no AES-NI / C compiler for the client lib")
        res = np.zeros(4, dtype=types.EVENT_RESULT_DTYPE)
        res["index"] = [0, 2, 5, 6]
        res["result"] = [10, 11, 12, 13]
        lens = np.array([2, 3, 2], dtype=np.uint32)
        offs = np.zeros(3, dtype=np.uint32)
        counts = np.zeros(3, dtype=np.uint32)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        lib.tbc_demux_results.argtypes = [
            u8p, ctypes.c_uint32, u32p, ctypes.c_uint32, u32p, u32p,
        ]
        lib.tbc_demux_results.restype = ctypes.c_int
        buf = res.copy()
        rc = lib.tbc_demux_results(
            buf.ctypes.data_as(u8p), len(buf),
            lens.ctypes.data_as(u32p), len(lens),
            offs.ctypes.data_as(u32p), counts.ctypes.data_as(u32p),
        )
        assert rc == 0
        py = AsyncClient.demux_results(res, lens.tolist())
        assert counts.tolist() == [len(p) for p in py]
        for b in range(3):
            span = buf[offs[b] : offs[b] + counts[b]]
            assert span.tobytes() == py[b].tobytes()

    def test_c_demux_rejects_garbage(self):
        import ctypes

        from tigerbeetle_tpu import native

        lib = native.tb_client()
        if lib is None:
            pytest.skip("no AES-NI / C compiler for the client lib")
        res = np.zeros(2, dtype=types.EVENT_RESULT_DTYPE)
        res["index"] = [5, 1]  # non-ascending
        lens = np.array([4, 4], dtype=np.uint32)
        offs = np.zeros(2, dtype=np.uint32)
        counts = np.zeros(2, dtype=np.uint32)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        rc = lib.tbc_demux_results(
            res.ctypes.data_as(u8p), len(res),
            lens.ctypes.data_as(u32p), len(lens),
            offs.ctypes.data_as(u32p), counts.ctypes.data_as(u32p),
        )
        assert rc != 0


class TestAsyncSubmitMany:
    def test_submit_many_over_tcp(self, tmp_path):
        """submit_many through a REAL server: results match separate
        requests, using fewer wire requests."""
        import os
        import subprocess
        import sys
        import time as _time

        port = 38200 + os.getpid() % 500
        path = tmp_path / "demux.tb"
        subprocess.run(
            [sys.executable, "-m", "tigerbeetle_tpu.cli", "format",
             "--replica", "0", str(path)],
            check=True, capture_output=True,
        )
        from tigerbeetle_tpu.cli import spawn_replica

        proc, _device = spawn_replica(
            [f"--addresses=127.0.0.1:{port}", "--replica=0",
             "--backend=numpy"], str(path),
        )
        try:
            from tigerbeetle_tpu.client import Client

            c = Client([("127.0.0.1", port)])
            accs = np.zeros(2, dtype=types.ACCOUNT_DTYPE)
            accs["id_lo"] = [1, 2]
            accs["ledger"] = 1
            accs["code"] = 1
            assert len(c.create_accounts(accs)) == 0
            c.close()

            batches = _mk_batches()

            async def run():
                async with AsyncClient(
                    [("127.0.0.1", port)], sessions=2
                ) as ac:
                    return await ac.submit_many(
                        Operation.CREATE_TRANSFERS, batches
                    )

            got = asyncio.run(run())
            # Failures land in the right batches with rebased indices.
            assert [len(g) for g in got] == [0, 1, 0, 1, 0]
            assert got[1]["index"].tolist() == [1]
            assert got[3]["index"].tolist() == [0]
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
