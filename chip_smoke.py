#!/usr/bin/env python3
"""Serve the ledger from a real TPU once, and check every answer.

The quickest proof that the system still starts on the chip: format a
production-size data file, start `cli.py start --backend=jax` as a child
(the process that holds the chip — this one never imports JAX), drive it
over TCP with the normal clients at the full production width
(accounts_max 2^20, 8190-record messages), apply the same operations to
`models/oracle.py` in a third process, and compare every result code,
every balance and every query answer byte for byte, except the
timestamps the server's clock assigns. Then check on /metrics that each
device route the chip is supposed to take was taken, restart the server
on the same data file and read the same bytes back.

    python3 chip_smoke.py             one replica on one chip (what the driver runs)
    python3 chip_smoke.py --chips 4   three replicas, one chip each, the primary
                                      killed — this phase and its oracle only

Every failed phase is a non-zero exit: nothing here catches an error to
carry on. The last line of stdout, printed only when everything passed:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as the SERVER process reported it on its `listening`
line. Anything but a TPU is a failure; there is no CPU fallback. (The
tests rehearse the phases at a tiny size on the CPU by calling
one_chip()/three_replicas() with the platform they expect, see
tests/test_chip_smoke.py — the script itself has no switch for it.)
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import glob
import json
import multiprocessing
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# Neither loads JAX nor builds a shim (rebuild_shims() comes first for
# everything that does); alone in a directory the script stops here.
from tigerbeetle_tpu import types
from tigerbeetle_tpu.flags import AccountFilterFlags, AccountFlags, TransferFlags

REPO = os.path.dirname(os.path.abspath(__file__))

LINKED, PENDING = TransferFlags.LINKED, TransferFlags.PENDING
POST, VOID = TransferFlags.POST_PENDING_TRANSFER, TransferFlags.VOID_PENDING_TRANSFER
DEBITS, CREDITS, REVERSED = (AccountFilterFlags.DEBITS, AccountFilterFlags.CREDITS,
                             AccountFilterFlags.REVERSED)

# Each replica gets ONE chip from outside, through libtpu's process-
# visibility environment (no device option in the program). Left alone,
# the first process claims all four chips and the next one fails with
# "The TPU is already in use". TPU_VISIBLE_CHIPS alone is not enough on
# libtpu 0.0.34: without the two bounds the second process still fails.
CHIP_ENV = {"TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1", "TPU_PROCESS_BOUNDS": "1,1,1"}


@dataclasses.dataclass(frozen=True)
class Plan:
    """How much traffic. `batch` is the events per request: main() always
    sends full production messages (8190); only a CPU rehearsal narrows it."""

    config: str = "production"
    batch: int = 8190
    accounts: int = 1_000_000
    fast_batches: int = 64
    sessions: int = 8  # concurrent sessions in the fast phase (> commit depth 4)
    # One replica never drops a request, so its client waits longer than
    # any cold compile on the request path (a dead or hung server is the
    # watchdog's business, not a time-out's). A cluster does drop them —
    # a backup, a view change at start-up — and the client's time-out is
    # what resends: 30 s, times its 16 attempts, still covers a compile.
    request_timeout_s: float = 900.0
    deadline_s: float = 1150.0

    @property
    def k(self) -> int:
        """Size of each special account set (limit, history; 2k exact)."""
        return self.batch // 4


ONE_CHIP = Plan()
THREE_REPLICAS = Plan(accounts=100_000, fast_batches=16, request_timeout_s=30.0)


def say(msg: str) -> None:
    print(msg, flush=True)


class Failure(Exception):
    """A phase failed; main() exits non-zero with this message."""


# --- the traffic, a pure function of (seed, plan) ---------------------------
# Both this process (to send it) and the oracle's process (to apply it)
# build it from the seed; nothing but the fast phase's commit order
# crosses between them before the comparison.


class Traffic:
    def __init__(self, seed: int, plan: Plan) -> None:
        self.seed, self.plan = seed, plan
        k = plan.k
        n = plan.accounts
        assert n > 8 * k, "too few accounts for the special sets"
        # ids 1..n; the top 4k are the special sets, the rest plain.
        self.n_plain = n - 4 * k
        self.limit = np.arange(n - 4 * k + 1, n - 3 * k + 1, dtype=np.uint64)
        self.history = np.arange(n - 3 * k + 1, n - 2 * k + 1, dtype=np.uint64)
        self.exact = np.arange(n - 2 * k + 1, n + 1, dtype=np.uint64)
        self.next_id = 1  # transfer ids are handed out in build order
        self.fast = [self._fast_batch(b) for b in range(plan.fast_batches)]
        # Sequential batches after the fast phase, in this order. Route:
        # which commit path the state machine must take for each.
        self.special = [
            ("fund", "exact", self._fund()),
            ("limits", "exact", self._limits()),
            ("chains", "exact", self._chains()),
            ("pending", "fast", self._pending()),
            ("post", "exact", self._post()),
            ("void+chains", "exact", self._void()),
            ("balancing", "exact", self._balancing()),
            ("history", "exact", self._history()),
            ("duplicates", "serial", self._duplicates()),
        ]

    def rng(self, *stream: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, *stream])

    # accounts ----------------------------------------------------------

    def account_batches(self):
        n, b = self.plan.accounts, self.plan.batch
        for start in range(1, n + 1, b):
            ids = np.arange(start, min(start + b, n + 1), dtype=np.uint64)
            acc = np.zeros(len(ids), dtype=types.ACCOUNT_DTYPE)
            acc["id_lo"] = ids
            acc["user_data_128_lo"] = ids * np.uint64(0x9E3779B97F4A7C15)
            acc["user_data_128_hi"] = ids
            acc["user_data_64"] = ids % np.uint64(97)
            acc["user_data_32"] = ids % np.uint64(65521)
            acc["ledger"] = 1
            acc["code"] = 1 + ids % np.uint64(5)
            acc["flags"] = np.where(
                np.isin(ids, self.limit),
                AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS,
                np.where(np.isin(ids, self.history), AccountFlags.HISTORY, 0),
            )
            yield acc

    # transfers ---------------------------------------------------------

    def _blank(self, n: int) -> np.ndarray:
        t = np.zeros(n, dtype=types.TRANSFER_DTYPE)
        t["id_lo"] = np.arange(self.next_id, self.next_id + n, dtype=np.uint64)
        self.next_id += n
        t["ledger"] = 1
        t["code"] = 1
        t["amount_lo"] = 1
        return t

    def _fill_plain(self, t: np.ndarray, rng) -> None:
        """Debit and credit sides drawn uniformly from the plain accounts."""
        n = len(t)
        dr = rng.integers(1, self.n_plain + 1, n, dtype=np.uint64)
        cr = rng.integers(1, self.n_plain + 1, n, dtype=np.uint64)
        t["debit_account_id_lo"] = dr
        t["credit_account_id_lo"] = np.where(
            cr == dr, dr % np.uint64(self.n_plain) + np.uint64(1), cr)

    def _fast_batch(self, b: int) -> np.ndarray:
        """Uniform transfers between plain accounts (upstream's benchmark
        shape), a twentieth of them pending, the queryable columns drawn
        from small sets, and a few events that must fail on rungs whose
        answer does not depend on commit order."""
        rng = self.rng(1, b)
        n = self.plan.batch
        t = self._blank(n)
        self._fill_plain(t, rng)
        t["amount_lo"] = rng.integers(1, 1000, n)
        t["code"] = rng.integers(1, 5, n)
        t["user_data_64"] = rng.integers(1, 17, n)
        t["user_data_32"] = rng.integers(1, 17, n)
        t["user_data_128_lo"] = rng.integers(1, 1 << 62, n)
        t["user_data_128_hi"] = b + 1
        t["flags"] = np.where(rng.random(n) < 0.05, PENDING, 0)
        bad = rng.random(n)
        bad[0] = 1.0  # event 0 always commits: its timestamp dates the batch
        same = bad < 0.004
        t["credit_account_id_lo"][same] = t["debit_account_id_lo"][same]
        t["debit_account_id_lo"][(bad >= 0.004) & (bad < 0.008)] += np.uint64(
            self.plan.accounts + 7
        )  # no such account
        t["ledger"][(bad >= 0.008) & (bad < 0.012)] = 2
        t["amount_lo"][(bad >= 0.012) & (bad < 0.016)] = 0
        return t

    def _fund(self) -> np.ndarray:
        """10,000 to every special account from plain ones. Touches the
        limit and history accounts, so the batch must go exact."""
        t = self._blank(self.plan.batch)
        self._fill_plain(t, self.rng(2))
        special = np.concatenate([self.limit, self.history, self.exact])
        t["credit_account_id_lo"][: len(special)] = special
        t["amount_lo"][: len(special)] = 10_000
        return t

    def _limits(self) -> np.ndarray:
        """Four debits of 4,000 against each limit account's 10,000: the
        third and fourth must fail, which only an in-order evaluation of
        the batch can know."""
        t = self._blank(self.plan.batch)
        self._fill_plain(t, self.rng(3))
        k = self.plan.k
        for j in range(4):
            sl = slice(j * k, (j + 1) * k)
            t["debit_account_id_lo"][sl] = self.limit
            t["credit_account_id_lo"][sl] = self.exact[:k]
            t["amount_lo"][sl] = 4_000
        return t

    def _chains(self) -> np.ndarray:
        """Linked chains of three between exact accounts. Every fifth
        chain's middle link has amount zero (a host rung), every seventh
        overdraws a limit account (a kernel rung): the whole chain rolls
        back."""
        n = self.plan.batch
        t = self._blank(n)
        x = self.exact
        i = np.arange(n)
        t["debit_account_id_lo"] = x[i % len(x)]
        t["credit_account_id_lo"] = x[(i + 1) % len(x)]
        t["amount_lo"] = 3
        chains = n // 3
        in_chain = i < 3 * chains
        t["flags"] = np.where(in_chain & (i % 3 != 2), LINKED, 0)
        chain = i // 3
        mid = in_chain & (i % 3 == 1)
        t["amount_lo"][mid & (chain % 5 == 0)] = 0
        over = mid & (chain % 7 == 3)
        t["debit_account_id_lo"][over] = self.limit[chain[over] % len(self.limit)]
        t["amount_lo"][over] = 1_000_000
        return t

    def _pending(self) -> np.ndarray:
        """Pending transfers (no time-out) for the next two batches to
        post and void. Plain accounts, no exact flag: the fast path."""
        n = self.plan.batch
        t = self._blank(n)
        x = self.exact
        i = np.arange(n)
        t["debit_account_id_lo"] = x[i % len(x)]
        t["credit_account_id_lo"] = x[(i + 5) % len(x)]
        t["amount_lo"] = 10
        t["flags"] = PENDING
        self.pending_ids = t["id_lo"].copy()
        return t

    def _post(self) -> np.ndarray:
        """Post the first half of the pendings (every third one
        partially), then post what does not exist and what was never
        pending."""
        n = self.plan.batch
        t = self._blank(n)
        half = n // 2
        t["flags"] = POST
        t["pending_id_lo"][:half] = self.pending_ids[:half]
        t["amount_lo"] = 0  # the pending amount
        t["amount_lo"][:half:3] = 4
        rest = n - half
        t["pending_id_lo"][half:] = np.where(
            np.arange(rest) % 2 == 0,
            np.uint64(1 << 40) + np.arange(rest, dtype=np.uint64),  # not found
            self.fast[0]["id_lo"][:rest],  # exists, is not pending
        )
        self.posted_ids = self.pending_ids[:half]
        return t

    def _void(self) -> np.ndarray:
        """Void the second half of the pendings, each linked to a plain
        transfer that follows it; then void what was already posted."""
        n = self.plan.batch
        t = self._blank(n)
        half = n // 2
        rest_pending = self.pending_ids[half:]
        pairs = min(len(rest_pending), n // 3)
        i = np.arange(2 * pairs)
        voids, follows = i[::2], i[1::2]
        t["flags"][voids] = VOID | LINKED
        t["pending_id_lo"][voids] = rest_pending[:pairs]
        t["amount_lo"][voids] = 0
        x = self.exact
        t["debit_account_id_lo"][follows] = x[follows % len(x)]
        t["credit_account_id_lo"][follows] = x[(follows + 9) % len(x)]
        t["amount_lo"][follows] = 2
        tail = slice(2 * pairs, n)
        m = n - 2 * pairs
        t["flags"][tail] = VOID
        t["amount_lo"][tail] = 0
        t["pending_id_lo"][tail] = self.posted_ids[np.arange(m) % len(self.posted_ids)]
        return t

    def _balancing(self) -> np.ndarray:
        """balancing_debit: ask for far more than the account holds and
        get what is there; the next ask of the same account finds
        nothing. Debits come from one half of the exact accounts and go
        to the other, so no answer waits on a long line of earlier ones
        (the exact kernel gives up after 64 sweeps and bails to serial)."""
        n = self.plan.batch
        t = self._blank(n)
        half = len(self.exact) // 2
        i = np.arange(n)
        t["debit_account_id_lo"] = self.exact[(i // 2) % half]
        t["credit_account_id_lo"] = self.exact[half + (i // 2) % half]
        t["amount_lo"] = np.where(i % 2 == 0, 1 << 40, 50)
        t["flags"] = TransferFlags.BALANCING_DEBIT
        return t

    def _history(self) -> np.ndarray:
        """Transfers in and out of the history accounts: each leaves a
        balance row for get_account_history."""
        n = self.plan.batch
        t = self._blank(n)
        self._fill_plain(t, self.rng(4))
        h = self.history
        i = np.arange(n)
        t["debit_account_id_lo"][0::2] = h[(i[0::2] // 2) % len(h)]
        t["credit_account_id_lo"][1::2] = h[(i[1::2] // 2 + 1) % len(h)]
        t["amount_lo"] = 1 + i % 9
        return t

    def _duplicates(self) -> np.ndarray:
        """Every id twice in one batch (the second copy now and then with
        another amount): only the serial path may judge these."""
        n = self.plan.batch
        t = self._blank(n)
        self._fill_plain(t, self.rng(5))
        twin = np.arange(1, n, 2)
        t[twin] = t[twin - 1]
        t["amount_lo"][twin[::4]] += np.uint64(1)
        return t

    # reads, asked after all the writes -----------------------------------

    def account_lookups(self):
        n, b = self.plan.accounts, self.plan.batch
        for start in range(1, n + 1, b):
            yield list(range(start, min(start + b, n + 1)))

    def transfer_lookups(self):
        b = self.plan.batch
        rng = self.rng(6)
        all_fast = np.concatenate([t["id_lo"] for t in self.fast])
        sample = rng.choice(all_fast, size=b - 16, replace=False)
        missing = np.uint64(1 << 41) + np.arange(16, dtype=np.uint64)
        yield "fast sample", [int(v) for v in np.concatenate([sample, missing])]
        for name, _route, t in self.special:
            if name in ("chains", "post", "void+chains", "duplicates"):
                yield name, [int(v) for v in t["id_lo"]]

    def account_filters(self):
        """(account_id, flags) for get_account_transfers."""
        rng = self.rng(7)
        touched = np.concatenate(
            [t["debit_account_id_lo"][:64] for t in self.fast[:4]]
        )
        ids = [int(v) for v in rng.choice(touched, 8, replace=False)]
        ids += [int(v) for v in self.exact[:8]]
        ids += [int(v) for v in self.limit[:4]] + [int(v) for v in self.history[:4]]
        for j, a in enumerate(ids):
            flags = (DEBITS | CREDITS, DEBITS, CREDITS | REVERSED,
                     DEBITS | CREDITS | REVERSED)[j % 4]
            yield a, flags

    def history_filters(self):
        for j, a in enumerate(self.history[:8]):
            yield int(a), DEBITS | CREDITS | (REVERSED if j % 2 else 0)
        yield int(self.exact[0]), DEBITS | CREDITS  # no history flag: no rows

    def queries(self):
        """query_transfers filters, two predicates each."""
        yield dict(user_data_64=3, code=2)
        yield dict(user_data_64=11, code=4, flags=1)  # reversed
        yield dict(user_data_64=5, user_data_32=5)
        yield dict(user_data_32=9, code=1, limit=100)
        yield dict(user_data_64=16, user_data_32=16, flags=1, limit=1000)
        yield dict(user_data_64=99, code=1)  # matches nothing


def _no_timestamp(recs: np.ndarray) -> np.ndarray:
    """The reply with the one field the server's clock decides zeroed."""
    out = np.array(recs)  # a writable copy
    out["timestamp"] = 0
    return out


# --- the oracle, in its own process ------------------------------------------


def _codes(pairs) -> np.ndarray:
    return np.array(pairs, dtype=types.EVENT_RESULT_DTYPE).reshape(len(pairs))


def oracle_process(seed: int, plan: Plan, conn, out_path: str) -> None:
    """Apply the same traffic to models/oracle.py and save every expected
    answer under the name the serving side saves its own."""
    from tigerbeetle_tpu.models import oracle as om

    t0 = time.perf_counter()
    traffic = Traffic(seed, plan)
    o = om.Oracle()
    want = {}

    def create(kind, name, events):
        ts = o.prepare(kind, len(events))
        want[name] = _codes(getattr(o, kind)(events, ts))

    def accounts(found) -> np.ndarray:
        return types.batch([om.account_to_numpy(a) for a in found],
                           types.ACCOUNT_DTYPE)

    def transfers(found) -> np.ndarray:
        return types.batch([om.transfer_to_numpy(t) for t in found],
                           types.TRANSFER_DTYPE)

    for b, acc in enumerate(traffic.account_batches()):
        create("create_accounts", f"accounts/{b}",
               [om.account_from_numpy(r) for r in acc])
    conn.send(("accounts applied", time.perf_counter() - t0))
    # The server serialises the fast phase's concurrent sessions; the
    # order it chose comes back from the timestamps it assigned.
    order = conn.recv()
    for b in order:
        create("create_transfers", f"fast/{b}",
               [om.transfer_from_numpy(r) for r in traffic.fast[b]])
    for name, _route, t in traffic.special:
        create("create_transfers", f"special/{name}",
               [om.transfer_from_numpy(r) for r in t])

    # The oracle's records carry the timestamps IT assigned; the
    # comparison zeroes that field on both sides (_no_timestamp).
    for b, ids in enumerate(traffic.account_lookups()):
        want[f"lookup_accounts/{b}"] = accounts(o.lookup_accounts(ids))
    for name, ids in traffic.transfer_lookups():
        want[f"lookup_transfers/{name}"] = transfers(o.lookup_transfers(ids))
    for a, flags in traffic.account_filters():
        want[f"get_account_transfers/{a}/{flags}"] = transfers(
            o.get_account_transfers(a, flags=flags))
    for a, flags in traffic.history_filters():
        rows = o.get_account_history(a, flags=flags)
        bal = np.zeros(len(rows), dtype=types.ACCOUNT_BALANCE_DTYPE)
        for col, name in enumerate(("debits_pending", "debits_posted",
                                    "credits_pending", "credits_posted"), 1):
            bal[name + "_lo"] = [r[col] & types.U64_MAX for r in rows]
            bal[name + "_hi"] = [r[col] >> 64 for r in rows]
        want[f"get_account_history/{a}/{flags}"] = bal
    for q in traffic.queries():
        want["query_transfers/" + json.dumps(q, sort_keys=True)] = transfers(
            o.query_transfers(**q))
    np.savez(out_path, **want)
    conn.send(("done", time.perf_counter() - t0))


# --- the servers ---------------------------------------------------------------


class Servers:
    """The replica processes this run started, a watchdog over them and
    over the run's deadline. A server that dies while the run expects it
    alive ends the run AT ONCE with its stderr — not after the client's
    time-outs — and so does a run that outlives its deadline."""

    def __init__(self, workdir: str, deadline_s: float) -> None:
        self.workdir = workdir
        self.live = {}  # data file path -> Popen, expected alive
        self.started = []  # every data file a server was started on
        self._lock = threading.Lock()
        self._deadline = time.monotonic() + deadline_s
        threading.Thread(target=self._watch, daemon=True).start()

    def start(self, path: str, args: list, env=None):
        """cli.py start as a child; returns (proc, device, seconds to
        `listening`)."""
        from tigerbeetle_tpu import cli

        t0 = time.perf_counter()
        proc, device = cli.spawn_replica(
            args, path, env={**os.environ, **env} if env else None
        )
        with self._lock:
            self.live[path] = proc
            if path not in self.started:
                self.started.append(path)
        return proc, device, time.perf_counter() - t0

    def stop(self, path: str, kill: bool = False) -> None:
        with self._lock:
            proc = self.live.pop(path)
        proc.kill() if kill else proc.terminate()
        proc.wait(timeout=60)

    def stop_all(self) -> None:
        with self._lock:
            procs, self.live = list(self.live.values()), {}
        for p in procs:
            p.kill()
        for p in procs:
            p.wait(timeout=60)

    def show_stderr(self) -> None:
        """What each server this run started wrote to its stderr."""
        from tigerbeetle_tpu import cli

        for path in self.started:
            say(f"--- stderr of the server on {os.path.basename(path)} "
                f"(its end):\n{cli.replica_stderr_tail(path, 3000)}")

    def _watch(self) -> None:
        while True:
            time.sleep(0.25)
            with self._lock:
                dead = [(path, p) for path, p in self.live.items()
                        if p.poll() is not None]
                late = time.monotonic() > self._deadline
            if not dead and not late:
                continue
            for path, p in dead:
                say(f"FAIL: the server on {os.path.basename(path)} exited with "
                    f"code {p.returncode} while the run needed it")
            if late and not dead:
                say("FAIL: the run outlived its deadline")
            self.show_stderr()
            self.stop_all()
            shutil.rmtree(self.workdir, ignore_errors=True)
            os._exit(1)


def free_ports(n: int) -> list:
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def require_tpu(device: dict, what: str) -> None:
    if device["platform"] != "tpu" or device["device_count"] != 1:
        raise Failure(
            f"{what} reports platform={device['platform']} "
            f"device_kind={device['device_kind']!r} "
            f"device_count={device['device_count']}: this check needs one TPU "
            "device per server and has no CPU fallback"
        )


def rebuild_shims() -> None:
    """Drop whatever .so the tree came with (the chip tool copies the
    working tree, stale builds included) and let the program build its
    shims from the committed csrc/*.c on THIS machine."""
    for f in glob.glob(os.path.join(REPO, "csrc", "*.so*")):
        os.unlink(f)
    from tigerbeetle_tpu import native
    from tigerbeetle_tpu.net import codec
    from tigerbeetle_tpu.vsr import header

    loaded = {
        "hostops": native.hostops() is not None,
        "busio": native.busio() is not None,
        "aegis128l": native.aegis128l_mac() is not None,
    }
    if not all(loaded.values()):
        raise Failure(f"native shims did not build and load: {loaded}")
    say(f"shims rebuilt from csrc/*.c and loaded: {', '.join(loaded)}; "
        f"checksum={header.CHECKSUM_ALGORITHM}, "
        f"bus codec={'native (csrc/busio.c)' if codec.enabled() else 'python'}")


def cache_entries() -> tuple:
    """(entries, MiB) in the compile cache the server will use."""
    from tigerbeetle_tpu import compilecache

    d = os.environ.get(compilecache.ENV) or compilecache.CACHE_DIR
    files = glob.glob(os.path.join(d, "*-cache"))
    return len(files), sum(os.path.getsize(f) for f in files) / 2**20


# --- driving and comparing -----------------------------------------------------


class Drive:
    """One client session plus what the run records about it: every
    reply under a name (for the comparison) and the seconds to the first
    reply of each operation kind (compile time, seen from the client)."""

    def __init__(self, addresses: list, request_timeout_s: float) -> None:
        from tigerbeetle_tpu.client import AsyncClient, Client

        self.addresses = addresses
        self.got = {}
        self.first_reply = {}
        self.writes_s = 0.0  # seconds the server took to commit the writes
        # Registering compiles nothing, and a cluster fresh from a
        # start-up view change drops the first try: resend it soon.
        Client.REQUEST_TIMEOUT = min(5.0, request_timeout_s)
        t0 = time.perf_counter()
        self.client = Client(addresses)
        self.first_reply["register"] = time.perf_counter() - t0
        Client.REQUEST_TIMEOUT = AsyncClient.REQUEST_TIMEOUT = request_timeout_s

    def call(self, kind: str, *args, **kw):
        t0 = time.perf_counter()
        out = getattr(self.client, kind)(*args, **kw)
        self.last_s = time.perf_counter() - t0
        self.first_reply.setdefault(kind, self.last_s)
        if kind.startswith("create_"):
            self.writes_s += self.last_s
        return out

    def writes(self, traffic: Traffic) -> None:
        from tigerbeetle_tpu.client import AsyncClient

        plan = traffic.plan
        for b, acc in enumerate(traffic.account_batches()):
            self.got[f"accounts/{b}"] = self.call("create_accounts", acc)
        say(f"  {plan.accounts:,} accounts in {b + 1} batches of "
            f"{plan.batch}: {self.writes_s:.1f} s")

        async def fast():
            async with AsyncClient(self.addresses, sessions=plan.sessions) as ac:
                t1 = time.perf_counter()
                first = asyncio.ensure_future(ac.create_transfers(traffic.fast[0]))
                rest = [asyncio.ensure_future(ac.create_transfers(t))
                        for t in traffic.fast[1:]]
                await asyncio.wait([first, *rest],
                                   return_when=asyncio.FIRST_COMPLETED)
                self.first_reply["create_transfers"] = time.perf_counter() - t1
                return await asyncio.gather(first, *rest)

        t0 = time.perf_counter()
        for b, res in enumerate(asyncio.run(fast())):
            self.got[f"fast/{b}"] = res
        fast_s = time.perf_counter() - t0
        self.writes_s += fast_s
        say(f"  {plan.fast_batches} fast batches of {plan.batch} "
            f"({plan.fast_batches * plan.batch:,} transfers) over "
            f"{plan.sessions} sessions: {fast_s:.1f} s")

    def fast_order(self, traffic: Traffic) -> list:
        """The order in which the server committed the fast batches, from
        the timestamp it gave each batch's first transfer."""
        first_ids = [int(t["id_lo"][0]) for t in traffic.fast]
        recs = self.call("lookup_transfers", first_ids)
        if [int(v) for v in recs["id_lo"]] != first_ids:
            raise Failure("a fast batch's first transfer was not stored")
        return [int(b) for b in np.argsort(recs["timestamp"], kind="stable")]

    def special(self, traffic: Traffic) -> None:
        for name, route, t in traffic.special:
            self.got[f"special/{name}"] = self.call("create_transfers", t)
            say(f"  batch '{name}' ({len(t)} events, must go {route}): "
                f"{len(self.got[f'special/{name}'])} result codes, "
                f"{self.last_s:.1f} s")

    def read_back(self, traffic: Traffic, acknowledged: dict, when: str) -> None:
        """Every balance again, byte for byte what an earlier session was
        told (timestamps included: it is the same ledger)."""
        for b, ids in enumerate(traffic.account_lookups()):
            back = self.call("lookup_accounts", ids)
            if back.tobytes() != acknowledged[f"lookup_accounts/{b}"].tobytes():
                raise Failure(f"{when}, lookup_accounts batch {b} is not what "
                              "was acknowledged before")

    def reads(self, traffic: Traffic) -> None:
        t0 = time.perf_counter()
        for b, ids in enumerate(traffic.account_lookups()):
            self.got[f"lookup_accounts/{b}"] = self.call("lookup_accounts", ids)
        say(f"  lookup_accounts over all {traffic.plan.accounts:,} accounts: "
            f"{time.perf_counter() - t0:.1f} s")
        for name, ids in traffic.transfer_lookups():
            self.got[f"lookup_transfers/{name}"] = self.call("lookup_transfers", ids)
        for a, flags in traffic.account_filters():
            self.got[f"get_account_transfers/{a}/{flags}"] = self.call(
                "get_account_transfers", a, flags=flags)
        for a, flags in traffic.history_filters():
            self.got[f"get_account_history/{a}/{flags}"] = self.call(
                "get_account_history", a, flags=flags)
        for q in traffic.queries():
            self.got["query_transfers/" + json.dumps(q, sort_keys=True)] = (
                self.call("query_transfers", **q))
        say(f"  all reads: {time.perf_counter() - t0:.1f} s")


def _row_bytes(recs: np.ndarray) -> np.ndarray:
    return np.frombuffer(recs.tobytes(), np.uint8).reshape(len(recs), -1)


def compare(got: dict, want_path: str) -> None:
    """Every reply against the oracle's, byte for byte but for the
    timestamps. Any difference fails the run."""
    want = np.load(want_path)
    if set(got) != set(want.files):
        raise Failure(f"replies and oracle answers differ in kind: "
                      f"{sorted(set(got) ^ set(want.files))[:8]}")
    counts = {}
    mismatches = []
    for name in sorted(got):
        g, w = got[name], want[name]
        if "timestamp" in (g.dtype.names or ()):
            g, w = _no_timestamp(g), _no_timestamp(w)
        kind = name.split("/")[0]
        counts[kind] = counts.get(kind, 0) + len(w)
        if g.dtype != w.dtype or g.tobytes() != w.tobytes():
            rows = min(len(g), len(w))
            differ = np.nonzero(
                (_row_bytes(g[:rows]) != _row_bytes(w[:rows])).any(axis=1))[0]
            mismatches.append(
                f"{name}: {len(g)} rows against the oracle's {len(w)}, first "
                f"difference at row {int(differ[0]) if len(differ) else rows}")
    say("  compared with models/oracle.py: " + ", ".join(
        f"{n:,} {kind} rows" for kind, n in counts.items()))
    if mismatches:
        raise Failure(f"{len(mismatches)} replies differ from the oracle:\n  "
                      + "\n  ".join(mismatches[:12]))
    say(f"  0 mismatches in {len(got)} replies")


def scrape(port: int, path: str = "/metrics") -> str:
    # /device may compile on the server's event loop: wait it out.
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=900) as r:
        return r.read().decode()


def keep(text: str, name: str) -> None:
    """The server's whole registry after the traffic, too long for the
    end of the output: chiprun_out/ is what the chip tool brings back."""
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, name), "w") as f:
        f.write(text)


def metric(text: str, family: str, event: str) -> float:
    for line in text.splitlines():
        if line.startswith(f'{family}{{event="{event}"}} '):
            return float(line.rsplit(" ", 1)[1])
    return 0


def commit_paths(text: str) -> dict:
    """How many batches took which commit path (sm.route.* on /metrics)."""
    return {r: int(metric(text, "tbtpu_events_total", f"sm.route.{r}_batches"))
            for r in ("fast", "exact", "serial", "bail")}


def check_routes(text: str, traffic: Traffic) -> None:
    """Fail unless every route the chip is supposed to take was taken."""
    spans = {
        e: int(metric(text, "tbtpu_span_seconds_count", e))
        for e in (
            "device.register_accounts", "device.read_balances",
            "device.step.create_transfers_fast",
            "device.step.create_transfers_exact",
        )
    }
    routes = commit_paths(text)
    # The longest call of an entry is, on a cold start, its compile.
    say("  device routes taken (calls, longest call; from /metrics): " + ", ".join(
        f"{e.removeprefix('device.').removeprefix('step.')}={n} "
        f"({metric(text, 'tbtpu_span_max_seconds', e):.1f} s)"
        for e, n in spans.items()))
    say(f"  commit paths (sm.route.* counters): {routes}")
    missing = [e for e, n in spans.items() if n == 0]
    if missing:
        raise Failure(f"device routes not taken: {missing}")
    sent = {r: sum(1 for _n, route, _t in traffic.special if route == r)
            for r in ("fast", "exact", "serial")}
    sent["fast"] += len(traffic.fast)
    sent["bail"] = 0
    if routes != sent:
        raise Failure(f"commit paths taken {routes} are not the ones the "
                      f"traffic was built for {sent}")


def start_oracle(seed: int, plan: Plan, workdir: str):
    ctx = multiprocessing.get_context("spawn")
    parent, child = ctx.Pipe()
    want_path = os.path.join(workdir, "oracle.npz")
    # daemon: a run that fails must not leave the oracle behind
    proc = ctx.Process(target=oracle_process, daemon=True,
                       args=(seed, plan, child, want_path))
    proc.start()
    child.close()
    return proc, parent, want_path


def finish_oracle(proc, conn, what: str) -> float:
    """Wait for the oracle's next message; a dead oracle is a failure."""
    while not conn.poll(0.5):
        if not proc.is_alive():
            raise Failure(f"the oracle process died before '{what}' "
                          f"(exit code {proc.exitcode})")
    try:
        tag, seconds = conn.recv()
    except EOFError:
        raise Failure(f"the oracle process died before '{what}'") from None
    assert tag == what, (tag, what)
    return seconds


def format_file(path: str, plan: Plan, replica: int, replica_count: int) -> None:
    subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu.cli", "format",
         f"--config={plan.config}", f"--replica={replica}",
         f"--replica-count={replica_count}", path],
        check=True, cwd=REPO, stdout=subprocess.DEVNULL,
    )


def drive_and_compare(drive: Drive, traffic: Traffic, oracle) -> None:
    proc, conn, want_path = oracle
    drive.writes(traffic)
    s = finish_oracle(proc, conn, "accounts applied")
    say(f"  oracle (own process) applied the accounts in {s:.1f} s, meanwhile")
    order = drive.fast_order(traffic)
    conn.send(order)
    moved = sum(1 for i, b in enumerate(order) if i != b)
    say(f"  commit order of the fast batches read back from the server's "
        f"timestamps ({moved} of {len(order)} not where they were sent)")
    drive.special(traffic)
    drive.reads(traffic)
    s = finish_oracle(proc, conn, "done")
    proc.join(timeout=60)
    say(f"  oracle finished after {s:.1f} s")
    compare(drive.got, want_path)


# --- phase: one replica on one chip ------------------------------------------


def one_chip(seed: int, plan: Plan, servers: Servers,
             expect=require_tpu) -> tuple:
    workdir = servers.workdir
    rebuild_shims()
    traffic = Traffic(seed, plan)
    oracle = start_oracle(seed, plan, workdir)
    path = os.path.join(workdir, "0.tigerbeetle")
    format_file(path, plan, 0, 1)
    port, mport = free_ports(2)
    args = [f"--addresses=127.0.0.1:{port}", "--replica=0",
            f"--config={plan.config}", "--backend=jax",
            f"--metrics-port={mport}"]
    entries_before, mib = cache_entries()

    say(f"first start ({plan.config} config, compile cache holds "
        f"{entries_before} entries, {mib:.0f} MiB):")
    _proc, device, boot_1 = servers.start(path, args)
    say(f"  listening after {boot_1:.1f} s: {device}")
    expect(device, "the server")
    drive = Drive([("127.0.0.1", port)], plan.request_timeout_s)
    drive_and_compare(drive, traffic, oracle)
    metrics, lifecycle = scrape(mport), scrape(mport, "/lifecycle")
    keep(metrics, f"chip_smoke_metrics_{device['platform']}.txt")
    keep(lifecycle, f"chip_smoke_lifecycle_{device['platform']}.json")
    check_routes(metrics, traffic)
    window = json.loads(lifecycle)["flat"]
    say("  commit window (from /lifecycle): depth "
        f"{window.get('commit_depth')}, batches in flight mean "
        f"{window.get('commit_inflight_mean')} max "
        f"{window.get('commit_inflight_max')}")
    t0 = time.perf_counter()
    status = json.loads(scrape(mport, "/device"))
    say(f"  one /device scrape after the traffic: {time.perf_counter() - t0:.1f} s "
        f"({len(status['entries'])} cost rows, backend {status['backend']})")
    entries_after, mib = cache_entries()
    if entries_after == 0:
        raise Failure("the server left nothing in the compile cache")
    drive.client.close()
    servers.stop(path)

    say(f"second start, same data file (compile cache holds {entries_after} "
        f"entries, {mib:.0f} MiB):")
    _proc, device_2, boot_2 = servers.start(path, args)
    say(f"  listening after {boot_2:.1f} s: {device_2}")
    expect(device_2, "the restarted server")
    again = Drive([("127.0.0.1", port)], plan.request_timeout_s)
    again.read_back(traffic, drive.got, "after the restart")
    say(f"  every balance read back as acknowledged before the restart "
        f"({plan.accounts:,} accounts, byte for byte)")
    name, ids = next(traffic.transfer_lookups())
    if again.call("lookup_transfers", ids).tobytes() != (
            drive.got[f"lookup_transfers/{name}"].tobytes()):
        raise Failure("after the restart, lookup_transfers differs")
    again.client.close()
    servers.stop(path)

    say("seconds to `listening` and to the first reply of each kind "
        "(client side; one run, not a benchmark):")
    say(f"  first start : listening {boot_1:.1f}; " + ", ".join(
        f"{k} {v:.2f}" for k, v in drive.first_reply.items()))
    say(f"  second start: listening {boot_2:.1f}; " + ", ".join(
        f"{k} {v:.2f}" for k, v in again.first_reply.items()))
    # The second start replays every write from its WAL before it
    # listens — the same kernels in the same order — so its seconds to
    # `listening` are the first start's seconds to come up AND commit
    # those writes, less whatever the first start spent compiling.
    starts = {
        "cache_was_cold": entries_before == 0,
        "first": boot_1 + drive.writes_s,
        "second": boot_2,
    }
    say(f"  the first start came up and committed the writes in "
        f"{starts['first']:.1f} s, compiling as it went; the second came up "
        f"in {starts['second']:.1f} s, the same writes replayed from its WAL "
        f"(it added {cache_entries()[0] - entries_after} cache entries)")
    return device, starts


def check_restart_quicker(starts: dict) -> None:
    """The compile cache's check, for main() only: on the chip a cold
    start compiles for minutes, so a second start that reads the cache
    must be far quicker over the same writes. (On the CPU a compile is
    too short to tell, and so is a first start that found a warm cache.)"""
    if not starts["cache_was_cold"]:
        say("  (the cache was warm before the first start, so both starts "
            "read it: the quicker-restart check needs a cold cache)")
    elif starts["second"] >= starts["first"] / 2:
        raise Failure("the second start was not far quicker than the cold "
                      "first: the compile cache is not working")


# --- phase: three replicas on a four-chip host -----------------------------


def chips_held(pid: int) -> set:
    """The /dev/vfio/<n> chips a process has open. From inside, every
    one-chip process sees the same device (id 0, coords 0,0,0): only the
    device node tells the chips apart."""
    held = set()
    for fd in os.listdir(f"/proc/{pid}/fd"):
        try:
            target = os.readlink(f"/proc/{pid}/fd/{fd}")
        except OSError:
            continue  # closed while we looked
        if target.startswith("/dev/vfio/") and target != "/dev/vfio/vfio":
            held.add(target)
    return held


def three_replicas(seed: int, plan: Plan, servers: Servers,
                   expect=require_tpu, distinct=chips_held) -> None:
    workdir = servers.workdir
    rebuild_shims()
    traffic = Traffic(seed, plan)
    oracle = start_oracle(seed, plan, workdir)
    ports = free_ports(6)
    addresses = ",".join(f"127.0.0.1:{p}" for p in ports[:3])
    paths = [os.path.join(workdir, f"{i}.tigerbeetle") for i in range(3)]
    for i, path in enumerate(paths):
        format_file(path, plan, i, 3)

    def start(i: int):
        return servers.start(
            paths[i],
            [f"--addresses={addresses}", f"--replica={i}",
             f"--config={plan.config}", "--backend=jax",
             f"--metrics-port={ports[3 + i]}"],
            env={**CHIP_ENV, "TPU_VISIBLE_CHIPS": str(i)},
        )

    say(f"three replicas ({plan.config} config), one chip each through "
        f"{CHIP_ENV} and TPU_VISIBLE_CHIPS=<i>:")
    with ThreadPoolExecutor(3) as pool:
        started = list(pool.map(start, range(3)))
    held = []
    for i, (proc, device, boot) in enumerate(started):
        say(f"  replica {i} listening after {boot:.1f} s: {device}")
        expect(device, f"replica {i}")
        held.append(distinct(proc.pid))
        say(f"  replica {i} (pid {proc.pid}) holds {sorted(held[-1])}")
    if any(len(h) != 1 for h in held) or len(set().union(*held)) != 3:
        raise Failure(f"the three replicas do not hold three distinct chips: {held}")

    drive = Drive([("127.0.0.1", p) for p in ports[:3]], plan.request_timeout_s)
    drive_and_compare(drive, traffic, oracle)
    routes = {i: commit_paths(scrape(ports[3 + i])) for i in range(3)}
    say(f"  commit paths per replica: {routes}")
    if any(r["bail"] for r in routes.values()):
        raise Failure("a replica bailed out of a device kernel")

    def who_leads(replicas) -> dict:
        """replica -> (view, is_primary), as each says on /cluster."""
        status = {i: json.loads(scrape(ports[3 + i], "/cluster")) for i in replicas}
        return {i: (c["view"], c["is_primary"]) for i, c in status.items()}

    before = who_leads(range(3))
    primaries = [i for i, (_view, leads) in before.items() if leads]
    if len(primaries) != 1:
        raise Failure(f"expected one primary, /cluster says {before}")
    primary = primaries[0]
    survivors = [i for i in range(3) if i != primary]
    say(f"killing the primary (replica {primary} in view {before[primary][0]}, "
        f"SIGKILL; /cluster said {before}):")
    drive.client.close()
    servers.stop(paths[primary], kill=True)
    # Everything is compiled by now, and the old primary's address only
    # refuses connections: a short time-out walks the client on to the
    # new primary instead of waiting on a backup that cannot answer yet.
    t0 = time.perf_counter()
    survivor = Drive([("127.0.0.1", p) for p in ports[:3]], 10.0)
    survivor.call("lookup_accounts", [1])
    say(f"  first reply from the remaining two after "
        f"{time.perf_counter() - t0:.1f} s; /cluster now says "
        f"{who_leads(survivors)}")
    survivor.read_back(traffic, drive.got, "after the primary was killed")
    after = who_leads(survivors)
    if sorted(leads for _view, leads in after.values()) != [0, 1] or any(
            view <= before[primary][0] for view, _leads in after.values()):
        raise Failure(f"no new primary in a later view: {after}")
    say(f"  every balance ({plan.accounts:,} accounts) read back byte for byte "
        f"from a quorum without the old primary")
    survivor.client.close()
    servers.stop_all()


def probe_devices() -> dict:
    """What a fresh process sees when nothing narrows its view — run
    after every replica has let go of its chip (this process stays off
    JAX, and a chip belongs to one process at a time)."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, json; d = jax.devices(); print(json.dumps({"
         "'platform': d[0].platform, 'device_kind': d[0].device_kind, "
         "'device_count': len(d)}))"],
        check=True, capture_output=True, text=True, cwd=REPO,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="the traffic is a pure function of this")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: three replicas on a four-chip host, one chip "
                         "each, and no one-chip phase")
    args = ap.parse_args(argv)
    os.chdir(REPO)  # the children run `python -m tigerbeetle_tpu.cli` from here
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    plan = ONE_CHIP if args.chips == 1 else THREE_REPLICAS
    servers = Servers(workdir, plan.deadline_s)
    t0 = time.perf_counter()
    passed = False
    try:
        if args.chips == 1:
            device, starts = one_chip(args.seed, plan, servers)
            check_restart_quicker(starts)
        else:
            three_replicas(args.seed, plan, servers)
            device = probe_devices()
            say(f"the host, once the replicas let go: {device}")
            if device["platform"] != "tpu" or device["device_count"] != 4:
                raise Failure(f"expected a four-chip TPU host, found {device}")
        passed = True
    except Failure as e:
        say(f"FAIL: {e}")
        return 1
    finally:  # also on an error nobody foresaw: that one keeps its traceback
        if not passed:
            servers.show_stderr()
        servers.stop_all()
        shutil.rmtree(workdir, ignore_errors=True)
    say(f"all phases passed in {time.perf_counter() - t0:.0f} s")
    assert "jax" not in sys.modules, "this process must stay off JAX"
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["device_count"],
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
