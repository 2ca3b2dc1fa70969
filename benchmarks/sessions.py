"""Client sessions for the benchmark: real TCP, one request in flight each.

A copy of `tigerbeetle_tpu/testing/loadgen.py`'s `_Session` and `LoadGen`
cut to what a cell needs (no churn, no identity rotation) and mended
where the original could not serve as a yardstick:

- the traffic comes from a seeded generator handed in, never from a seed
  fixed here;
- every request is kept as a record (its operation, sent, done, the
  reply's body, and the `op` of its header: the place the server
  committed it at), so the
  window is cut out afterwards by the times of the replies, warm-up never
  mixes into a latency list, and the replay can follow the server's order;
- a reply is kept, not counted: what it says is judged later against the
  plain reference (`accepted_tx` in the original never reads a code).

A closed loop: each session sends its next request when the reply lands
(the next one is built while the reply is awaited, so a session's think
time is the seal and the send). A request is an operation and a body:
what the generator names, `create_transfers` or `lookup_accounts`
(`OPERATIONS`). The original's open loop is not copied: no cell offers
load at a fixed rate yet (PERF.md, Open questions).

Against a cluster a session holds one connection, to the replica it
believes primary: a reply only comes over a connection the PRIMARY holds
for this client (a backup forwards the request and the answer is lost).
The hello's PONG_CLIENT carries the replica's view, so one read steers a
session to `view % replicas`; on a time-out or a lost connection it moves
to the next address and resends the SAME request number, as
`tigerbeetle_tpu.client.Client` does. A request's latency runs from its
FIRST send. With one address none of this happens: the session resends to
it for ever.
"""

from __future__ import annotations

import asyncio
import dataclasses
import secrets
import time
from typing import Callable, List, Optional

from tigerbeetle_tpu.client import BUSY_RETRY_MAX, busy_backoff_s
from tigerbeetle_tpu.net.bus import read_message
from tigerbeetle_tpu.vsr import header as hdr
from tigerbeetle_tpu.vsr.header import Command, Operation


# What a generator may name, and the protocol's number for it.
OPERATIONS = {"create_transfers": Operation.CREATE_TRANSFERS,
              "lookup_accounts": Operation.LOOKUP_ACCOUNTS}


@dataclasses.dataclass
class Record:
    """One request of one session."""

    session: int
    seq: int  # the session's own order: request (session, seq) of the generator
    operation: str  # a key of OPERATIONS
    events: int  # the events of a create_transfers, the ids of a lookup_accounts
    sent: float = 0.0  # 0.0: never sent
    done: float = 0.0  # 0.0: never answered
    reply: Optional[bytes] = None  # the reply's body (EVENT_RESULT pairs, or ACCOUNT rows)
    view: int = -1  # the view in the reply's header
    # The op in the reply's header: the primary numbers the prepares in the one order it
    # commits them (a resend answered from the client table carries the same one), so
    # the answered requests sorted by it are the order the ledger was written in.
    op: int = -1

    @property
    def latency(self) -> float:
        return self.done - self.sent


class _Steered(Exception):
    """A PONG_CLIENT named another replica as the view's primary."""


class Session:
    """One VSR client session on one TCP connection at a time."""

    CONNECT_RETRIES = 40

    def __init__(self, addresses: list, request_timeout: float, cluster: int = 0):
        self.addresses = list(addresses)
        self.target = 0  # the address this session believes primary
        self.request_timeout = request_timeout
        self.cluster = cluster
        self.client_id = secrets.randbits(127) | 1  # a session id, not traffic
        self.request = 0
        self.reader = self.writer = None
        self.resends = 0
        self.moves = 0  # times the session went on to another address
        self.steered = 0  # times a hello's answer named another replica as primary
        self.busy = 0
        self.view = -1  # of the last reply
        self.replica = -1  # that sent it
        self._refused = -1  # the address that last refused a connection: never steered to

    async def connect(self) -> None:
        backoff, last = 0.05, None
        for _ in range(self.CONNECT_RETRIES):
            try:
                self.reader, self.writer = await asyncio.open_connection(
                    *self.addresses[self.target], limit=1 << 21)
                hello = hdr.make_sealed(Command.PING_CLIENT, self.cluster,
                                        client=self.client_id)
                self.writer.write(hello.to_bytes())
                await self.writer.drain()
                return
            except OSError as e:
                last = e
                self.reader = self.writer = None
                self._refused = self.target
                self._move()  # a dead listener: the next address (the same, of one)
                await asyncio.sleep(backoff)
                backoff = min(backoff * 2, 1.0)
        raise ConnectionError(f"session could not connect: {last!r}")

    def _move(self) -> None:
        if len(self.addresses) > 1:
            self.target = (self.target + 1) % len(self.addresses)
            self.moves += 1

    def close(self) -> None:
        if self.writer is not None and self.writer.transport is not None:
            self.writer.transport.abort()
        self.reader = self.writer = None

    async def _read_reply(self, request: int):
        while True:
            msg = await read_message(self.reader)
            if msg is None:
                raise ConnectionResetError("connection lost")
            h = msg.header
            if h["command"] == Command.EVICTION and h["client"] == self.client_id:
                raise ConnectionError("session evicted")
            if h["command"] == Command.PONG_CLIENT and h["client"] == self.client_id:
                primary = int(h["view"]) % len(self.addresses)
                if primary not in (self.target, self._refused):
                    self.target = primary
                    self.steered += 1
                    raise _Steered
                continue
            if h["client"] != self.client_id or h["request"] != request:
                continue  # a reply to an earlier send
            if h["command"] in (Command.REPLY, Command.BUSY):
                return msg

    async def roundtrip(self, operation: int, body: bytes, on_sent=None):
        """Send, absorb BUSY with the client's own backoff, resend on a
        time-out or a lost connection (the same request number: the
        primary answers a duplicate from its table), to the next address
        where there is one."""
        self.request += 1
        request = self.request
        frame = hdr.make_sealed(
            Command.REQUEST, self.cluster, body=body, client=self.client_id,
            request=request, operation=operation).to_bytes()
        busy_retries = sends = 0
        while True:
            if self.writer is None:
                await self.connect()
            try:
                self.writer.write(frame)
                if on_sent is not None and sends == 0:
                    on_sent()
                sends += 1
                await self.writer.drain()
                reply = await asyncio.wait_for(self._read_reply(request),
                                               self.request_timeout)
            except _Steered:
                self.close()  # the hello's answer: no time was lost, nothing counted
                continue
            except asyncio.TimeoutError:
                self.resends += 1
                if sends > 8:
                    raise
                if len(self.addresses) > 1:
                    self.close()
                    self._move()
                continue
            except (OSError, ConnectionResetError):
                self.resends += 1
                self.close()
                self._move()
                if sends > 8:
                    raise
                continue
            if reply.header["command"] == Command.BUSY:
                busy_retries += 1
                self.busy += 1
                if busy_retries > BUSY_RETRY_MAX:
                    raise TimeoutError("persistently BUSY")
                await asyncio.sleep(busy_backoff_s(busy_retries))
                continue
            self.view, self.replica = int(reply.header["view"]), int(reply.header["replica"])
            self._refused = -1
            return reply

    async def register(self) -> None:
        await self.roundtrip(Operation.REGISTER, b"")


class Load:
    """The sessions of one run and every request they made.

    `make(session, seq)` returns that request as (operation, body): a key
    of OPERATIONS and a structured array, one element an event or an id; it
    is called in each session's own order."""

    def __init__(self, addresses: list, sessions: int, make: Callable, request_timeout: float):
        self.make = make
        self.sessions = [Session(addresses, request_timeout)
                         for _ in range(sessions)]
        self.records: List[Record] = []
        self.completed = 0  # create_transfers requests answered: what prefill counts
        self.stopping = False
        self.errors: List[str] = []
        self._progress = asyncio.Event()

    async def _closed(self, s: int) -> None:
        sess = self.sessions[s]
        seq = 0
        operation, body = self.make(s, seq)
        while not self.stopping:
            rec = Record(s, seq, operation, len(body))
            self.records.append(rec)
            call = asyncio.ensure_future(sess.roundtrip(
                OPERATIONS[operation], body.tobytes(),
                on_sent=lambda r=rec: self._stamp(r)))
            await asyncio.sleep(0)  # let the frame go out first
            seq += 1
            operation, body = self.make(s, seq)  # built while the reply is awaited
            if not await self._finish(rec, call):
                return

    def _stamp(self, rec: Record) -> None:
        rec.sent = time.perf_counter()

    async def _finish(self, rec: Record, call) -> bool:
        try:
            reply = await call
        except (OSError, ConnectionError, asyncio.TimeoutError, TimeoutError) as e:
            self.errors.append(f"session {rec.session} request {rec.seq}: {e!r}")
            self._progress.set()
            return False
        rec.done = time.perf_counter()
        rec.reply = reply.body
        rec.view = int(reply.header["view"])
        rec.op = int(reply.header["op"])
        self.completed += rec.operation == "create_transfers"
        self._progress.set()
        return True

    # lifecycle -----------------------------------------------------------

    async def start(self) -> None:
        async def one(sess):
            await sess.connect()
            await sess.register()

        await asyncio.gather(*[one(s) for s in self.sessions])
        self.tasks = [asyncio.ensure_future(self._closed(s))
                      for s in range(len(self.sessions))]

    def primary(self) -> int:
        """The replica that answered most sessions' last request."""
        said = [s.replica for s in self.sessions if s.replica >= 0]
        return max(set(said), key=said.count) if said else 0

    async def until_completed(self, batches: int) -> None:
        """Returns once `batches` create_transfers requests have been
        answered (or a session has given up: the caller reads `errors`)."""
        while self.completed < batches and not self.errors:
            self._progress.clear()
            await self._progress.wait()

    async def drain(self, timeout: float) -> None:
        """No new requests; wait for the ones in flight up to `timeout` seconds."""
        self.stopping = True
        done, pending = await asyncio.wait(self.tasks, timeout=timeout)
        for t in pending:
            t.cancel()
        for t in done:
            if t.exception() is not None:
                self.errors.append(repr(t.exception()))
        for s in self.sessions:
            s.close()
