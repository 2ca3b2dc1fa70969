"""What the harness starts for a configuration's `replica_count`, and how a
session behaves when it has several addresses. Fast: no server is started.

For one replica the `format` and `start` argument lists and the child's
environment are what they were before the harness could start a cluster,
to the byte (golden lists: the accepted one-chip cells must read what they
read). For three they are what upstream's documented cluster takes, with
one chip made visible to each child.

    python -m pytest benchmarks/tests/test_launch.py -q -p no:cacheprovider
"""

import asyncio
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import launch  # noqa: E402
from benchmarks.sessions import Load, Session  # noqa: E402

START = {"config": "production", "backend": "jax"}
CLI = [sys.executable, "-m", "tigerbeetle_tpu.cli"]


@pytest.mark.parametrize("replica,count,want", [
    (0, 1, ["format", "--config=production", "--replica=0", "--replica-count=1", "/w/0.tigerbeetle"]),
    (2, 3, ["format", "--config=production", "--replica=2", "--replica-count=3", "/w/2.tigerbeetle"]),
])
def test_format_arguments(replica, count, want):
    path = f"/w/{replica}.tigerbeetle"
    assert launch.format_args(path, "production", replica, count) == CLI + want


@pytest.mark.parametrize("ports,replica,mport,want", [
    # one replica, untraced and traced: today's lists, the metrics port before the path
    ([3001], 0, 0, ["--addresses=127.0.0.1:3001", "--replica=0", "--config=production",
                    "--backend=jax", "/w/0.tigerbeetle"]),
    ([3001], 0, 3002, ["--addresses=127.0.0.1:3001", "--replica=0", "--config=production",
                       "--backend=jax", "--metrics-port=3002", "/w/0.tigerbeetle"]),
    # a cluster: every address to every replica, each its own index and metrics port
    ([3001, 3002, 3003], 1, 0,
     ["--addresses=127.0.0.1:3001,127.0.0.1:3002,127.0.0.1:3003", "--replica=1",
      "--config=production", "--backend=jax", "/w/1.tigerbeetle"]),
    ([3001, 3002, 3003], 2, 3006,
     ["--addresses=127.0.0.1:3001,127.0.0.1:3002,127.0.0.1:3003", "--replica=2",
      "--config=production", "--backend=jax", "--metrics-port=3006", "/w/2.tigerbeetle"]),
])
def test_start_arguments(ports, replica, mport, want):
    assert launch.start_args(ports, replica, START, mport, f"/w/{replica}.tigerbeetle") == want


@pytest.mark.parametrize("replica,count,want", [
    (0, 1, {}),  # no chip-visibility variable: the one replica takes the chip it finds
    (0, 3, {"TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1", "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_VISIBLE_CHIPS": "0"}),
    (2, 3, {"TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1", "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_VISIBLE_CHIPS": "2"}),
])
def test_the_environment_added_to_a_child(replica, count, want):
    assert launch.chip_env(replica, count) == want


def test_the_child_environment_is_todays_for_one_replica(tmp_path, monkeypatch):
    """What `Server.start` hands Popen: the same three things over os.environ as before."""
    seen = {}

    class Done(Exception):
        pass

    def popen(argv, **kw):
        seen.update(argv=argv, **kw)
        raise Done

    monkeypatch.setattr(launch.subprocess, "Popen", popen)
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "7")  # the caller's wins
    server = launch.Server(launch.Watchdog(str(tmp_path), 60.0))
    with pytest.raises(Done):
        server.start(["--replica=0", "x"], launch.chip_env(0, 1))
    server.stop()  # (disarms its watchdog, which would end this process at the deadline)
    assert seen["argv"] == [sys.executable, launch.SERVE, "--replica=0", "x"]
    assert seen["cwd"] == launch.REPO
    assert seen["env"] == {**os.environ, "TMPDIR": str(tmp_path)}
    assert server.stderr_path == os.path.join(str(tmp_path), "server.stderr")
    assert not any(k.startswith("TPU_") for k in set(seen["env"]) - set(os.environ))


@pytest.mark.parametrize("held,sound", [
    ([{"/dev/vfio/0"}, {"/dev/vfio/1"}, {"/dev/vfio/2"}], True),
    ([{"/dev/vfio/0"}, {"/dev/vfio/0"}, {"/dev/vfio/2"}], False),  # two replicas on one chip
    ([{"/dev/vfio/0", "/dev/vfio/1"}, {"/dev/vfio/2"}, {"/dev/vfio/3"}], False),  # one took two
    ([set(), {"/dev/vfio/1"}, {"/dev/vfio/2"}], False),  # one holds none: a CPU fallback
])
def test_a_cluster_must_hold_distinct_chips(held, sound):
    if sound:
        launch.require_distinct_chips(held)
    else:
        with pytest.raises(launch.Failure):
            launch.require_distinct_chips(held)


# --- sessions against several addresses ---------------------------------------------------


class FakeReplica:
    """Listens, answers the hello with its view, and either drops every
    request (a backup whose forwarded answer is lost) or answers it."""

    def __init__(self, index: int, view: int, answers: bool, pongs: bool = True):
        self.index, self.view, self.answers, self.pongs = index, view, answers, pongs
        self.requests = []  # (client, request number) of every REQUEST that arrived

    async def start(self):
        self.server = await asyncio.start_server(self.serve, "127.0.0.1", 0)
        return self.server.sockets[0].getsockname()[:2]

    async def serve(self, reader, writer):
        from tigerbeetle_tpu.net.bus import read_message
        from tigerbeetle_tpu.vsr import header as hdr
        from tigerbeetle_tpu.vsr.header import Command

        try:
            while (msg := await read_message(reader)) is not None:
                h = msg.header
                if h["command"] == Command.PING_CLIENT and self.pongs:
                    writer.write(hdr.make_sealed(
                        Command.PONG_CLIENT, 0, replica=self.index, view=self.view,
                        client=h["client"]).to_bytes())
                elif h["command"] == Command.REQUEST:
                    self.requests.append((int(h["client"]), int(h["request"])))
                    if self.answers:
                        writer.write(hdr.make_sealed(
                            Command.REPLY, 0, body=b"", view=self.view, client=h["client"],
                            request=h["request"], replica=self.index,
                            operation=h["operation"]).to_bytes())
                await writer.drain()
        except (ConnectionError, OSError):
            pass


def drive_one_request(replicas, timeout=0.3):
    async def go():
        addresses = [await r.start() for r in replicas]
        session, stamps = Session(addresses, timeout), []
        from tigerbeetle_tpu.vsr.header import Operation

        reply = await session.roundtrip(Operation.CREATE_TRANSFERS, b"",
                                        on_sent=lambda: stamps.append(1))
        session.close()
        for r in replicas:
            r.server.close()
        return session, reply, stamps

    return asyncio.run(go())


def test_a_session_moves_on_from_an_address_that_drops_requests_and_resends_the_same_number():
    # no hello answers: only the time-out can move the session
    replicas = [FakeReplica(0, 1, answers=False, pongs=False),
                FakeReplica(1, 1, answers=True, pongs=False),
                FakeReplica(2, 1, answers=False, pongs=False)]
    session, reply, stamps = drive_one_request(replicas)
    assert replicas[0].requests == [(session.client_id, 1)]  # sent, dropped
    assert replicas[1].requests == [(session.client_id, 1)]  # the SAME request number
    assert replicas[2].requests == []
    assert (session.resends, session.moves, session.target) == (1, 1, 1)
    assert (session.view, session.replica) == (1, 1) and int(reply.header["request"]) == 1
    assert stamps == [1]  # stamped at the FIRST send only: latency runs from there


def test_a_hello_answer_steers_a_session_to_the_views_primary_without_a_time_out():
    replicas = [FakeReplica(i, 2, answers=(i == 2)) for i in range(3)]
    session, _reply, stamps = drive_one_request(replicas, timeout=30.0)
    assert (session.target, session.steered, session.resends, session.moves) == (2, 1, 0, 0)
    assert replicas[2].requests == [(session.client_id, 1)] and stamps == [1]


def test_a_session_with_one_address_stays_on_it():
    only = FakeReplica(0, 5, answers=True)  # whatever the view: there is one address
    session, _reply, _stamps = drive_one_request([only])
    assert (session.target, session.steered, session.moves, session.resends) == (0, 0, 0, 0)


def test_load_names_the_replica_that_answers_most_sessions():
    load = Load([("127.0.0.1", 1)], 3, lambda s, k: None, 1.0)
    assert load.primary() == 0  # nothing answered yet: the one replica a one-chip cell has
    for s, replica in zip(load.sessions, (1, 1, 2)):
        s.replica = replica
    assert load.primary() == 1
