"""Starting, watching and stopping the servers a run drives: as many as
the configuration's `replica_count` says, one chip each.

Copies of `chip_smoke.py`'s launcher pieces (`Servers`, `free_ports`,
`require_tpu`, `format_file`, and for a cluster `CHIP_ENV`, `chips_held`,
`probe_devices`), changed in one way: the child is `benchmarks/serve.py`,
which wraps `cli.py start` so that the process holding the chip can be
asked for its trace, its compile count and its memory
(`cli.spawn_replica` hard-codes `-m tigerbeetle_tpu.cli`). The parent
never imports JAX: a device is the one a child names on its `listening`
line, and the chips of a cluster are told apart from outside.
"""

from __future__ import annotations

import json
import os
import queue
import shutil
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVE = os.path.join(REPO, "benchmarks", "serve.py")


def say(msg: str) -> None:
    """Progress goes to stderr: stdout's last line is the result."""
    print(msg, file=sys.stderr, flush=True)


class Failure(Exception):
    """The run cannot give a result; main() exits non-zero with this."""


def free_ports(n: int) -> list:
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


# Each replica of a cluster gets ONE chip from outside, through libtpu's
# process-visibility environment (the program has no device option). Left
# alone, the first process claims all four chips and the next one fails with
# "The TPU is already in use"; TPU_VISIBLE_CHIPS alone is not enough on
# libtpu 0.0.34 (chip_smoke.py, PR 21).
CHIP_ENV = {"TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1", "TPU_PROCESS_BOUNDS": "1,1,1"}


def format_args(path: str, config: str, replica: int, replica_count: int) -> list:
    return [sys.executable, "-m", "tigerbeetle_tpu.cli", "format", f"--config={config}",
            f"--replica={replica}", f"--replica-count={replica_count}", path]


def start_args(ports: list, replica: int, start: dict, metrics_port: int, path: str) -> list:
    """What `cli.py start` is given: every replica's address, which of them
    this one is, and the metrics port in the traced run only (0: the tracer
    stays off)."""
    args = ["--addresses=" + ",".join(f"127.0.0.1:{p}" for p in ports),
            f"--replica={replica}", f"--config={start['config']}",
            f"--backend={start['backend']}"]
    if metrics_port:
        args.append(f"--metrics-port={metrics_port}")
    return [*args, path]


def chip_env(replica: int, replica_count: int) -> dict:
    """Added to a child's environment: nothing for the one replica of a
    one-chip cell (it takes the chip it finds), chip `replica` and no other
    for a replica of a cluster."""
    if replica_count == 1:
        return {}
    return {**CHIP_ENV, "TPU_VISIBLE_CHIPS": str(replica)}


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


def probe_devices() -> dict:
    """What a fresh process sees when nothing narrows its view: run after
    every replica has let go of its chip (this process stays off JAX, and
    a chip belongs to one process at a time)."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, json; d = jax.devices(); print(json.dumps({"
         "'platform': d[0].platform, 'device_kind': d[0].device_kind, "
         "'device_count': len(d)}))"],
        capture_output=True, text=True, cwd=REPO)
    if out.returncode != 0:
        raise Failure("the host could not be asked for its devices: " + out.stderr[-1000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def require_distinct_chips(held: list) -> None:
    """`held`: per replica, the set `chips_held` found."""
    if any(len(h) != 1 for h in held) or len(set().union(*held)) != len(held):
        raise Failure(f"the {len(held)} replicas do not hold {len(held)} distinct chips: "
                      f"{[sorted(h) for h in held]}")


def require_tpu(device: dict, chips: int) -> None:
    if device["platform"] != "tpu" or device["device_count"] != chips:
        raise Failure(
            f"the server reports platform={device['platform']} "
            f"device_kind={device['device_kind']!r} "
            f"device_count={device['device_count']}: this cell needs "
            f"{chips} TPU device(s) and there is no CPU fallback")


def load_shims() -> None:
    """Build the C shims in this process, before the child needs them
    (they are keyed on a hash of their source, so a stale one is never
    trusted), and refuse to measure a run that fell back to Python."""
    from tigerbeetle_tpu import native

    loaded = {"hostops": native.hostops() is not None,
              "busio": native.busio() is not None,
              "aegis128l": native.aegis128l_mac() is not None}
    if not all(loaded.values()):
        raise Failure(f"native shims did not build and load: {loaded}")


def format_file(path: str, config: str, replica: int = 0, replica_count: int = 1) -> None:
    subprocess.run(format_args(path, config, replica, replica_count),
                   check=True, cwd=REPO, stdout=subprocess.DEVNULL)


def durable_mode(path: str) -> str:
    """Which of its two durable writes the server can use on this file
    system (the probe `io/storage.py` makes itself)."""
    try:
        os.close(os.open(path, os.O_RDWR | os.O_DIRECT | os.O_DSYNC))
        return "O_DIRECT|O_DSYNC"
    except (OSError, AttributeError):
        return "buffered write + fdatasync (the file system refuses O_DIRECT)"


class Watchdog:
    """One watch over every child of a run: a child that dies while the
    run needs it, or a run that outlives its deadline, ends the run AT ONCE
    with every child's stderr."""

    def __init__(self, workdir: str, deadline_s: float):
        self.workdir = workdir
        self.servers = []
        self._deadline = time.monotonic() + deadline_s
        threading.Thread(target=self._watch, daemon=True).start()

    def disarm_if_all_stopped(self) -> None:
        if not any(s.expected_alive for s in self.servers):
            self._deadline = float("inf")  # the watchdog has nothing left to end

    def _watch(self) -> None:
        while True:
            time.sleep(0.25)
            servers = list(self.servers)
            dead = [s for s in servers if s.expected_alive and s.proc is not None
                    and s.proc.poll() is not None]
            late = time.monotonic() > self._deadline
            if not dead and not late:
                continue
            for s in dead:
                say(f"FAIL: {s.name} exited with code {s.proc.returncode} "
                    "while the run needed it")
            if not dead:
                say("FAIL: the run outlived its deadline")
            for s in servers:
                say(f"--- {s.name}'s stderr (its end):\n{s.stderr_tail()}")
            for s in servers:
                if s.proc is not None and s.proc.poll() is None:
                    s.proc.kill()
                    s.proc.wait(timeout=60)
            shutil.rmtree(self.workdir, ignore_errors=True)
            os._exit(1)


class Server:
    """One child under the run's watchdog, its stdout as a queue of lines."""

    def __init__(self, watchdog: Watchdog, child: str = SERVE, name: str = "the server",
                 stderr_name: str = "server.stderr"):
        self.workdir = workdir = watchdog.workdir
        self.child = child
        self.name = name
        self.proc = None
        self.expected_alive = False
        self.answers = {}  # request word -> queue of its answers
        self._answers_lock = threading.Lock()
        self.stderr_path = os.path.join(workdir, stderr_name)
        self._listening: "queue.Queue[str]" = queue.Queue()
        self.watchdog = watchdog
        watchdog.servers.append(self)

    def start(self, args: list, chip: dict = None) -> dict:
        """`chip`: what `chip_env` gives this replica (nothing for the one
        replica of a one-chip cell)."""
        from tigerbeetle_tpu import cli

        # Cache every program, also those that compile in under a second
        # (JAX's default leaves them out): each run is a new process, and
        # what is not in the cache is compiled again in every set-up.
        # What the program drops in the temporary directory (flight-recorder
        # dumps) goes where the run removes it.
        env = {"JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
               **os.environ, "TMPDIR": self.workdir, **(chip or {})}
        with open(self.stderr_path, "ab") as err:
            self.proc = subprocess.Popen(
                [sys.executable, self.child, *args], cwd=REPO, env=env,
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=err)
        self.expected_alive = True
        threading.Thread(target=self._read_stdout, daemon=True).start()
        line = self._listening.get()  # the watchdog ends a run whose child died
        return cli.parse_listening(line)

    def _read_stdout(self) -> None:
        for raw in self.proc.stdout:
            line = raw.decode("utf-8", "replace")
            if line.startswith("BENCH "):
                answer = json.loads(line[6:])
                self._answers_of(answer["re"]).put(answer)
            elif "listening on" in line:
                self._listening.put(line)

    def _answers_of(self, word: str) -> "queue.Queue[dict]":
        with self._answers_lock:
            return self.answers.setdefault(word, queue.Queue())

    def ask(self, request: str, timeout: float = 120.0) -> dict:
        self.proc.stdin.write((request + "\n").encode())
        self.proc.stdin.flush()
        try:
            answer = self._answers_of(request.split()[0]).get(timeout=timeout)
        except queue.Empty:
            raise Failure(f"{self.name} did not answer '{request}'") from None
        if "error" in answer:
            raise Failure(f"{self.name} could not '{request}': {answer['error']}")
        return answer

    def stderr_tail(self, nbytes: int = 3000) -> str:
        try:
            with open(self.stderr_path, "rb") as f:
                f.seek(0, 2)
                f.seek(max(0, f.tell() - nbytes))
                return f.read().decode("utf-8", "replace")
        except OSError:
            return ""

    def stop(self, kill: bool = False) -> None:
        """`kill`: SIGKILL, what a crashed machine looks like to its peers."""
        self.expected_alive = False
        self.watchdog.disarm_if_all_stopped()
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill() if kill else self.proc.terminate()
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=60)
