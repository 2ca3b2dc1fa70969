"""Starting, watching and stopping the server a run drives.

Copies of `chip_smoke.py`'s launcher pieces (`Servers`, `free_ports`,
`require_tpu`, `format_file`), changed in one way: the child is
`benchmarks/serve.py`, which wraps `cli.py start` so that the process
holding the chip can be asked for its trace, its compile count and its
memory (`cli.spawn_replica` hard-codes `-m tigerbeetle_tpu.cli`). The
parent never imports JAX: the device is the one the child names on its
`listening` line.
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


def format_file(path: str, config: str) -> None:
    subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu.cli", "format",
         f"--config={config}", "--replica=0", "--replica-count=1", path],
        check=True, cwd=REPO, stdout=subprocess.DEVNULL)


def durable_mode(path: str) -> str:
    """Which of its two durable writes the server can use on this file
    system (the probe `io/storage.py` makes itself)."""
    try:
        os.close(os.open(path, os.O_RDWR | os.O_DIRECT | os.O_DSYNC))
        return "O_DIRECT|O_DSYNC"
    except (OSError, AttributeError):
        return "buffered write + fdatasync (the file system refuses O_DIRECT)"


class Server:
    """The child, its stdout as a queue of lines, and a watchdog: a child
    that dies while the run needs it, or a run that outlives its
    deadline, ends the run AT ONCE with the child's stderr."""

    def __init__(self, workdir: str, deadline_s: float, child: str = SERVE):
        self.workdir = workdir
        self.child = child
        self.proc = None
        self.expected_alive = False
        self.answers = {}  # request word -> queue of its answers
        self._answers_lock = threading.Lock()
        self.stderr_path = os.path.join(workdir, "server.stderr")
        self._deadline = time.monotonic() + deadline_s
        self._listening: "queue.Queue[str]" = queue.Queue()
        threading.Thread(target=self._watch, daemon=True).start()

    def start(self, args: list) -> dict:
        from tigerbeetle_tpu import cli

        # Cache every program, also those that compile in under a second
        # (JAX's default leaves them out): each run is a new process, and
        # what is not in the cache is compiled again in every set-up.
        # What the program drops in the temporary directory (flight-recorder
        # dumps) goes where the run removes it.
        env = {"JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
               **os.environ, "TMPDIR": self.workdir}
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
            raise Failure(f"the server did not answer '{request}'") from None
        if "error" in answer:
            raise Failure(f"the server could not '{request}': {answer['error']}")
        return answer

    def stderr_tail(self, nbytes: int = 3000) -> str:
        try:
            with open(self.stderr_path, "rb") as f:
                f.seek(0, 2)
                f.seek(max(0, f.tell() - nbytes))
                return f.read().decode("utf-8", "replace")
        except OSError:
            return ""

    def stop(self) -> None:
        self.expected_alive = False
        self._deadline = float("inf")  # the watchdog has nothing left to end
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=60)

    def _watch(self) -> None:
        while True:
            time.sleep(0.25)
            dead = (self.expected_alive and self.proc is not None
                    and self.proc.poll() is not None)
            late = time.monotonic() > self._deadline
            if not dead and not late:
                continue
            if dead:
                say(f"FAIL: the server exited with code {self.proc.returncode} "
                    "while the run needed it")
            else:
                say("FAIL: the run outlived its deadline")
            say(f"--- the server's stderr (its end):\n{self.stderr_tail()}")
            if self.proc is not None and self.proc.poll() is None:
                self.proc.kill()
                self.proc.wait(timeout=60)
            shutil.rmtree(self.workdir, ignore_errors=True)
            os._exit(1)
