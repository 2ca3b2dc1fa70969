"""chip_smoke.py, held to its contract without a chip.

On the CPU the script must FAIL (no accelerator, no result line) — that
is half of what the driver checks. The other half, that its phases still
run end to end, is rehearsed here at a tiny size: this file, run as a
script, calls chip_smoke.one_chip()/three_replicas() with the platform
the rehearsal expects (the steering lives here, in the test; the script
has no switch for it) and with the one existing override that puts an
XLA-CPU server on the chip's side of its route: a depth-4 commit window.

Each rehearsal runs in a child process: chip_smoke's watchdog ends a
failed run with os._exit, and it retunes the clients' class-wide
time-outs — neither belongs inside the pytest process.
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]

# The CPU stands in for the chip: take the routes the chip takes.
ROUTES_AS_ON_CHIP = {
    "JAX_PLATFORMS": "cpu",
    "TIGERBEETLE_TPU_COMMIT_DEPTH": "4",
}


def _run(argv, cwd=REPO, env=None, timeout=600):
    return subprocess.run(
        [sys.executable, *argv], cwd=cwd, text=True, capture_output=True,
        timeout=timeout, env={**os.environ, **(env or {})},
    )


def _no_result_line(out: str) -> bool:
    return not any(line.startswith('{"ok"') for line in out.splitlines())


@pytest.mark.parametrize("mode", ["one_chip", "three_replicas"])
def test_rehearsal_on_cpu(mode):
    """Every phase of the chip run at a tiny size: shims rebuilt, served
    traffic byte-equal to the oracle, each device route taken, restart
    read-back (one_chip); three replicas, primary killed, read-back from
    the remaining two (three_replicas)."""
    r = _run([__file__, mode], env=ROUTES_AS_ON_CHIP)
    assert r.returncode == 0, r.stdout[-6000:] + r.stderr[-3000:]
    assert "0 mismatches" in r.stdout
    assert "REHEARSAL OK" in r.stdout


def test_fails_without_an_accelerator():
    """As the driver runs it in a sandbox: the server reports a CPU, so
    the script exits non-zero, says what it found, prints no result."""
    r = _run(["chip_smoke.py"], env={"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0, r.stdout[-3000:]
    assert "platform=cpu" in r.stdout
    assert _no_result_line(r.stdout)


def test_fails_alone_in_a_directory(tmp_path):
    """...and in a directory that holds chip_smoke.py and nothing else
    of the repo."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, text=True,
        capture_output=True, timeout=120, env=env,
    )
    assert r.returncode != 0
    assert _no_result_line(r.stdout)


# --- the launcher the script (and cli.py benchmark, and chaos) starts servers with


def test_listening_line_names_the_device():
    from tigerbeetle_tpu import cli

    numpy_backend = (
        "replica 0/1 listening on 127.0.0.1:3001 (backend=numpy, "
        f"status=normal, {cli._device_fields('numpy')})"
    )
    assert cli.parse_listening(numpy_backend) == {
        "platform": "none", "device_kind": "none", "device_count": 0,
    }
    assert cli.parse_listening(
        "replica 2/3 listening on 127.0.0.1:3003 (backend=jax, status=normal, "
        'platform=tpu, device_kind="TPU v5 lite", device_count=1)'
    ) == {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1}
    with pytest.raises(ValueError):
        cli.parse_listening("metrics on http://127.0.0.1:3002/metrics")


def test_launcher_reports_a_server_that_dies_before_listening(tmp_path):
    """No data file: the child exits at once. The launcher must say so,
    with the child's stderr — not hand back a process that looks like a
    client time-out later."""
    from tigerbeetle_tpu import cli

    path = str(tmp_path / "never_formatted.tigerbeetle")
    with pytest.raises(cli.ReplicaStartError) as e:
        cli.spawn_replica(
            ["--addresses=127.0.0.1:1", "--replica=0", "--config=test_min",
             "--backend=numpy"], path,
        )
    assert "before listening" in str(e.value)
    assert "Error" in str(e.value)  # the child's own traceback
    assert os.path.getsize(path + ".stderr") > 0


def _rehearse(mode: str) -> None:
    import tempfile

    sys.path.insert(0, str(REPO))
    import chip_smoke

    def expect_cpu(device, what):
        assert device["platform"] == "cpu", (what, device)

    def a_chip_each(pid):
        return {f"the chip of pid {pid}"}

    plan = chip_smoke.Plan(
        config="development", batch=512, accounts=20_000, fast_batches=12,
        deadline_s=500.0,
        request_timeout_s=30.0 if mode == "three_replicas" else 120.0,
    )
    workdir = tempfile.mkdtemp(prefix="chip_smoke_rehearsal_")
    servers = chip_smoke.Servers(workdir, plan.deadline_s)
    try:
        if mode == "one_chip":
            device, starts = chip_smoke.one_chip(0, plan, servers, expect=expect_cpu)
            print(json.dumps({"device": device, "starts": starts}))
        else:
            chip_smoke.three_replicas(
                0, plan, servers, expect=expect_cpu, distinct=a_chip_each
            )
    finally:
        servers.stop_all()
        shutil.rmtree(workdir, ignore_errors=True)
    assert "jax" not in sys.modules, "the driving process must stay off JAX"
    print("REHEARSAL OK")


if __name__ == "__main__":
    _rehearse(sys.argv[1])
