"""C++ binding CI (the reference's per-language client CI role,
src/scripts/ci.zig + clients/*/ci.zig): compile the C++ sample app
against the C ABI and run it against a REAL server process. A foreign
compiled runtime exercising libtbclient's wire contract end-to-end."""

import os
import shutil
import subprocess
import sys

import pytest

CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "csrc")


def _has_aes() -> bool:
    from tigerbeetle_tpu import native

    return native.aegis128l_mac() is not None


@pytest.fixture(scope="module")
def sample_bin(tmp_path_factory):
    gxx = shutil.which("g++")
    if gxx is None:
        pytest.skip("no g++")
    if not _has_aes():
        pytest.skip("no AES-NI (cluster checksum)")
    out = tmp_path_factory.mktemp("cpp") / "cpp_sample"
    build = subprocess.run(
        [
            gxx, "-std=c++17", "-O2", "-maes", "-mssse3",
            os.path.join(CSRC, "cpp_sample.cpp"),
            "-x", "c", os.path.join(CSRC, "tb_client.c"),
            "-o", str(out), f"-I{CSRC}",
        ],
        capture_output=True, text=True,
    )
    assert build.returncode == 0, build.stderr[-2000:]
    return str(out)


def test_cpp_sample_against_live_server(sample_bin, tmp_path):
    port = 38700 + os.getpid() % 500
    path = tmp_path / "cpp.tb"
    subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu.cli", "format",
         "--replica", "0", str(path)],
        check=True, capture_output=True,
    )
    from tigerbeetle_tpu.cli import spawn_replica

    proc, _device = spawn_replica(
        [f"--addresses=127.0.0.1:{port}", "--replica=0", "--backend=numpy"],
        str(path),
    )
    try:
        run = subprocess.run(
            [sample_bin, "127.0.0.1", str(port)],
            capture_output=True, text=True, timeout=60,
        )
        assert run.returncode == 0, (run.stdout, run.stderr)
        assert "cpp_sample OK" in run.stdout
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
