"""The C-boundary analyzer (tidy/nativecheck.py + tidy/cparse.py) and
its dynamic leg (tools/nativecheck.py).

Fixture pairs under tests/fixtures/nativecheck/ pin EXACT findings for
each seeded violation class (shifted layout define, narrowed ctypes
arg, captured temporary address, off-by-one loop bound) next to clean
inverses that must stay silent. The real-source tests pin two harder
properties: every manifest-listed C function PROVES in-bounds with
non-trivial coverage (a parser regression that silently checked
nothing would fail the coverage pin, not pass vacuously), and mutating
any single layout expectation against the real csrc/ produces exactly
one parity finding (the proof is sensitive, not a tautology).

The sanitizer harness tests build ASan+UBSan sidecars through the
native._build_lib flags mechanism: a smoke replay of the real corpora
(tier-1), a `slow` full replay, and a planted-overflow probe asserting
the harness actually detects memory bugs on this host.
"""

import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
FIX = REPO / "tests" / "fixtures" / "nativecheck"

from tigerbeetle_tpu.tidy import cparse, manifest, nativecheck  # noqa: E402


def _tool():
    spec = importlib.util.spec_from_file_location(
        "nativecheck_tool", REPO / "tools" / "nativecheck.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --- native-layout: fixture pair + real-source mutation sensitivity -----

# The fixture files' private expectation table (values the layout_*.c
# defines are checked against; `truth` strings only appear in messages).
_EXPECT = {
    "OFF_CHECKSUM": (0, "fixture table"),
    "OFF_SIZE": (80, "fixture table"),
    "HEADER_SIZE": (256, "fixture table"),
    "T_LEDGER": (52, "fixture table"),
    "OFF_GONE": (10, "fixture table"),
}


def test_layout_fixture_exact_findings():
    fs = nativecheck.check_layout_file(
        FIX / "layout_bad.c", "fix/layout_bad.c", _EXPECT
    )
    assert sorted((f.code, f.subject) for f in fs) == [
        ("layout-missing", "OFF_GONE"),
        ("layout-parity", "HEADER_SIZE"),
        ("layout-parity", "OFF_SIZE"),
        ("layout-unknown", "OFF_MYSTERY"),
    ], [f.message for f in fs]
    assert all(f.pass_name == "native-layout" for f in fs)


def test_layout_fixture_clean():
    fs = nativecheck.check_layout_file(
        FIX / "layout_clean.c", "fix/layout_clean.c", _EXPECT
    )
    assert fs == [], [f.message for f in fs]


def test_layout_mutation_sensitivity_real_sources():
    """Shifting ANY single expected constant against the real C sources
    yields exactly one parity finding naming that constant — the proof
    notices every field of HEADER_DTYPE/TRANSFER_DTYPE it covers."""
    expect_all = nativecheck._layout_expectations()
    for rel in ("csrc/busio.c", "csrc/tb_client.c"):
        base = expect_all[rel]
        for name, (want, truth) in base.items():
            mutated = dict(base)
            mutated[name] = (want + 1, truth)
            fs = nativecheck.check_layout_file(REPO / rel, rel, mutated)
            assert [(f.code, f.subject) for f in fs] == [
                ("layout-parity", name)
            ], (rel, name, [f.message for f in fs])


# --- native-abi: fixture pair -------------------------------------------


def _fx_exports():
    fns = cparse.parse_functions((FIX / "abi_shim.c").read_text())
    return {f.name: f for f in fns if not f.static}


def test_abi_fixture_exact_findings():
    fs = nativecheck.check_abi_decls(
        FIX / "abi_bad.py", "fix/abi_bad.py", _fx_exports()
    )
    assert sorted((f.code, f.subject) for f in fs) == [
        ("abi-arity", "fx_fill"),
        ("abi-restype", "fx_fill"),
        ("abi-type", "fx_sum[1]"),
        ("abi-unknown-symbol", "fx_missing"),
        ("abi-unwrapped", "fx_unwrapped"),
    ], [f.message for f in fs]


def test_abi_fixture_clean():
    fs = nativecheck.check_abi_decls(
        FIX / "abi_clean.py", "fix/abi_clean.py", _fx_exports()
    )
    assert fs == [], [f.message for f in fs]


def test_ptr_lifetime_fixture_exact_findings():
    fs = nativecheck._lifetime_scan_file(FIX / "ptr_bad.py", "fix/ptr_bad.py")
    assert sorted((f.code, f.line) for f in fs) == [
        ("ptr-lifetime", 7),
        ("ptr-lifetime", 12),
    ], [f.message for f in fs]


def test_ptr_lifetime_fixture_clean():
    fs = nativecheck._lifetime_scan_file(
        FIX / "ptr_clean.py", "fix/ptr_clean.py"
    )
    assert fs == [], [f.message for f in fs]


# --- native-absint: fixture pair + real-source coverage pin -------------


def test_absint_fixture_exact_findings():
    fs, ops = nativecheck.analyze_c_function(
        FIX / "absint_bad.c", "fix/absint_bad.c", "fx_oob"
    )
    assert [(f.code, f.scope, f.subject) for f in fs] == [
        ("c-index-bound", "fx_oob", "a")
    ], [f.message for f in fs]
    assert ops > 0


def test_absint_fixture_clean():
    fs, ops = nativecheck.analyze_c_function(
        FIX / "absint_clean.c", "fix/absint_clean.c", "fx_inbounds"
    )
    assert fs == [], [f.message for f in fs]
    assert ops > 0


def test_absint_real_functions_prove_clean_with_coverage():
    """Every manifest-listed C hot loop proves in-bounds AND actually
    checked subscripts — zero checked ops would mean the proof went
    vacuous (parse drift, annotation rot), which must fail loudly."""
    for rel, fname in manifest.NATIVE_ABSINT_FUNCS:
        fs, ops = nativecheck.analyze_c_function(REPO / rel, rel, fname)
        assert fs == [], (rel, fname, [f.message for f in fs])
        assert ops > 0, (rel, fname)


# --- the dynamic leg: warnings gate + sanitizer replay ------------------

_HAS_CC = any(shutil.which(c) for c in ("cc", "gcc", "clang"))


@pytest.mark.skipif(not _HAS_CC, reason="no C compiler")
def test_strict_warnings_clean():
    tool = _tool()
    findings, note = tool.check_warnings()
    if note is not None:
        pytest.skip(note)
    assert findings == [], findings


@pytest.mark.skipif(not _HAS_CC, reason="no C compiler")
def test_build_staleness_keys_on_source_text_not_mtime(tmp_path):
    """A copied or unpacked tree carries arbitrary file times: a .so
    NEWER than a source whose text changed must be rebuilt, and an
    unchanged source must not be (whatever the times say)."""
    from tigerbeetle_tpu import native

    src = tmp_path / "probe.c"
    lib = tmp_path / "libprobe.so"
    src.write_text("int probe(void) { return 1; }\n")
    assert native._build_lib(str(src), str(lib)) == str(lib)
    built_one = lib.read_bytes()

    src.write_text("int probe(void) { return 2; }\n")
    future = os.path.getmtime(src) + 3600
    os.utime(lib, (future, future))  # the stale .so looks fresher
    assert native._build_lib(str(src), str(lib)) == str(lib)
    assert lib.read_bytes() != built_one

    os.utime(lib, (1, 1))  # ... and a current one looks ancient
    before = os.stat(lib).st_ino
    assert native._build_lib(str(src), str(lib)) == str(lib)
    assert os.stat(lib).st_ino == before  # trusted, not rebuilt


@pytest.mark.skipif(not _HAS_CC, reason="no C compiler")
def test_sanitizer_detects_planted_overflow(tmp_path, monkeypatch):
    """The harness mechanism end-to-end on a seeded bug: a sidecar
    build of an out-of-bounds read must produce a sanitizer report in
    the replay child. If this host cannot run the mechanism the smoke
    test would skip too — so prove the skip/detect split is honest."""
    tool = _tool()
    asan = tool._find_runtime("libasan.so")
    ubsan = tool._find_runtime("libubsan.so")
    if asan is None or ubsan is None:
        pytest.skip("sanitizer runtimes unavailable")
    from tigerbeetle_tpu import native

    bad = tmp_path / "bad.c"
    bad.write_text(
        "#include <stdint.h>\n"
        "int64_t fx_probe(void) {\n"
        "    int64_t a[4] = {1, 2, 3, 4};\n"
        "    volatile int64_t s = 0;\n"
        "    for (int i = 0; i <= 4; i++) s += a[i];\n"
        "    return s;\n"
        "}\n"
    )
    drive = tmp_path / "drive.py"
    drive.write_text(
        "import ctypes, sys\n"
        "lib = ctypes.CDLL(sys.argv[1])\n"
        "lib.fx_probe.restype = ctypes.c_int64\n"
        "print(lib.fx_probe())\n"
    )
    monkeypatch.setenv(native._FLAGS_ENV, tool.SANITIZE_FLAGS)
    lib = native._build_lib(str(bad), str(tmp_path / "libbad.so"))
    if lib is None:
        pytest.skip("sanitized build failed on this host")
    env = dict(
        os.environ,
        LD_PRELOAD=f"{asan} {ubsan}",
        ASAN_OPTIONS="detect_leaks=0:exitcode=97",
        UBSAN_OPTIONS="print_stacktrace=1:halt_on_error=1",
    )
    r = subprocess.run(
        [sys.executable, str(drive), lib],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert r.returncode != 0, r.stdout
    assert any(m in r.stderr for m in tool._SAN_MARKERS), r.stderr[-2000:]


@pytest.mark.skipif(not _HAS_CC, reason="no C compiler")
def test_sanitize_smoke_replay():
    """Tier-1 leg: ASan+UBSan sidecar builds + the small corpora. The
    production .so files must be untouched afterwards (sidecar names
    carry the flags hash)."""
    tool = _tool()
    res = tool.run_sanitize(full=False, timeout=600)
    if not res["ran"]:
        pytest.skip(res.get("note") or "sanitize unavailable")
    assert res["failures"] == [], res.get("output", "")[-6000:]
    assert "REPLAY OK" in res["output"]


@pytest.mark.slow
@pytest.mark.skipif(not _HAS_CC, reason="no C compiler")
def test_sanitize_full_replay():
    tool = _tool()
    res = tool.run_sanitize(full=True, timeout=1800)
    if not res["ran"]:
        pytest.skip(res.get("note") or "sanitize unavailable")
    assert res["failures"] == [], res.get("output", "")[-6000:]
