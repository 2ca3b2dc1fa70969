"""Native (C) shims for the host runtime.

The TPU compute path is JAX/XLA; the host runtime around it uses native
code where the reference does (SURVEY §7 hard part (f)): AEGIS-128L
checksums run one AES round per 16 bytes on AES-NI hardware — an order of
magnitude past any software hash, and every message header/body and grid
block is sealed with one (reference src/vsr/checksum.zig).

The shim self-builds from csrc/aegis128l.c with the system compiler on
first import (cached next to the source) and loads via ctypes — no
pybind11 dependency. Hosts without AES-NI or a C compiler fall back to
BLAKE2b-128 transparently (vsr/header.py); the two algorithms are format-
incompatible, so a deployment picks one via TIGERBEETLE_TPU_CHECKSUM and
all replicas of a cluster must agree (the same class of constraint as the
reference's fixed AEGIS choice).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Callable, Optional, Tuple

_CSRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "csrc",
)
_SRC = os.path.join(_CSRC, "aegis128l.c")
_LIB = os.path.join(_CSRC, "libaegis128l.so")

_mac: Optional[Callable[[bytes], bytes]] = None
_tried = False

# Baseline flag set for every shim build. The warning set is part of the
# contract: the sources compile warning-free, and tools/nativecheck.py
# --strict-warnings turns any regression into a finding.
_BASE_FLAGS = ("-O3", "-Wall", "-Wextra")

# Extra flags injected by tooling (the sanitizer replay harness sets
# "-fsanitize=address,undefined -g -O1" here). Non-empty values route the
# build into a flag-hashed SIDECAR .so, so an instrumented build can never
# be mistaken for — or clobber — the production library.
_FLAGS_ENV = "TIGERBEETLE_TPU_NATIVE_CFLAGS"


def _env_flags() -> Tuple[str, ...]:
    v = os.environ.get(_FLAGS_ENV, "")
    return tuple(v.split()) if v else ()


def _flags_hash(flags: Tuple[str, ...]) -> str:
    return hashlib.sha256(" ".join(flags).encode()).hexdigest()[:12]


def _build_lib(src: str, lib: str, extra_flags: tuple = ()) -> Optional[str]:
    """Compile `src` → a shared object; returns the built path or None.

    Staleness keys on a hash of the source TEXT and the full flag set
    (sidecar stamp `<lib>.flags`), never on file times: a tree that was
    copied, unpacked or checked out carries arbitrary mtimes, and a .so
    built from other sources or under other flags is never trusted.
    With _FLAGS_ENV set the output itself moves to a flag-hashed sidecar
    name beside the production library.
    """
    flags = (*_BASE_FLAGS, *extra_flags, *_env_flags())
    if _env_flags():
        base, ext = os.path.splitext(lib)
        lib = f"{base}.{_flags_hash(flags)}{ext}"
    with open(src, "rb") as f:
        fh = hashlib.sha256(
            " ".join(flags).encode() + b"\0" + f.read()
        ).hexdigest()[:16]
    stamp = f"{lib}.flags"
    try:
        with open(stamp) as f:
            stamp_ok = f.read().strip() == fh
    except OSError:
        stamp_ok = False
    if stamp_ok and os.path.exists(lib):
        return lib
    tmp = f"{lib}.{os.getpid()}.tmp"  # pid-unique: concurrent first builds
    # must not interleave into one output (os.replace is atomic)
    for cc in ("cc", "gcc", "clang"):
        try:
            r = subprocess.run(
                [cc, *flags, "-shared", "-fPIC", src, "-o", tmp],
                capture_output=True, timeout=120,
            )
        except (OSError, subprocess.TimeoutExpired):
            continue
        if r.returncode == 0:
            os.replace(tmp, lib)
            stamp_tmp = f"{stamp}.{os.getpid()}.tmp"
            try:
                with open(stamp_tmp, "w") as f:
                    f.write(fh)
                os.replace(stamp_tmp, stamp)
            except OSError:
                pass  # stampless: next import just rebuilds
            return lib
    try:
        os.unlink(tmp)
    except OSError:
        pass
    return None


_hostops: Optional[ctypes.CDLL] = None
_hostops_tried = False


def hostops() -> Optional[ctypes.CDLL]:
    """Batch host primitives (csrc/hostops.c): u128 hash map, radix
    argsort, exact u128 posting. Plain C — any host with a compiler."""
    global _hostops, _hostops_tried
    if _hostops_tried:
        return _hostops
    _hostops_tried = True
    src = os.path.join(_CSRC, "hostops.c")
    if not os.path.exists(src):
        return None
    lib_path = _build_lib(src, os.path.join(_CSRC, "libhostops.so"))
    if lib_path is None:
        return None
    try:
        lib = ctypes.CDLL(lib_path)
    except OSError:
        return None
    u64p = ctypes.POINTER(ctypes.c_uint64)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.hostops_map_new.argtypes = [ctypes.c_uint64]
    lib.hostops_map_new.restype = ctypes.c_void_p
    lib.hostops_map_free.argtypes = [ctypes.c_void_p]
    lib.hostops_map_free.restype = None
    lib.hostops_map_len.argtypes = [ctypes.c_void_p]
    lib.hostops_map_len.restype = ctypes.c_uint64
    lib.hostops_map_insert_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, u64p, u64p, u32p,
    ]
    lib.hostops_map_insert_batch.restype = None
    lib.hostops_map_lookup_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, u64p, u64p, u32p,
    ]
    lib.hostops_map_lookup_batch.restype = None
    lib.hostops_map_contains_any.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, u64p, u64p,
    ]
    lib.hostops_map_contains_any.restype = ctypes.c_int
    lib.hostops_batch_has_dup.argtypes = [ctypes.c_int64, u64p, u64p]
    lib.hostops_batch_has_dup.restype = ctypes.c_int
    lib.hostops_argsort_u64.argtypes = [ctypes.c_int64, u64p, u32p]
    lib.hostops_argsort_u64.restype = ctypes.c_int
    lib.hostops_bloom_add.argtypes = [
        u64p, ctypes.c_uint64, ctypes.c_int64, u64p, u64p,
    ]
    lib.hostops_bloom_add.restype = None
    lib.hostops_bloom_maybe.argtypes = [
        u64p, ctypes.c_uint64, ctypes.c_int64, u64p, u64p, u8p,
    ]
    lib.hostops_bloom_maybe.restype = None
    lib.hostops_post_u128.argtypes = [
        u32p, u32p, u32p, u32p, ctypes.c_int64,
        i64p, i64p, u64p, u64p, u8p, u8p,
    ]
    lib.hostops_post_u128.restype = ctypes.c_int
    lib.hostops_ct_stage.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,  # events, n, stride
        ctypes.c_uint64,                                   # ts_base
        ctypes.c_void_p,                                   # account map
        u32p, u32p,                                        # acc_ledger, acc_flags
        u64p, ctypes.c_uint64,                             # bloom words, mask
        u32p, u32p, i64p, i64p, u64p, u64p, u8p, u8p,
    ]
    lib.hostops_ct_stage.restype = ctypes.c_int
    lib.hostops_build_sorted_kv.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_uint32, ctypes.c_char_p, u32p,
    ]
    lib.hostops_build_sorted_kv.restype = ctypes.c_int
    lib.hostops_extract_kv.argtypes = lib.hostops_build_sorted_kv.argtypes
    lib.hostops_extract_kv.restype = ctypes.c_int
    # Fused flush-path sort+gather. Guarded: a stale pre-r5 .so (mtime
    # newer than the source, e.g. copied around) must degrade to the
    # numpy fallback in sort_kv, not AttributeError inside a flush.
    if hasattr(lib, "hostops_sort_kv"):
        lib.hostops_sort_kv.argtypes = [ctypes.c_int64, u64p, u32p, u64p, u32p]
        lib.hostops_sort_kv.restype = ctypes.c_int
    # Stable k-way merge of sorted runs (round-13 device query-index
    # pipeline's host merge substrate). Same stale-.so guard as above.
    if hasattr(lib, "hostops_merge_kv"):
        lib.hostops_merge_kv.argtypes = [
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int64), u64p, u32p,
        ]
        lib.hostops_merge_kv.restype = ctypes.c_int
    # Fused merge + segmented Bloom build (round-16 streaming compaction).
    # Same stale-.so guard: older libraries fall back to the two-pass path.
    if hasattr(lib, "hostops_merge_kv_bloom"):
        lib.hostops_merge_kv_bloom.argtypes = [
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int64), u64p, u32p,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_void_p), u64p,
        ]
        lib.hostops_merge_kv_bloom.restype = ctypes.c_int
    # Galloping sorted-set row intersects (round-21 multi-predicate scan
    # engine). Same stale-.so guard: older libraries keep the numpy path.
    if hasattr(lib, "hostops_intersect_u32"):
        lib.hostops_intersect_u32.argtypes = [
            ctypes.c_int64, u32p, ctypes.c_int64, u32p, u32p,
        ]
        lib.hostops_intersect_u32.restype = ctypes.c_int64
    if hasattr(lib, "hostops_gallop_mark_u32"):
        lib.hostops_gallop_mark_u32.argtypes = [
            ctypes.c_int64, u32p, ctypes.c_int64, u32p, u8p,
        ]
        lib.hostops_gallop_mark_u32.restype = ctypes.c_int64
    # The exact kernel's line walk (same stale-.so guard: older libraries
    # keep the Python loop, commit_exact._line_walk).
    if hasattr(lib, "hostops_line_plan"):
        i32p = ctypes.POINTER(ctypes.c_int32)
        lib.hostops_line_plan.argtypes = [
            ctypes.c_int64, u8p, i32p, ctypes.c_int64, ctypes.c_int32,
            i32p, i32p, i32p, i32p,
        ]
        lib.hostops_line_plan.restype = ctypes.c_int32
    # The C staging ladder hardcodes the wire-contract result codes; refuse
    # the shim (fall back to numpy) if the enums ever drift.
    from tigerbeetle_tpu.results import CreateTransferResult as _TR

    _expect = {
        "TIMESTAMP_MUST_BE_ZERO": 3, "RESERVED_FLAG": 4,
        "ID_MUST_NOT_BE_ZERO": 5, "ID_MUST_NOT_BE_INT_MAX": 6,
        "DEBIT_ACCOUNT_ID_MUST_NOT_BE_ZERO": 8,
        "DEBIT_ACCOUNT_ID_MUST_NOT_BE_INT_MAX": 9,
        "CREDIT_ACCOUNT_ID_MUST_NOT_BE_ZERO": 10,
        "CREDIT_ACCOUNT_ID_MUST_NOT_BE_INT_MAX": 11,
        "ACCOUNTS_MUST_BE_DIFFERENT": 12, "PENDING_ID_MUST_BE_ZERO": 13,
        "TIMEOUT_RESERVED_FOR_PENDING_TRANSFER": 17,
        "AMOUNT_MUST_NOT_BE_ZERO": 18, "LEDGER_MUST_NOT_BE_ZERO": 19,
        "CODE_MUST_NOT_BE_ZERO": 20, "DEBIT_ACCOUNT_NOT_FOUND": 21,
        "CREDIT_ACCOUNT_NOT_FOUND": 22,
        "ACCOUNTS_MUST_HAVE_THE_SAME_LEDGER": 23,
        "TRANSFER_MUST_HAVE_THE_SAME_LEDGER_AS_ACCOUNTS": 24,
        "OVERFLOWS_TIMEOUT": 53,
    }
    for name, val in _expect.items():
        if int(getattr(_TR, name)) != val:
            return None
    _hostops = lib
    return _hostops


def _cpu_has_aes() -> bool:
    import platform

    # x86-only shim (wmmintrin intrinsics); ARM also spells its feature
    # flag "aes", so gate on the architecture first.
    if platform.machine() not in ("x86_64", "amd64", "AMD64"):
        return False
    try:
        with open("/proc/cpuinfo") as f:
            return " aes " in f.read().replace("\n", " ")
    except OSError:
        return False


_lib_built: Optional[str] = None  # actual aegis .so path (variant-aware)


def _build() -> bool:
    global _lib_built
    _lib_built = _build_lib(_SRC, _LIB, extra_flags=("-maes", "-mssse3"))
    return _lib_built is not None


def aegis128l_mac() -> Optional[Callable[[bytes], bytes]]:
    """Returns bytes -> 16-byte tag, or None if unavailable on this host."""
    global _mac, _tried
    if _tried:
        return _mac
    _tried = True
    if not _cpu_has_aes() or not os.path.exists(_SRC):
        return None
    if not _build():
        return None
    try:
        lib = ctypes.CDLL(_lib_built or _LIB)
    except OSError:
        return None
    fn = lib.aegis128l_mac
    fn.argtypes = [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p]
    fn.restype = None

    def mac(data: bytes) -> bytes:
        out = ctypes.create_string_buffer(16)
        fn(data, len(data), out)
        return out.raw

    # Smoke: deterministic and length-sensitive before we trust it.
    a, b = mac(b"x"), mac(b"x")
    if a != b or mac(b"y") == a or mac(b"") == a:
        return None
    _mac = mac
    return _mac


_busio: Optional[ctypes.CDLL] = None
_busio_tried = False


def busio() -> Optional[ctypes.CDLL]:
    """The framed-codec + WAL-ring shim (csrc/busio.c — scan, encode,
    transfer SoA decode, batched pwrite; docs/NATIVE_DATAPATH.md). Frames
    are sealed with AEGIS-128L, so the shim requires AES-NI like the
    checksum it verifies; hosts without it keep the pure-Python bus."""
    global _busio, _busio_tried
    if _busio_tried:
        return _busio
    _busio_tried = True
    if not _cpu_has_aes():
        return None
    src = os.path.join(_CSRC, "busio.c")
    if not os.path.exists(src):
        return None
    lib_path = _build_lib(
        src, os.path.join(_CSRC, "libbusio.so"),
        extra_flags=("-maes", "-mssse3"),
    )
    if lib_path is None:
        return None
    try:
        lib = ctypes.CDLL(lib_path)
    except OSError:
        return None
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u8pp = ctypes.POINTER(ctypes.c_char_p)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.busio_scan.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, u64p, ctypes.c_int64, u64p,
    ]
    lib.busio_scan.restype = ctypes.c_int64
    # tidy: allow=abi-type — arg 3 (const uint64_t *p) takes codec._ENC_PARAMS.pack's 14-word bytes block; c_char_p marshals it in one conversion instead of 14 scalar casts
    lib.busio_encode_frame.argtypes = [
        u8p, ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
    ]
    lib.busio_encode_frame.restype = None
    lib.busio_decode_transfers.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_uint64,
        i64p, i64p, u32p, u32p, u32p, i32p, i32p,
        u32p, u32p, u32p, u32p, u32p,
    ]
    lib.busio_decode_transfers.restype = None
    lib.busio_pwritev.argtypes = [
        ctypes.c_int32, ctypes.c_int64, u8pp, u64p, u64p,
    ]
    lib.busio_pwritev.restype = ctypes.c_int64
    _busio = lib
    return _busio


_tbclient: Optional[ctypes.CDLL] = None
_tbclient_tried = False


def tb_client() -> Optional[ctypes.CDLL]:
    """The C ABI client library (csrc/tb_client.c + tb_client.h — the
    reference's clients/c/tb_client.zig role): built on demand, loaded via
    ctypes for the test harness; external embedders link it directly.
    Requires AES-NI (the cluster checksum)."""
    global _tbclient, _tbclient_tried
    if _tbclient_tried:
        return _tbclient
    _tbclient_tried = True
    if not _cpu_has_aes():
        return None
    src = os.path.join(_CSRC, "tb_client.c")
    if not os.path.exists(src):
        return None
    lib_path = _build_lib(
        src, os.path.join(_CSRC, "libtbclient.so"),
        extra_flags=("-maes", "-mssse3"),
    )
    if lib_path is None:
        return None
    try:
        lib = ctypes.CDLL(lib_path)
    except OSError:
        return None
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    lib.tbc_connect.argtypes = [
        ctypes.c_char_p, ctypes.c_uint16, ctypes.c_uint64, ctypes.c_uint32,
    ]
    lib.tbc_connect.restype = ctypes.c_void_p
    lib.tbc_close.argtypes = [ctypes.c_void_p]
    lib.tbc_close.restype = None
    for fn in (
        lib.tbc_create_accounts, lib.tbc_create_transfers,
        lib.tbc_lookup_accounts, lib.tbc_lookup_transfers,
    ):
        fn.argtypes = [
            ctypes.c_void_p, u8p, ctypes.c_uint32, u8p, ctypes.c_uint32,
        ]
        fn.restype = ctypes.c_int64
    lib.tbc_demux_results.argtypes = [
        u8p, ctypes.c_uint32, u32p, ctypes.c_uint32, u32p, u32p,
    ]
    lib.tbc_demux_results.restype = ctypes.c_int
    _tbclient = lib
    return _tbclient


def aegis128l_mac_ptr() -> Optional[Callable[[int, int], bytes]]:
    """(address, nbytes) -> 16-byte tag over raw memory — the zero-copy
    sibling of aegis128l_mac for numpy-array bodies."""
    if aegis128l_mac() is None:
        return None
    lib = ctypes.CDLL(_lib_built or _LIB)
    fn = lib.aegis128l_mac
    fn.argtypes = [ctypes.c_void_p, ctypes.c_uint64, ctypes.c_char_p]
    fn.restype = None

    def mac_ptr(addr: int, size: int) -> bytes:
        out = ctypes.create_string_buffer(16)
        fn(addr, size, out)
        return out.raw

    return mac_ptr
