"""The thread-topology manifest: what the ownership pass analyzes.

The pipeline runs four thread roles (docs/COMMIT_PIPELINE.md):

  - `loop`   — the asyncio event loop (or the simulator/test main
               thread standing in for it): all VSR protocol state.
  - `wal`    — the WalWriter thread (vsr/journal.py): durable WAL
               writes.
  - `commit` — the commit-execution context: the CommitExecutor thread
               when the overlapped stage is attached, the event loop
               itself on the serial fallback. State-machine execution
               and everything "commit-thread-owned" lives here.
  - `store`  — the StoreExecutor thread: deferred groove/index writes
               and compaction beats.

A class is analyzed when it appears here or carries any `# tidy:`
annotation. Method→role resolution order: `thread=` annotation on the
def, a `threading.Thread(target=self._x, name=...)` construction (the
name maps through THREAD_NAME_ROLES), the METHOD_ROLES entry below,
intra-class call-graph propagation from resolved methods, and finally
the class's default role. Cross-class call edges are NOT traced — the
role of a public entry point is a declaration (exactly the ownership
comment it replaces), which keeps the pass honest and the annotations
load-bearing.
"""

from __future__ import annotations

ROLES = frozenset(("loop", "wal", "commit", "store", "any"))

# threading.Thread(name=...) literal -> role of its target method.
THREAD_NAME_ROLES = {
    "wal-writer": "wal",
    "commit-executor": "commit",
    "store-executor": "store",
}

# Barrier callables (names) accepted by `barrier=` annotations: a
# cross-thread access ordered by one of these is sequenced, not racing.
BARRIERS = frozenset(("store_barrier", "drain", "wait", "quiesce", "join"))

# (repo-relative file, class) -> default role set ("|"-joined) for
# methods the resolution steps above leave unassigned. These are the
# pipeline-coupled classes named in the ownership design; annotated
# classes not listed here default to "loop". A multi-role default
# (DurableIndex, Grid) says "this object is shared between the commit
# and store contexts wholesale" — its attributes then REQUIRE explicit
# declarations, which is the point.
OWNERSHIP_CLASSES = {
    ("tigerbeetle_tpu/vsr/pipeline.py", "CommitExecutor"): "loop",
    ("tigerbeetle_tpu/vsr/pipeline.py", "StoreExecutor"): "loop",
    ("tigerbeetle_tpu/vsr/journal.py", "WalWriter"): "loop",
    ("tigerbeetle_tpu/lsm/tree.py", "DurableIndex"): "commit|store",
    ("tigerbeetle_tpu/models/state_machine.py", "StateMachine"): "commit",
    ("tigerbeetle_tpu/io/grid.py", "Grid"): "commit|store",
    ("tigerbeetle_tpu/net/bus.py", "_Conn"): "loop",
    ("tigerbeetle_tpu/net/bus.py", "ReplicaServer"): "loop",
}

# Modules whose top-level mutable globals are ownership-checked the same
# way (functions stand in for methods; `with <lockname>:` scopes count).
# value = default role for the module's functions.
OWNERSHIP_MODULES = {
    "tigerbeetle_tpu/tracer.py": "any",
    "tigerbeetle_tpu/devicestats.py": "any",
}

# --- determinism lint scope ---------------------------------------------

# The deterministic core: every replica must be a pure function of
# (state, ordered batch). vsr/clock.py is the ONE sanctioned wall-clock
# reader (Marzullo-synchronized timestamps enter state only through the
# primary's prepare headers, which the batch carries).
DETERMINISM_INCLUDE = (
    "tigerbeetle_tpu/models",
    "tigerbeetle_tpu/lsm",
    "tigerbeetle_tpu/vsr",
    "tigerbeetle_tpu/ops",
)
DETERMINISM_EXCLUDE = ("tigerbeetle_tpu/vsr/clock.py",)

# --- jaxlint: device hot-path lint scope ---------------------------------

# Modules the host-sync / retrace / reduction passes analyze: the jitted
# kernels themselves (ops/, parallel/) and the host dispatcher that calls
# them (models/state_machine.py). Like the ownership pass, scope is a
# declaration — cross-module call edges resolve only within this set.
JAXLINT_MODULES = (
    "tigerbeetle_tpu/ops/commit.py",
    "tigerbeetle_tpu/ops/commit_exact.py",
    "tigerbeetle_tpu/models/state_machine.py",
    "tigerbeetle_tpu/parallel/sharding.py",
    "tigerbeetle_tpu/parallel/sharded_ops.py",
)

# Jit entry points (by callable tail name) → their static argnames. A
# call site passing a batch-dependent value in a static position is a
# retrace per value; a device value returned by one of these is a sync
# when materialized (bool/int/float/np.asarray/.item).
JIT_ENTRIES = {
    "create_transfers_fast": (),
    "create_transfers_exact": ("max_sweeps", "has_pv", "has_chains"),
    "register_accounts": (),
    "write_balances": (),
    "read_balances": (),
}

# (repo-relative file, qualified function) pairs forming the SANCTIONED
# dispatch/finish seam: the only host-side places allowed to materialize
# device values (device→host sync) or block_until_ready. Everything else
# must stay async — a sync elsewhere silently serializes the overlapped
# pipeline (docs/COMMIT_PIPELINE.md split-phase dispatch).
JAXLINT_SYNC_SEAM = frozenset((
    ("tigerbeetle_tpu/models/state_machine.py", "StateMachine._commit_fast_device"),
    ("tigerbeetle_tpu/models/state_machine.py", "StateMachine.create_transfers_finish"),
    ("tigerbeetle_tpu/models/state_machine.py", "StateMachine._exact_finish"),
    ("tigerbeetle_tpu/models/state_machine.py", "StateMachine._read_balances"),
))

# Functions whose results count as shape-stabilized (bucket-padded):
# jit-entry arguments produced by these escape the retrace-shape rule.
JAXLINT_PAD_HELPERS = frozenset((
    "_device_batch", "_pad_slots", "pad1", "p1",
))

# --- absint: limb-width abstract interpretation scope --------------------

# file → limb width in bits. Every +, -, *, << in these files must be
# PROVEN to stay within the width from annotated entry ranges (`range=`),
# or carry an inline `allow=` with the reason (intentional wrap carry
# tricks).
ABSINT_TARGETS = {
    "tigerbeetle_tpu/ops/u128.py": 32,
    "tigerbeetle_tpu/lsm/scan.py": 64,
}

# --- nativecheck: C-boundary analysis scope ------------------------------

# Every C-family file under csrc/ must either be scanned (layout parity +
# ctypes ABI + prototype extraction) or carry an explicit exclusion with
# its reason here — the pass asserts the scanned set equals the csrc/
# glob minus these, so a new C file cannot ride in unanalyzed.
NATIVE_C_SOURCES = (
    "csrc/busio.c",
    "csrc/hostops.c",
    "csrc/aegis128l.c",
    "csrc/tb_client.c",
    "csrc/tb_client.h",
)
NATIVE_C_EXCLUDE = {
    "csrc/cpp_sample.cpp":
        "C++17 embedder sample (templates/RAII outside cparse's C "
        "subset); compiled and exercised end-to-end by "
        "tests/test_cpp_client.py, exposes no ctypes surface",
    "csrc/tb_client.hpp":
        "header-only C++ wrapper over tb_client.h; the C ABI underneath "
        "is the scanned contract (tb_client.h), the wrapper is covered "
        "by tests/test_cpp_client.py",
}

# (repo-relative C file, function) pairs the C bounds-absint interprets.
# Each carries a `/* tidy: range=/bound= */` entry annotation in source;
# a listed function that fails to parse or goes missing is a finding
# (c-parse), never a silent skip.
NATIVE_ABSINT_FUNCS = (
    ("csrc/busio.c", "busio_scan"),
    ("csrc/hostops.c", "gallop_lower_u32"),
    ("csrc/hostops.c", "hostops_intersect_u32"),
    ("csrc/hostops.c", "hostops_gallop_mark_u32"),
    ("csrc/hostops.c", "hostops_merge_kv_bloom"),
)

# Directories the pointer-lifetime lint walks for `.ctypes.data` captures
# (native call sites live in the package and the tools).
NATIVE_LIFETIME_SCAN_DIRS = ("tigerbeetle_tpu", "tools")

# --- vsrlint: VSR protocol lint scope ------------------------------------

# Modules the protocol lints analyze (the consensus-critical layer: the
# replica state machine, the WAL journal, the durable superblock, and
# the wire ingress). Like every other domain, scope is a declaration.
VSRLINT_MODULES = (
    "tigerbeetle_tpu/vsr/replica.py",
    "tigerbeetle_tpu/vsr/journal.py",
    "tigerbeetle_tpu/vsr/superblock.py",
    "tigerbeetle_tpu/net/bus.py",
)

# Where the Command enum and the replica dispatch table live (the
# handler-exhaustiveness rule parses both, no runtime import).
VSRLINT_COMMAND_MODULE = "tigerbeetle_tpu/vsr/header.py"
VSRLINT_DISPATCH = ("tigerbeetle_tpu/vsr/replica.py", "on_message")

# Command members that deliberately have NO replica dispatch handler.
# Every entry carries the reason (where the command IS handled); an
# exempted command that grows a handler becomes a stale-exemption
# finding, so this table cannot rot.
VSRLINT_COMMAND_EXEMPT = {
    "RESERVED":
        "command 0 is the invalid-frame sentinel — the codec and "
        "Header.verify reject it before dispatch, it never reaches "
        "on_message",
    "PING_CLIENT":
        "answered at the bus ingress (net/bus.py ReplicaServer pre-"
        "dispatch fast path) — client pings never reach the replica "
        "state machine",
    "PONG_CLIENT":
        "client-bound: emitted by ReplicaServer in answer to "
        "PING_CLIENT, consumed by client.py — a replica never receives "
        "one",
    "REPLY":
        "client-bound: produced by the commit path (ReplyBuilder), "
        "consumed by client.py and testing SimClient — replicas route "
        "it outward, never inward",
    "EVICTION":
        "client-bound session eviction, consumed by client.py / "
        "SimClient",
    "BUSY":
        "client-bound admission shed, consumed by client.py / "
        "SimClient",
}

# Inbound header fields the wire-taint rule treats as attacker-tainted
# until they pass a validation guard (comparison / bounds check / MAC
# verify) inside the handler.
VSRLINT_WIRE_FIELDS = frozenset((
    "view", "op", "commit", "commit_min", "commit_max", "op_checkpoint",
    "checksum", "parent", "client", "request", "replica", "timestamp",
    "operation", "context", "size", "session", "epoch",
))

# Replica/journal/superblock state attributes that constitute protocol
# state: a wire-tainted value must be validated before being assigned
# into any of these.
VSRLINT_STATE_FIELDS = frozenset((
    "view", "log_view", "op", "commit_min", "commit_max", "status",
    "op_checkpoint", "checksum_floor", "timestamp_max", "view_durable",
))

# Fields whose assignments must be PROVEN non-decreasing (max() form,
# guarded adoption, positive increment) or carry an explicit
# `# tidy: monotonic=<field> — reason` annotation (the sanctioned-bump
# discipline, same shape as absint's `range=`). `op` is here although it
# legitimately decreases on view-change truncation — exactly those two
# sites carry the annotation with the truncation proof.
VSRLINT_MONOTONIC_FIELDS = frozenset((
    "view", "log_view", "op", "commit_min", "commit_max",
    "op_checkpoint", "checksum_floor", "timestamp_max", "sequence",
    "config_epoch",
))

# Functions that ESTABLISH state rather than advance it: constructors
# and the disk-image formatter. Monotonicity applies to the running
# replica; recovery paths that re-load durable state annotate instead
# (the annotation carries the durability argument).
# Boot-path functions rebuild in-memory protocol state from durable
# storage: monotonicity is a WITHIN-boot invariant (the conformance
# checker in tidy/protomodel.py enforces exactly the same per-boot
# semantics at runtime), so these reset/reload sites are sanctioned
# wholesale rather than annotated line by line.
VSRLINT_MONOTONIC_INIT_FUNCS = frozenset(
    ("__init__", "format", "open", "recover")
)

# Cluster-size range the quorum-arithmetic pass exhaustively evaluates
# (reference constants.zig replicas_max) and the standby counts it
# proves irrelevant to quorum sizes.
VSRLINT_QUORUM_REPLICA_RANGE = (1, 6)
VSRLINT_QUORUM_STANDBY_RANGE = (0, 6)

# --- marker scan scope ---------------------------------------------------

# Directories / top-level scripts covered by the banned-marker scan.
# tests/fixtures is excluded: fixture modules deliberately contain
# violations for the analyzer's own test suite.
MARKER_SCAN_DIRS = ("tigerbeetle_tpu", "tools", "tests")
MARKER_SCAN_FILES = ("bench.py", "profile_e2e.py", "profile_exact.py", "__graft_entry__.py")
MARKER_SCAN_EXCLUDE_DIRS = ("tests/fixtures",)

# Stub markers and debug leftovers (the reference tidy.zig banned-word
# family). Spelled split so this file never matches its own scan.
BANNED_MARKERS = (
    "NotImplemented" + "Error",
    "TO" + "DO",
    "FIX" + "ME",
    "X" + "XX",
    "breakpoint" + "(",
    "import" + " pdb",
)

# Module-docstring requirement applies to the package only (tests and
# tools document themselves more loosely).
DOCSTRING_SCAN_DIRS = ("tigerbeetle_tpu",)
