"""Device-plane observability: per-kernel cost/roofline attribution,
the device memory ledger, and transfer-bandwidth accounting.

The device-plane sibling of the round-19 cluster plane
(vsr/peerstats.py), wired through the same tracer registry
(docs/OBSERVABILITY.md "Device plane"):

  - **Cost model.** Every JIT_ENTRIES kernel call records its observed
    argument shapes (`note_call`, duck-typed `.shape`/`.dtype` reads —
    jax-free, sync-free metadata). `cost_table()` re-lowers each
    (entry, bucket shape) against `jax.ShapeDtypeStruct` specs and
    reads `lowered.compile().cost_analysis()` for static FLOPs and
    bytes-accessed (graceful n/a when the backend doesn't report),
    then joins them with the round-11 `device.step.<entry>` wall times
    to publish achieved GFLOP/s, achieved GB/s, and a compute-vs-
    memory-bound roofline classification (static arithmetic intensity
    vs the backend balance point).
  - **Memory ledger.** tracer.device_mem_* owner-tagged gauges
    (`device.mem.<owner>.bytes`): the dispatch scratch ring's buckets
    and the balance tables — reconciled against
    `jax.local_devices()[0].memory_stats()` where the backend reports it, with high-water tracking surfaced as the
    bench-gated `device_mem_high_water_bytes` lifecycle flat key.
  - **Transfer bandwidth.** The `device.xfer.{h2d,d2h}.gbps`
    histograms (stamped in tracer.device_finish, i.e. only inside the
    sanctioned sync seams) plus a bytes-per-committed-transfer
    efficiency metric.
  - **Surfacing.** `device_status()` is the `GET /device` payload
    (mounted by cli.py next to /cluster); `tools/device_top.py`
    renders it; the Perfetto device lane rides `tracer.export_trace`.

Import discipline: this module NEVER imports jax at module level and
never triggers a fresh jax import at runtime — the cost model and the
memory_stats reconciliation only touch jax when the jax backend
already loaded it (`sys.modules` check), so every numpy-backend
endpoint answers sanely with no jax loaded (round-13 jax-free-parent
rule, asserted by the existing import test).
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Any, Dict, Optional, Tuple

from tigerbeetle_tpu import tracer
from tigerbeetle_tpu.tidy import runtime as tidy_runtime

_lock = tidy_runtime.make_lock("devicestats")
_shapes: Dict[str, Dict[str, dict]] = {}  # tidy: guarded-by=_lock
_costs: Dict[Tuple[str, str], Optional[dict]] = {}  # tidy: guarded-by=_lock
_SHAPES_PER_ENTRY_MAX = 16  # bucket shapes are power-of-two padded: few

# entry name -> module holding the jitted callable (resolved from
# sys.modules only — never a fresh import; see module docstring).
_ENTRY_MODULES = {  # tidy: atomic — immutable constant table, never written after import
    "create_transfers_fast": "tigerbeetle_tpu.ops.commit",
    "register_accounts": "tigerbeetle_tpu.ops.commit",
    "write_balances": "tigerbeetle_tpu.ops.commit",
    "read_balances": "tigerbeetle_tpu.ops.commit",
    "create_transfers_exact": "tigerbeetle_tpu.ops.commit_exact",
}

# Roofline balance point (FLOPs per byte at which the machine is
# compute- and memory-balanced): static arithmetic intensity below it
# classifies memory-bound, above compute-bound. An accelerator's comes
# from its published peaks, keyed by the device_kind jax reports; a
# device that is not in the table gets NO classification (bound null),
# never another part's ratio. XLA-CPU keeps an order-of-magnitude host
# ratio; TIGERBEETLE_TPU_ROOFLINE_FLOP_PER_BYTE overrides either.
_DEVICE_PEAKS = {  # tidy: atomic — immutable constant table, never written after import
    # device_kind: (peak FLOP/s, peak memory bytes/s) — Google Cloud
    # documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
    "TPU v5 lite": (197e12, 819e9),
}
_CPU_BALANCE = 8.0


def _spec(x) -> tuple:
    """Shape/dtype spec of one call argument — duck-typed metadata
    reads only (works on numpy arrays AND device handles without a
    sync), recursing through NamedTuple pytrees (LedgerState,
    TransferBatch) and plain sequences; anything else rides verbatim
    as a literal (static args: tile sizes, sweep counts, flags)."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return ("arr", tuple(int(d) for d in x.shape), str(x.dtype))
    if isinstance(x, tuple) and hasattr(x, "_fields"):
        return ("nt", type(x), tuple(_spec(f) for f in x))
    if isinstance(x, (tuple, list)):
        return ("seq", isinstance(x, list), tuple(_spec(f) for f in x))
    return ("lit", x)


def _spec_key(spec) -> str:
    """Compact stable key over the array leaves of a spec tree —
    "8192x4:uint32|8192:uint32|t=256"-style, the per-bucket cost-row
    identity."""
    parts = []

    def walk(s):
        kind = s[0]
        if kind == "arr":
            parts.append("x".join(str(d) for d in s[1]) + ":" + s[2])
        elif kind in ("nt", "seq"):
            for f in s[2]:
                walk(f)
        else:
            parts.append(f"={s[1]!r}")

    for s in spec:
        walk(s)
    return "|".join(parts)


def note_call(entry: str, args: tuple, kwargs: Optional[dict] = None,
              bucket: Optional[int] = None) -> None:
    """Record the argument shapes of one jit-entry call (called next to
    tracer.device_dispatch/device_step at the existing seams). Cheap:
    metadata reads + one dict insert; bounded per entry. `bucket` tags
    the row with its scratch-ring pad size so bucket retirement can
    drop the matching cost rows."""
    if not tracer.enabled():
        return
    spec = tuple(_spec(a) for a in args)
    kwspec = {k: _spec(v) for k, v in (kwargs or {}).items()}
    key = _spec_key(spec)
    if kwspec:
        key += "|" + ",".join(
            f"{k}{_spec_key((v,))}" for k, v in sorted(kwspec.items())
        )
    with _lock:
        rows = _shapes.setdefault(entry, {})
        if key not in rows and len(rows) >= _SHAPES_PER_ENTRY_MAX:
            return
        rows[key] = {"spec": spec, "kwspec": kwspec, "bucket": bucket}


def retire_bucket(bucket: int) -> None:
    """Drop every recorded shape row (and cached cost) tagged with a
    retired scratch-ring bucket — the cost-table half of the
    tracer.device_mem_retire_prefix gauge retirement, so the registry
    and the /device cost table both stay bounded under bucket churn."""
    with _lock:
        for entry, rows in list(_shapes.items()):
            dead = [k for k, r in rows.items() if r["bucket"] == bucket]
            for k in dead:
                del rows[k]
                _costs.pop((entry, k), None)
            if not rows:
                del _shapes[entry]


def observed_shapes() -> Dict[str, list]:
    with _lock:
        return {e: sorted(rows) for e, rows in _shapes.items()}


def _jax_if_loaded():
    """The jax module ONLY if something else already imported it — the
    numpy backend must never pay (or break on) a jax import because an
    observability endpoint was scraped."""
    return sys.modules.get("jax")


def _entry_callable(entry: str):
    mod = sys.modules.get(_ENTRY_MODULES.get(entry, ""))
    return getattr(mod, entry, None) if mod else None


def _rebuild(spec, jax):
    kind = spec[0]
    if kind == "arr":
        return jax.ShapeDtypeStruct(spec[1], spec[2])
    if kind == "nt":
        return spec[1](*(_rebuild(f, jax) for f in spec[2]))
    if kind == "seq":
        seq = tuple(_rebuild(f, jax) for f in spec[2])
        return list(seq) if spec[1] else seq
    return spec[1]


def _cost_analyze(entry: str, row: dict) -> Optional[dict]:
    """Static cost of one (entry, bucket shape): lower + compile against
    ShapeDtypeStructs, read cost_analysis(). Every failure mode —
    no jax, unregistered callable, a backend that doesn't lower from
    specs or doesn't report costs — is an n/a (None), never a raise:
    the cost model is telemetry, not a dependency."""
    jax = _jax_if_loaded()
    fn = _entry_callable(entry)
    if jax is None or fn is None or not hasattr(fn, "lower"):
        return None
    try:
        args = tuple(_rebuild(s, jax) for s in row["spec"])
        kwargs = {k: _rebuild(s, jax) for k, s in row["kwspec"].items()}
        ca = fn.lower(*args, **kwargs).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if not isinstance(ca, dict):
            return None
        flops = ca.get("flops")
        nbytes = ca.get("bytes accessed")
        out = {}
        if isinstance(flops, (int, float)) and flops > 0:
            out["flops"] = float(flops)
        if isinstance(nbytes, (int, float)) and nbytes > 0:
            out["bytes_accessed"] = float(nbytes)
        return out or None
    except Exception:  # noqa: BLE001 — any backend/lowering quirk is an n/a
        return None


def cost_for(entry: str, shape_key: str) -> Optional[dict]:
    """Cached static cost for one observed bucket shape (None = n/a)."""
    with _lock:
        ck = (entry, shape_key)
        if ck in _costs:
            return _costs[ck]
        row = _shapes.get(entry, {}).get(shape_key)
    cost = _cost_analyze(entry, row) if row is not None else None
    with _lock:
        _costs[ck] = cost
    return cost


def _backend_platform() -> Optional[str]:
    jax = _jax_if_loaded()
    if jax is None:
        return None
    try:
        return jax.default_backend()
    except Exception:  # noqa: BLE001 — an uninitialized backend is an n/a
        return None


def _balance_flop_per_byte() -> Optional[float]:
    """The balance point of the device this process runs on; None where
    it is not known (no jax loaded, or a device_kind that _DEVICE_PEAKS
    does not list)."""
    env = os.environ.get("TIGERBEETLE_TPU_ROOFLINE_FLOP_PER_BYTE")  # tidy: allow=env-read — roofline calibration knob, read per call so tests/hosts can retune without reimport
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    platform = _backend_platform()
    if platform == "cpu":
        return _CPU_BALANCE
    if platform is None:
        return None
    peaks = _DEVICE_PEAKS.get(_jax_if_loaded().devices()[0].device_kind)
    return peaks[0] / peaks[1] if peaks else None


def classify(flops: Optional[float], nbytes: Optional[float]) -> Optional[str]:
    """Roofline bound classification from STATIC cost: arithmetic
    intensity (FLOPs / bytes accessed) against the device's balance
    point. "n/a" whenever either static number is missing, None (null
    on the wire) on a device whose peaks are not known — a wrong
    classification is worse than none."""
    if not flops or not nbytes:
        return "n/a"
    balance = _balance_flop_per_byte()
    if balance is None:
        return None
    return "compute" if flops / nbytes > balance else "memory"


def cost_table(snap: Optional[dict] = None) -> list:
    """The per-entry cost/roofline rows: one row per (entry, observed
    bucket shape), static cost joined with the runtime device.step /
    device.<entry> wall times. Achieved GB/s and GFLOP/s come from the
    static per-call cost over the measured mean ms/call; bound is the
    static-intensity roofline side. Rows sort by entry then shape."""
    if snap is None:
        snap = tracer.snapshot()
    rows = []
    for entry, shape_rows in observed_shapes().items():
        rt = snap.get(f"device.step.{entry}") or snap.get(f"device.{entry}")
        ms_call = (rt["avg_us"] / 1e3) if rt else None
        for key in shape_rows:
            cost = cost_for(entry, key) or {}
            flops = cost.get("flops")
            nbytes = cost.get("bytes_accessed")
            row = {
                "entry": entry,
                "shape": key,
                "calls": rt["count"] if rt else 0,
                "ms_per_call": round(ms_call, 4) if ms_call else None,
                "flops": flops,
                "bytes_accessed": nbytes,
                "bound": classify(flops, nbytes),
            }
            if ms_call and flops:
                row["achieved_gflops"] = round(flops / (ms_call * 1e6), 3)
            if ms_call and nbytes:
                row["achieved_gbps"] = round(nbytes / (ms_call * 1e6), 3)
            rows.append(row)
    rows.sort(key=lambda r: (r["entry"], r["shape"]))
    return rows


def _jax_memory_stats() -> Optional[dict]:
    """The backend's own device-memory report, where it exists (TPU/GPU
    runtimes publish bytes_in_use/peak_bytes_in_use; CPU returns None)
    — the reconciliation column next to the owner-tagged ledger."""
    jax = _jax_if_loaded()
    if jax is None:
        return None
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 — backends without memory_stats are an n/a
        return None
    if not isinstance(stats, dict):
        return None
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
    out = {k: int(stats[k]) for k in keep if k in stats}
    return out or None


def xfer_summary(snap: Optional[dict] = None) -> dict:
    """Transfer-bandwidth ledger: per-direction achieved GB/s
    percentiles (the RAW-MB/s histograms read back via the p50_us
    convention — tracer.device_finish documents it), cumulative byte
    counters, and bytes-per-committed-transfer (total transfer volume
    over sm.stored_transfers — the wire efficiency of the device
    datapath; n/a before any transfer committed)."""
    if snap is None:
        snap = tracer.snapshot()
    out: Dict[str, Any] = {}
    for d in ("h2d", "d2h"):
        hist = snap.get(f"device.xfer.{d}.gbps")
        if hist:
            out[f"{d}_gbps_p50"] = hist["p50_us"]
            out[f"{d}_gbps_p99"] = hist["p99_us"]
            out[f"{d}_windows"] = hist["count"]
        cnt = snap.get(f"device.{d}_bytes")
        out[f"{d}_bytes"] = cnt["count"] if cnt else 0
    stored = snap.get("sm.stored_transfers", {}).get("count", 0)
    if stored:
        out["bytes_per_transfer"] = round(
            (out["h2d_bytes"] + out["d2h_bytes"]) / stored, 1
        )
    return out


def device_status(replica=None) -> dict:
    """The GET /device payload (cli.py mounts it next to /cluster):
    cost/roofline table, memory ledger (+ the backend's own
    memory_stats where available), transfer summary, and the open
    dispatch-window depths. Answers sanely on every backend — numpy
    reports an empty cost table, zero ledgers, and backend "none"."""
    snap = tracer.snapshot()
    mem = tracer.device_mem_totals()
    jax_mem = _jax_memory_stats()
    if jax_mem:
        mem["backend_reported"] = jax_mem
    status = {
        "backend": _backend_platform() or "none",
        "tracing": tracer.enabled(),
        "entries": cost_table(snap),
        "mem": mem,
        "xfer": xfer_summary(snap),
        "inflight": tracer.device_inflight(),
    }
    if replica is not None:
        depth = getattr(replica, "commit_depth", None)
        if depth is not None:
            status["commit_depth"] = int(depth)
    return status


def reset() -> None:
    """Drop recorded shapes and cached costs (test isolation; the
    tracer-side ledgers reset with tracer.reset())."""
    with _lock:
        _shapes.clear()
        _costs.clear()
