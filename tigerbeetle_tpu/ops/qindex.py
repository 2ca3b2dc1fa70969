"""Device-resident query-index key pipeline: fused fold56 composite-key
build (+ optional on-device sort) for the secondary query index.

The store stage's dominant row used to be `_store_query_index`'s host
work: five fold56 passes + a 5x-batch key fill per commit, then a full
radix re-sort of the memtable at every flush (~11 ms/batch on the dev
container). This module moves the key build onto the device as ONE fused
jit kernel over uint32 limbs (no x64 requirement, ops/u128.py style):

    key.lo = tag << 56 | fold56(field)   ->  limbs (lo0, lo1)
    key.hi = timestamp                   ->  payload (ts0, ts1)
    value  = object-log row              ->  payload val

The kernel emits the full 5-tag block in the merge kernel's device run
format (keys (N, 3) = [lo0, lo1, pad], payload (N, 3) = [hi0, hi1, val],
pad-flag most significant so padding sorts strictly last). Two variants:

  - `query_index_keys` — build only, natural (tag-block) order. Used
    where the device sort does not pay (XLA CPU variadic sort is
    comparator-driven and loses ~7x to the host C radix): the run is
    still a valid SORTED run whenever the batch's queryable columns are
    constant (lsm/scan.query_columns_constant — blocks ascend by tag,
    equal keys keep insertion order), which is the low-cardinality
    common case; otherwise the flush falls back to the host radix.
  - `query_index_keys_sorted` — build + 3-key stable lax.sort
    (pad, lo1, lo0), the accelerator path: the run leaves the kernel
    lo-major sorted, so memtable flushes fold sorted device runs through
    `merge_kernel_tiled` and only materialize at table-build boundaries.

Dispatch is SPLIT-PHASE like the commit kernel: `build_run` stages,
dispatches, and returns a `QueryKeyRun` handle without any device->host
sync; materialization happens batches later — at flush, or early via the
store stage's idle prefetch (`vsr/pipeline.StoreExecutor` idle poll) —
so batch N+1's key build overlaps batch N's merge drain. Byte-equality
with the host key build (including fold56 xor-fold edge cases) is
enforced by tests/test_qindex.py property tests; `tidy/absint.py` proves
the limb arithmetic in-width (ABSINT_TARGETS, width 32).
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np

from tigerbeetle_tpu import devicestats, tracer
from tigerbeetle_tpu.lsm import scan
from tigerbeetle_tpu.ops.merge import bucket_pow2

U32 = jnp.uint32

# Local mirrors of the scan-module tags so the composite constants fold
# inside this module's absint domain; asserted against the single source.
_TAG_UD128 = 5
_TAG_UD64 = 6
_TAG_UD32 = 7
_TAG_LEDGER = 9
_TAG_CODE = 10

assert tuple(t for t, _lo, _hi in scan.QUERY_TAG_FIELDS) == (
    _TAG_UD128, _TAG_UD64, _TAG_UD32, _TAG_LEDGER, _TAG_CODE
)

# Staged column layout (uint32 limbs of the queryable fields, one (n, 9)
# h2d transfer): ud128 as 4 limbs, ud64 as 2, then the three u32 fields.
_COL_UD128_L0, _COL_UD128_L1, _COL_UD128_H0, _COL_UD128_H1 = 0, 1, 2, 3
_COL_UD64_0, _COL_UD64_1 = 4, 5
_COL_UD32, _COL_LEDGER, _COL_CODE = 6, 7, 8


def _fold56_u64(lo0, lo1):
    """fold56 of a u64 in (lo0, lo1) uint32 limbs -> 56-bit (f0, f1)
    limbs (f1 < 2^24). Identity below 2^56, xor-fold above — bit-for-bit
    the limb re-expression of lsm/scan.fold56(lo)."""
    f0 = lo0 ^ (lo1 >> 24)
    f1 = lo1 & jnp.uint32(0xFFFFFF)
    return f0, f1


def _fold56_u128(lo0, lo1, hi0, hi1):
    """fold56 of a u128 in uint32 limbs — the hi word folds in as
    ((hi & MASK56) << 1 & MASK56) ^ (hi >> 55), limb-exact vs
    lsm/scan.fold56(lo, hi). The << 1 is written pre-masked
    ((hi0 & 0x7FFFFFFF) << 1) so every shift provably fits 32 bits."""
    a0, a1 = _fold56_u64(lo0, lo1)
    b0 = (hi0 & jnp.uint32(0x7FFFFFFF)) << 1
    b1 = (((hi1 & jnp.uint32(0xFFFFFF)) << 1) | (hi0 >> 31)) & jnp.uint32(
        0xFFFFFF
    )
    f0 = a0 ^ b0 ^ (hi1 >> 23)
    f1 = a1 ^ b1
    return f0, f1


def _key_block(tag, f0, f1, pad):  # tidy: range=tag:0..10,f1:0..0xFFFFFF
    """One tag block's (n, 3) key limbs: key.lo = tag << 56 | folded, so
    the tag lands in lo1's top byte — f1 < 2^24 makes the OR disjoint,
    and tag ≤ 10 keeps the shift in-width (both PROVEN by tidy/absint
    from this def's declared ranges)."""
    k1 = f1 | jnp.uint32(tag << 24)
    return jnp.stack([f0, k1, pad], axis=1)


def _build_blocks(cols, ts, rows, pad):
    """The shared kernel body: per-tag fold56 -> composite-key limbs ->
    5 stacked blocks in tag order (= ascending key.lo block order)."""
    zero = jnp.zeros_like(rows)
    f128_0, f128_1 = _fold56_u128(
        cols[:, _COL_UD128_L0], cols[:, _COL_UD128_L1],
        cols[:, _COL_UD128_H0], cols[:, _COL_UD128_H1],
    )
    f64_0, f64_1 = _fold56_u64(cols[:, _COL_UD64_0], cols[:, _COL_UD64_1])
    keys = jnp.concatenate([
        _key_block(_TAG_UD128, f128_0, f128_1, pad),
        _key_block(_TAG_UD64, f64_0, f64_1, pad),
        # u32 fields sit below 2^56: fold56 is the identity, hi limb 0.
        _key_block(_TAG_UD32, cols[:, _COL_UD32], zero, pad),
        _key_block(_TAG_LEDGER, cols[:, _COL_LEDGER], zero, pad),
        _key_block(_TAG_CODE, cols[:, _COL_CODE], zero, pad),
    ])
    # The payload (timestamp limbs + object-log row) is identical for
    # every tag block of a record.
    pay = jnp.tile(jnp.stack([ts[:, 0], ts[:, 1], rows], axis=1), (5, 1))
    return keys, pay


@jax.jit
def query_index_keys(cols, ts, rows, pad):
    """Fused 5-tag composite-key build, natural block order (pads flagged
    in the key's pad limb but left in place — callers strip per block)."""
    return _build_blocks(cols, ts, rows, pad)


@jax.jit
def query_index_keys_sorted(cols, ts, rows, pad):
    """Key build + stable lo-major device sort: 3-key (pad, lo1, lo0)
    variadic sort carries the payload, pads sort strictly last, equal
    keys keep block/insertion order — the same stable order the host
    radix (sort_kv) produces."""
    # Metadata only: names the two phases in a profiler trace.
    with jax.named_scope("key_build"):
        keys, pay = _build_blocks(cols, ts, rows, pad)
    with jax.named_scope("sort"):
        s = jax.lax.sort(
            (keys[:, 2], keys[:, 1], keys[:, 0], pay[:, 0], pay[:, 1], pay[:, 2]),
            num_keys=3, is_stable=True,
        )
    return (
        jnp.stack([s[2], s[1], s[0]], axis=1),
        jnp.stack([s[3], s[4], s[5]], axis=1),
    )


def device_sort_pays() -> bool:
    """Whether the on-device sort variant pays (accelerator backends).
    Mirrors ops/merge.device_merge_pays — one policy for the whole
    device query-index pipeline, TIGERBEETLE_TPU_DEVICE_MERGE overrides."""
    from tigerbeetle_tpu.ops.merge import device_merge_pays

    return device_merge_pays()


def stage_query_batch(recs: np.ndarray, rows: np.ndarray, tstamp: np.ndarray):
    """Host staging: wire columns -> uint32 limb arrays, bucket-padded
    via merge.bucket_pow2 (pow-2 ≥ MERGE_TILE) so (a) the kernels
    compile once per bucket and (b) 5·n_pad stays a MERGE_TILE multiple
    for the device fold — the same single-source bucket formula as
    merge._pad_pow2, so a tile retune cannot desynchronize the two."""
    n = len(recs)
    n_pad = bucket_pow2(n)
    cols = np.zeros((n_pad, 9), dtype=np.uint32)
    cols[:n, _COL_UD128_L0] = recs["user_data_128_lo"] & 0xFFFFFFFF
    cols[:n, _COL_UD128_L1] = recs["user_data_128_lo"] >> np.uint64(32)
    cols[:n, _COL_UD128_H0] = recs["user_data_128_hi"] & 0xFFFFFFFF
    cols[:n, _COL_UD128_H1] = recs["user_data_128_hi"] >> np.uint64(32)
    cols[:n, _COL_UD64_0] = recs["user_data_64"] & 0xFFFFFFFF
    cols[:n, _COL_UD64_1] = recs["user_data_64"] >> np.uint64(32)
    cols[:n, _COL_UD32] = recs["user_data_32"]
    cols[:n, _COL_LEDGER] = recs["ledger"]
    cols[:n, _COL_CODE] = recs["code"]
    ts = np.zeros((n_pad, 2), dtype=np.uint32)
    ts[:n, 0] = tstamp & np.uint64(0xFFFFFFFF)
    ts[:n, 1] = tstamp >> np.uint64(32)
    rows_p = np.zeros(n_pad, dtype=np.uint32)
    rows_p[:n] = rows
    pad = np.zeros(n_pad, dtype=np.uint32)
    pad[n:] = 1
    return cols, ts, rows_p, pad


class QueryKeyRun:
    """One committed batch's composite-key block as a dispatched (not yet
    synced) device run — the split-phase handle of the query-index
    pipeline. `materialize()` is the SANCTIONED device→host sync point
    (jaxlint seam); it is idempotent, so the store stage's idle prefetch
    can pull the transfer forward without changing flush semantics."""

    def __init__(self, keys_dev, pay_dev, n: int, n_pad: int,
                 sorted_: bool, device_sorted: bool, entry: str,
                 t_disp: int) -> None:
        self._keys_dev = keys_dev
        self._pay_dev = pay_dev
        self._n_batch = n
        self._n_pad = n_pad
        self.n = 5 * n  # rows contributed to the memtable
        self.sorted = sorted_
        self._device_sorted = device_sorted
        self._entry = entry
        self._t_disp = t_disp
        self._host: tuple | None = None
        # Memory ledger: this run's device-resident key/payload bytes,
        # released exactly once when the handles drop (materialize) or
        # the fold consumes the run on-chip (finish_dispatch).
        self._ledger_bytes = int(
            getattr(keys_dev, "nbytes", 0) + getattr(pay_dev, "nbytes", 0)
        )
        tracer.device_mem_adjust("query_runs", self._ledger_bytes)
        # materialize() can race itself: the store stage's idle prefetch
        # pulls the transfer forward while a barrier-synchronized reader
        # (commit thread) resolves the same run. One lock per run — both
        # callers get the same cached tuple, device handles are dropped
        # exactly once.
        self._lock = threading.Lock()

    def device_run(self):
        """(keys, payload) device arrays in merge-kernel format — the
        zero-materialization input of the flush's device fold."""
        return self._keys_dev, self._pay_dev

    def materialize(self):
        """(KEY_DTYPE keys, u32 vals) host arrays, pads stripped.
        Idempotent and thread-safe (idle prefetch vs barrier reader)."""
        if self._host is not None:
            return self._host
        with self._lock:
            return self._materialize_locked()

    def finish_dispatch(self, d2h_bytes: int = 0) -> None:
        """Close the dispatch token WITHOUT a host transfer — the device
        fold consumed this run on-chip (`_flush_sorted_kv` calls this at
        its table-build sync, the one d2h of the whole fold), giving
        `device.step.<entry>` its dispatch→sync sample on the primary
        path, where materialize() never runs. Idempotent with
        materialize(): whichever closes the token first wins."""
        with self._lock:
            if self._t_disp:
                tracer.device_finish(
                    self._entry, self._t_disp, d2h_bytes=d2h_bytes
                )
                self._t_disp = 0
            self._ledger_release()

    def _materialize_locked(self):
        if self._host is not None:
            return self._host
        ok = np.asarray(self._keys_dev)
        op = np.asarray(self._pay_dev)
        if self._t_disp:
            tracer.device_finish(
                self._entry, self._t_disp, d2h_bytes=ok.nbytes + op.nbytes
            )
        self._t_disp = 0
        n, n_pad = self._n_batch, self._n_pad
        if n != n_pad:
            if self._device_sorted:
                # Pads carry the sorted-last flag limb: strip the tail.
                ok = ok[: self.n]
                op = op[: self.n]
            else:
                sel = np.concatenate(
                    [np.arange(b * n_pad, b * n_pad + n) for b in range(5)]
                )
                ok = ok[sel]
                op = op[sel]
        from tigerbeetle_tpu.ops.merge import from_device_run

        self._host = from_device_run(ok, op, self.n)
        self._keys_dev = self._pay_dev = None
        self._ledger_release()
        return self._host

    def _ledger_release(self) -> None:
        """Return this run's bytes to the query_runs ledger owner, once
        (callers hold self._lock)."""
        if self._ledger_bytes:
            tracer.device_mem_adjust("query_runs", -self._ledger_bytes)
            self._ledger_bytes = 0

    @property
    def materialized(self) -> bool:
        return self._host is not None


def build_run(recs: np.ndarray, rows: np.ndarray,
              tstamp: np.ndarray) -> QueryKeyRun:
    """Stage + dispatch one batch's key build; no device→host sync."""
    use_device_sort = device_sort_pays()
    cols, ts, rows_p, pad = stage_query_batch(recs, rows, tstamp)
    entry = (
        "query_index_keys_sorted" if use_device_sort else "query_index_keys"
    )
    h2d = cols.nbytes + ts.nbytes + rows_p.nbytes + pad.nbytes
    devicestats.note_call(entry, (cols, ts, rows_p, pad))
    t_disp = tracer.device_dispatch(entry, h2d_bytes=h2d)
    if use_device_sort:
        keys_dev, pay_dev = query_index_keys_sorted(cols, ts, rows_p, pad)
        sorted_ = True
    else:
        keys_dev, pay_dev = query_index_keys(cols, ts, rows_p, pad)
        # Natural block order is already lo-major sorted exactly when the
        # queryable columns are constant (the low-cardinality common
        # case); otherwise the flush re-sorts on the host.
        sorted_ = scan.query_columns_constant(recs)
    return QueryKeyRun(
        keys_dev, pay_dev, len(recs), len(cols), sorted_,
        device_sorted=use_device_sort, entry=entry, t_disp=t_disp,
    )


def fold_runs_device(runs):
    """Fold sorted device runs pairwise through the tiled merge-path
    kernel, oldest first (stability: A-side precedes B-side at equal
    keys). Dispatch-only — returns device arrays plus the real-row count;
    pads sort last and accumulate at the tail."""
    from tigerbeetle_tpu.ops.merge import merge_kernel_tiled

    ka, pa = runs[0].device_run()
    for r in runs[1:]:
        kb, pb = r.device_run()
        devicestats.note_call("merge_kernel_tiled", (ka, pa, kb, pb))
        ka, pa = merge_kernel_tiled(ka, pa, kb, pb)
    return ka, pa, sum(r.n for r in runs)


def materialize_fold(keys_dev, pay_dev, n: int):
    """Sync + strip the device fold's output (sanctioned seam, the
    table-build boundary): (KEY_DTYPE keys, u32 vals) of the n real rows."""
    from tigerbeetle_tpu.ops.merge import from_device_run

    return from_device_run(keys_dev, pay_dev, n)
