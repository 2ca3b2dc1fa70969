"""Device merge kernel for sorted runs that are already on the device.

The reference's compaction inner loop is a serial k-way merge iterator
(/root/reference/src/lsm/compaction.zig:743 + k_way_merge.zig:8): pop the
smallest head among k sorted streams, append to the output block. The TPU
re-expression is sort-free and fully data-parallel:

    stable 2-way merge of sorted runs A (n) and B (m)
      pos_A[i] = i + |{ j : B[j] <  A[i] }|
      pos_B[j] = j + |{ i : A[i] <= B[j] }|
    → two vectorized branchless binary searches (lax-unrolled, the device
      analog of the reference's branchless binary_search.zig) + two
      scatters. O((n+m)·log) lane-parallel work, no data-dependent control
      flow.

Merge order is **lo-major** (the u128 key's low u64 word; ties in lo keep
A-before-B, matching the host tier's point-lookup discipline — see
lsm/store.py). The hi word rides as payload, so compares touch 2 limbs,
not 4; a third pad-flag limb makes padding sort strictly last even when a
real key's lo is all-ones.

Who folds over it: the memtable flush of lazy device key runs
(ops/qindex.fold_runs_device), where the runs never left the chip. Level and storm compaction do NOT:
their runs come off the grid on the host and go back to it on the host,
so lsm/tree.py merges them there (the C k-way merge of lsm/store.py) on
every backend. Stability contract: A's elements precede B's at equal
keys — callers pass the OLDER run as A so duplicate-key secondary indexes
keep insertion (row) order.

Byte-equality vs the host merge (merge_host below) is enforced by
tests/test_lsm.py property tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from tigerbeetle_tpu import devicestats, tracer
from tigerbeetle_tpu.ops import u128

I32 = jnp.int32


def _bound(keys: jnp.ndarray, queries: jnp.ndarray, upper: bool) -> jnp.ndarray:  # tidy: static=upper — side selector, passed as a literal at every call site
    """Per-query count of `keys` elements < query (upper=False) or <= query
    (upper=True). keys (n, W) sorted ascending; queries (m, W)."""
    n = keys.shape[0]
    m = queries.shape[0]
    lo = jnp.zeros((m,), dtype=I32)
    hi = jnp.full((m,), n, dtype=I32)
    if n == 0:
        return lo
    steps = int(n).bit_length() + 1
    for _ in range(steps):
        mid = (lo + hi) >> 1
        kmid = keys[jnp.clip(mid, 0, n - 1)]
        pred = u128.le(kmid, queries) if upper else u128.lt(kmid, queries)
        active = lo < hi
        lo = jnp.where(active & pred, mid + 1, lo)
        hi = jnp.where(active & ~pred, mid, hi)
    return lo


@functools.partial(jax.jit, static_argnames=())
def merge_kernel(keys_a, vals_a, keys_b, vals_b):
    """Stable merge of two padded sorted runs (pads must sort past every
    legal key). vals may be (n,) or (n, K). Returns (keys (n+m, W), vals)."""
    n = keys_a.shape[0]
    m = keys_b.shape[0]
    pos_a = jnp.arange(n, dtype=I32) + _bound(keys_b, keys_a, upper=False)
    pos_b = jnp.arange(m, dtype=I32) + _bound(keys_a, keys_b, upper=True)
    out_keys = jnp.zeros((n + m, keys_a.shape[1]), dtype=keys_a.dtype)
    out_keys = out_keys.at[pos_a].set(keys_a).at[pos_b].set(keys_b)
    out_vals = jnp.zeros((n + m, *vals_a.shape[1:]), dtype=vals_a.dtype)
    out_vals = out_vals.at[pos_a].set(vals_a).at[pos_b].set(vals_b)
    return out_keys, out_vals


MERGE_TILE = 256
# The bucket-floor logic below (bucket_pow2) relies on every pow-2
# bucket ≥ the tile being a tile MULTIPLE — true only for pow-2 tiles.
assert MERGE_TILE & (MERGE_TILE - 1) == 0


def bucket_pow2(n: int) -> int:
    """Power-of-two bucket ≥ MERGE_TILE for an n-row run: the kernels
    compile once per bucket AND every bucket is tile-aligned, so the
    tiled merge-path kernel runs for every input size. The single source
    for _pad_pow2 and qindex.stage_query_batch — one retune point."""
    return 1 << max(
        (MERGE_TILE - 1).bit_length(), (max(n, 1) - 1).bit_length()
    )


@functools.partial(jax.jit, static_argnames=("tile",))
def merge_kernel_tiled(keys_a, vals_a, keys_b, vals_b, tile: int = MERGE_TILE):
    """Merge-path tiled stable merge — the TPU-shaped formulation.

    The global binary-search kernel above does O(log n) *random HBM
    gathers* per element, the pathological access pattern for TPU memory.
    This version does only sequential reads:

      1. Merge-path partition: for every output-tile boundary d, a small
         binary search over the DIAGONAL finds how many A elements the
         first d outputs consume (a dense (tiles, log) loop over two
         gathers of tile-count size — negligible).
      2. Per tile (vmapped): contiguous dynamic slices of A and B (tile
         rows each), then an all-pairs (tile x tile) lexicographic compare
         + row-sum gives each element's local rank — dense VPU work, no
         gathers — and one small in-tile scatter builds the output block.

    Stability matches merge_kernel: A-side elements precede B-side at
    equal keys. Requires n % tile == 0 and m % tile == 0 (callers pad)."""
    n = keys_a.shape[0]
    m = keys_b.shape[0]
    w = keys_a.shape[1]
    assert n % tile == 0 and m % tile == 0
    total = n + m
    n_tiles = total // tile

    # --- 1. diagonal splits -------------------------------------------
    # For boundary d: a_taken(d) = the unique ai in [max(0,d-m), min(d,n)]
    # with A[ai-1] <= B[d-ai] (stability: ties drain A first) and
    # B[d-ai-1] < A[ai]. Monotone in ai, so binary search.
    ds = jnp.arange(n_tiles + 1, dtype=I32) * tile

    def a_taken(d):
        lo = jnp.maximum(0, d - m)
        hi = jnp.minimum(d, n)

        def step(_, carry):
            lo, hi = carry
            mid = (lo + hi) >> 1
            # valid split at ai=mid requires A[mid] > B[d-mid-1] is False →
            # need MORE a... condition: take more A while A[mid] <= B[d-mid-1]
            a_mid = keys_a[jnp.clip(mid, 0, n - 1)]
            b_prev = keys_b[jnp.clip(d - mid - 1, 0, m - 1)]
            take_more = u128.le(a_mid, b_prev) & (mid < n) & (d - mid - 1 >= 0)
            lo = jnp.where(take_more, mid + 1, lo)
            hi = jnp.where(take_more, hi, mid)
            return lo, hi

        steps = int(max(n, 1)).bit_length() + 1
        lo, hi = jax.lax.fori_loop(0, steps, step, (lo, hi))
        return lo

    ai = jax.vmap(a_taken)(ds)  # (n_tiles+1,)
    bi = ds - ai

    # Pad A/B with one extra tile of all-ones sentinel rows so the
    # per-tile dynamic slices never clamp into real data.
    pad_k = jnp.full((tile, w), jnp.uint32(0xFFFFFFFF), dtype=keys_a.dtype)
    ka_p = jnp.concatenate([keys_a, pad_k])
    kb_p = jnp.concatenate([keys_b, pad_k])
    pad_v = jnp.zeros((tile, *vals_a.shape[1:]), dtype=vals_a.dtype)
    va_p = jnp.concatenate([vals_a, pad_v])
    vb_p = jnp.concatenate([vals_b, pad_v])

    def one_tile(t):
        a0 = ai[t]
        b0 = bi[t]
        a_cnt = ai[t + 1] - a0
        b_cnt = bi[t + 1] - b0
        a_k = jax.lax.dynamic_slice_in_dim(ka_p, a0, tile)
        b_k = jax.lax.dynamic_slice_in_dim(kb_p, b0, tile)
        a_v = jax.lax.dynamic_slice_in_dim(va_p, a0, tile)
        b_v = jax.lax.dynamic_slice_in_dim(vb_p, b0, tile)
        ar = jnp.arange(tile, dtype=I32)
        a_live = ar < a_cnt
        b_live = ar < b_cnt
        # All-pairs lexicographic compare as per-limb 2D ops (a (T,T,W)
        # broadcast materializes W-times the traffic; the column form
        # keeps every intermediate (T,T)).
        b_lt_a = jnp.zeros((tile, tile), dtype=bool)
        b_eq_a = jnp.ones((tile, tile), dtype=bool)
        for limb in reversed(range(w)):
            bc = b_k[None, :, limb]
            ac = a_k[:, None, limb]
            b_lt_a = b_lt_a | (b_eq_a & (bc < ac))
            b_eq_a = b_eq_a & (bc == ac)
        pos_a = ar + jnp.sum(b_lt_a & b_live[None, :], axis=1, dtype=I32)
        a_le_b = ~b_lt_a  # A[i] <= B[j]
        pos_b = ar + jnp.sum(a_le_b.T & a_live[None, :], axis=1, dtype=I32)
        out_k = jnp.full((tile, w), jnp.uint32(0xFFFFFFFF), dtype=keys_a.dtype)
        out_v = jnp.zeros((tile, *vals_a.shape[1:]), dtype=vals_a.dtype)
        sp_a = jnp.where(a_live, pos_a, tile)
        sp_b = jnp.where(b_live, pos_b, tile)
        out_k = out_k.at[sp_a].set(a_k, mode="drop").at[sp_b].set(b_k, mode="drop")
        out_v = out_v.at[sp_a].set(a_v, mode="drop").at[sp_b].set(b_v, mode="drop")
        return out_k, out_v

    out_k, out_v = jax.vmap(one_tile)(jnp.arange(n_tiles, dtype=I32))
    return out_k.reshape(total, w), out_v.reshape(total, *vals_a.shape[1:])


def _pad_pow2(keys: np.ndarray, vals: np.ndarray):
    """Pad to the next power-of-two bucket ≥ MERGE_TILE so the kernel
    compiles once per bucket size AND every bucket is tile-aligned: any
    pow-2 ≥ the tile is a tile multiple, so the tiled merge-path kernel
    always runs (runs under 256 rows used to miss the n % tile == 0 gate
    and silently fall back to the slow global-binary-search kernel). Pad
    rows set the pad-flag limb (last key column) to 1, which sorts
    strictly after every real key."""
    n = len(keys)
    n_pad = bucket_pow2(n)
    if n == n_pad:
        return keys, vals
    pk = np.zeros((n_pad, keys.shape[1]), dtype=keys.dtype)
    pk[:n] = keys
    pk[n:, -1] = 1
    pv = np.zeros((n_pad, *vals.shape[1:]), dtype=vals.dtype)
    pv[:n] = vals
    return pk, pv


def device_merge_pays() -> bool:
    """Whether routing sorted-run merges through the device kernels pays
    on this backend. XLA's CPU variadic sort/merge lowering is comparator-
    driven (not vectorized) and loses to the host C radix/merge by >10x at
    memtable sizes, so the device path is reserved for accelerator
    backends; TIGERBEETLE_TPU_DEVICE_MERGE=1/0 overrides either way."""
    import os

    ov = os.environ.get("TIGERBEETLE_TPU_DEVICE_MERGE")  # tidy: allow=env-read — backend routing policy, fixed per process; both routes are byte-identical
    if ov is not None:
        return ov not in ("0", "false", "")
    import jax

    return jax.default_backend() != "cpu"


def to_device_run(keys: np.ndarray, vals: np.ndarray):
    """Host KEY_DTYPE run → padded device-format (keys (N, 3), payload
    (N, 3)) u32 arrays: [lo0, lo1, pad] / [hi0, hi1, val]."""
    n = len(keys)
    k = np.zeros((n, 3), dtype=np.uint32)
    k[:, 0] = keys["lo"] & 0xFFFFFFFF
    k[:, 1] = keys["lo"] >> np.uint64(32)
    p = np.zeros((n, 3), dtype=np.uint32)
    p[:, 0] = keys["hi"] & 0xFFFFFFFF
    p[:, 1] = keys["hi"] >> np.uint64(32)
    p[:, 2] = vals
    return _pad_pow2(k, p)


def from_device_run(ok: np.ndarray, op: np.ndarray, n: int):
    """Materialized device-format arrays → (KEY_DTYPE keys, u32 vals),
    padding stripped (pads sort strictly last)."""
    from tigerbeetle_tpu.lsm.store import KEY_DTYPE

    ok = np.asarray(ok)[:n]
    op = np.asarray(op)[:n]
    out = np.empty(n, dtype=KEY_DTYPE)
    out["lo"] = ok[:, 0].astype(np.uint64) | (ok[:, 1].astype(np.uint64) << 32)
    out["hi"] = op[:, 0].astype(np.uint64) | (op[:, 1].astype(np.uint64) << 32)
    return out, op[:, 2].copy()


def merge_device(keys_a, vals_a, keys_b, vals_b):
    """Merge two lo-major-sorted structured KEY_DTYPE runs on device.

    Comparison key: (lo as 2 u32 limbs, pad flag). hi + value ride as a
    (n, 3) u32 payload. _pad_pow2 buckets are tile multiples, so the
    tiled merge-path kernel runs for every input size.
    """
    n, m = len(keys_a), len(keys_b)
    ka, pa = to_device_run(keys_a, vals_a)
    kb, pb = to_device_run(keys_b, vals_b)
    devicestats.note_call("merge_kernel_tiled", (ka, pa, kb, pb))
    with tracer.device_step("merge_kernel_tiled"):
        ok, op = merge_kernel_tiled(ka, pa, kb, pb)
        out = from_device_run(ok, op, n + m)
    tracer.device_bytes(
        h2d=ka.nbytes + pa.nbytes + kb.nbytes + pb.nbytes,
        d2h=ok.nbytes + op.nbytes,
    )
    return out


# Host-side stable k-way merge: lives in lsm/store.py (jax-free, next to
# sort_kv and the C shim it wraps) so numpy-backend flush/compaction can
# use it WITHOUT importing this module — importing ops.merge pulls in jax
# (~1s), which must never happen mid-load on a numpy-backend server.
# Re-exported here for the device-pipeline callers and the test suite.
from tigerbeetle_tpu.lsm.store import merge_host_kway  # noqa: E402,F401


def merge_host(keys_a, vals_a, keys_b, vals_b):
    """Numpy reference with identical semantics (byte-equality oracle and
    the CPU-backend fallback): stable lo-major merge of structured runs."""
    pa = np.asarray(keys_a)["lo"]
    pb = np.asarray(keys_b)["lo"]
    n, m = len(pa), len(pb)
    pos_a = np.arange(n) + np.searchsorted(pb, pa, side="left")
    pos_b = np.arange(m) + np.searchsorted(pa, pb, side="right")
    out_keys = np.zeros((n + m,), dtype=np.asarray(keys_a).dtype)
    out_vals = np.zeros((n + m,), dtype=np.asarray(vals_a).dtype)
    out_keys[pos_a] = keys_a
    out_keys[pos_b] = keys_b
    out_vals[pos_a] = vals_a
    out_vals[pos_b] = vals_b
    return out_keys, out_vals
