/* Host-side batch primitives for the replica's commit path.
 *
 * The reference keeps its hot loops in native Zig (state_machine.zig's
 * per-transfer execute, lsm binary_search.zig, groove prefetch); this
 * build's host runtime equivalents were numpy, whose per-element costs
 * (searchsorted ~90 ns/el, argsort ~70 ns/el on this class of host)
 * dominated the 8190-event batch commit. These C loops recover the
 * native constant factors:
 *
 *   - u128 -> u32 open-addressing hash map (account id -> device slot;
 *     the role of groove.zig's id tree for the RAM-resident account
 *     index) with batch insert/lookup/contains and in-batch duplicate
 *     detection.
 *   - u64 radix argsort (memtable insert-time key ordering).
 *   - exact u128 two-phase balance posting via unsigned __int128
 *     (state_machine.zig:1330-1340 balance updates + overflow ladder
 *     rungs, batch-aggregated).
 *
 * Build: cc -O3 -shared -fPIC hostops.c -o libhostops.so  (no ISA
 * extensions required; loaded via ctypes by tigerbeetle_tpu/native).
 */

#include <stdint.h>
#include <stdlib.h>
#include <string.h>

#define NOT_FOUND 0xFFFFFFFFu

/* ---------------------------------------------------------------- hash */

static inline uint64_t mix64(uint64_t x) {
    /* splitmix64 finalizer — good avalanche for open addressing. */
    x += 0x9E3779B97F4A7C15ull;
    x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ull;
    x = (x ^ (x >> 27)) * 0x94D049BB133111EBull;
    return x ^ (x >> 31);
}

static inline uint64_t hash128(uint64_t lo, uint64_t hi) {
    return mix64(lo ^ mix64(hi));
}

typedef struct {
    uint64_t lo, hi;
    uint32_t val;
    uint32_t used;
} map_slot;

typedef struct {
    map_slot *slots;
    uint64_t mask; /* capacity - 1 (capacity is a power of two) */
    uint64_t count;
} u128map;

static void map_grow(u128map *m, uint64_t new_cap);

void *hostops_map_new(uint64_t cap_hint) {
    uint64_t cap = 64;
    while (cap < cap_hint * 2) cap <<= 1;
    u128map *m = (u128map *)malloc(sizeof(u128map));
    if (!m) return 0;
    m->slots = (map_slot *)calloc(cap, sizeof(map_slot));
    if (!m->slots) { free(m); return 0; }
    m->mask = cap - 1;
    m->count = 0;
    return m;
}

void hostops_map_free(void *h) {
    u128map *m = (u128map *)h;
    if (!m) return;
    free(m->slots);
    free(m);
}

uint64_t hostops_map_len(void *h) { return ((u128map *)h)->count; }

static inline void map_put(u128map *m, uint64_t lo, uint64_t hi, uint32_t val) {
    uint64_t i = hash128(lo, hi) & m->mask;
    for (;;) {
        map_slot *s = &m->slots[i];
        if (!s->used) {
            s->lo = lo; s->hi = hi; s->val = val; s->used = 1;
            m->count++;
            return;
        }
        if (s->lo == lo && s->hi == hi) { s->val = val; return; }
        i = (i + 1) & m->mask;
    }
}

static void map_grow(u128map *m, uint64_t new_cap) {
    map_slot *old = m->slots;
    uint64_t old_cap = m->mask + 1;
    m->slots = (map_slot *)calloc(new_cap, sizeof(map_slot));
    m->mask = new_cap - 1;
    m->count = 0;
    for (uint64_t i = 0; i < old_cap; i++)
        if (old[i].used) map_put(m, old[i].lo, old[i].hi, old[i].val);
    free(old);
}

void hostops_map_insert_batch(
    void *h, int64_t n,
    const uint64_t *lo, const uint64_t *hi, const uint32_t *vals
) {
    u128map *m = (u128map *)h;
    /* keep load factor under 0.7 */
    while ((m->count + (uint64_t)n) * 10 > (m->mask + 1) * 7)
        map_grow(m, (m->mask + 1) * 2);
    for (int64_t i = 0; i < n; i++) map_put(m, lo[i], hi[i], vals[i]);
}

void hostops_map_lookup_batch(
    void *h, int64_t n,
    const uint64_t *lo, const uint64_t *hi, uint32_t *out
) {
    const u128map *m = (const u128map *)h;
    for (int64_t q = 0; q < n; q++) {
        uint64_t i = hash128(lo[q], hi[q]) & m->mask;
        uint32_t r = NOT_FOUND;
        for (;;) {
            const map_slot *s = &m->slots[i];
            if (!s->used) break;
            if (s->lo == lo[q] && s->hi == hi[q]) { r = s->val; break; }
            i = (i + 1) & m->mask;
        }
        out[q] = r;
    }
}

int hostops_map_contains_any(
    void *h, int64_t n, const uint64_t *lo, const uint64_t *hi
) {
    const u128map *m = (const u128map *)h;
    for (int64_t q = 0; q < n; q++) {
        uint64_t i = hash128(lo[q], hi[q]) & m->mask;
        for (;;) {
            const map_slot *s = &m->slots[i];
            if (!s->used) break;
            if (s->lo == lo[q] && s->hi == hi[q]) return 1;
            i = (i + 1) & m->mask;
        }
    }
    return 0;
}

/* In-batch duplicate detection: returns 1 if any (lo, hi) key appears
 * twice within the batch. Scratch table allocated per call. */
int hostops_batch_has_dup(int64_t n, const uint64_t *lo, const uint64_t *hi) {
    uint64_t cap = 64;
    while (cap < (uint64_t)n * 2) cap <<= 1;
    uint64_t mask = cap - 1;
    map_slot *slots = (map_slot *)calloc(cap, sizeof(map_slot));
    if (!slots) return -1;
    int dup = 0;
    for (int64_t q = 0; q < n && !dup; q++) {
        uint64_t i = hash128(lo[q], hi[q]) & mask;
        for (;;) {
            map_slot *s = &slots[i];
            if (!s->used) { s->lo = lo[q]; s->hi = hi[q]; s->used = 1; break; }
            if (s->lo == lo[q] && s->hi == hi[q]) { dup = 1; break; }
            i = (i + 1) & mask;
        }
    }
    free(slots);
    return dup;
}

/* ------------------------------------------------------------- bloom */

static inline void bloom_hash2(uint64_t lo, uint64_t hi, uint64_t *h1, uint64_t *h2) {
    uint64_t x = lo ^ (hi * 0x94D049BB133111EBull);
    x ^= x >> 30; x *= 0xBF58476D1CE4E5B9ull;
    x ^= x >> 27; x *= 0x94D049BB133111EBull;
    *h1 = x ^ (x >> 31);
    *h2 = (*h1 >> 32) | (*h1 << 32);
}

void hostops_bloom_add(
    uint64_t *words, uint64_t bit_mask, int64_t n,
    const uint64_t *lo, const uint64_t *hi
) {
    /* Two-phase per block: the hash phase streams the keys and
     * prefetches the (randomly addressed) filter words the set phase
     * will touch — on filters past L2 size the word fetch is the whole
     * cost, and the prefetch pipeline hides most of it. */
    uint64_t b1s[256], b2s[256];
    int64_t i = 0;
    while (i < n) {
        int64_t c = n - i < 256 ? n - i : 256;
        for (int64_t t = 0; t < c; t++) {
            uint64_t h1, h2;
            bloom_hash2(lo[i + t], hi[i + t], &h1, &h2);
            b1s[t] = h1 & bit_mask;
            b2s[t] = h2 & bit_mask;
            __builtin_prefetch(&words[b1s[t] >> 6], 1);
            __builtin_prefetch(&words[b2s[t] >> 6], 1);
        }
        for (int64_t t = 0; t < c; t++) {
            words[b1s[t] >> 6] |= 1ull << (b1s[t] & 63);
            words[b2s[t] >> 6] |= 1ull << (b2s[t] & 63);
        }
        i += c;
    }
}

void hostops_bloom_maybe(
    const uint64_t *words, uint64_t bit_mask, int64_t n,
    const uint64_t *lo, const uint64_t *hi, uint8_t *out
) {
    for (int64_t i = 0; i < n; i++) {
        uint64_t h1, h2;
        bloom_hash2(lo[i], hi[i], &h1, &h2);
        uint64_t b1 = h1 & bit_mask, b2 = h2 & bit_mask;
        out[i] = ((words[b1 >> 6] >> (b1 & 63)) & 1)
               & ((words[b2 >> 6] >> (b2 & 63)) & 1);
    }
}

/* ------------------------------------------------------- radix argsort */

/* Stable LSB radix argsort of u64 keys (8 passes x 8 bits). `out` gets
 * the permutation (u32 indices). ~5x numpy's comparison argsort.
 * Returns 0 on success, -1 on allocation failure (out untouched). */
int hostops_argsort_u64(int64_t n, const uint64_t *keys, uint32_t *out) {
    uint32_t *idx = out;
    uint32_t *tmp = (uint32_t *)malloc((size_t)n * sizeof(uint32_t));
    if (!tmp) return -1;
    for (int64_t i = 0; i < n; i++) idx[i] = (uint32_t)i;
    uint64_t counts[256];
    for (int pass = 0; pass < 8; pass++) {
        int shift = pass * 8;
        /* skip passes whose byte is constant (common: high bytes zero) */
        uint8_t first = (uint8_t)(keys[idx[0]] >> shift);
        int constant = 1;
        memset(counts, 0, sizeof(counts));
        for (int64_t i = 0; i < n; i++) {
            uint8_t b = (uint8_t)(keys[idx[i]] >> shift);
            counts[b]++;
            constant &= (b == first);
        }
        if (constant) continue;
        uint64_t pos = 0;
        uint64_t starts[256];
        for (int b = 0; b < 256; b++) { starts[b] = pos; pos += counts[b]; }
        for (int64_t i = 0; i < n; i++) {
            uint8_t b = (uint8_t)(keys[idx[i]] >> shift);
            tmp[starts[b]++] = idx[i];
        }
        memcpy(idx, tmp, (size_t)n * sizeof(uint32_t));
    }
    free(tmp);
    return 0;
}

/* Fused stable lo-major sort of (16-byte key, u32 value) pairs: radix
 * argsort of the key lo-halves + ONE gather of keys and values in C —
 * the LSM flush path's hot pair (sort + numpy fancy-index gather) in a
 * single call. keys_in/keys_out are KEY_DTYPE rows (hi u64 FIRST, then
 * lo u64 — lsm/store.py layout). */
typedef struct {
    uint64_t lo;   /* the sort key */
    uint32_t row;  /* original position: resolves keys_out/vals_out */
    uint32_t _pad;
} sortkv_ent;

int hostops_sort_kv(
    int64_t n, const uint64_t *keys_in, const uint32_t *vals_in,
    uint64_t *keys_out, uint32_t *vals_out
) {
    /* Pair-moving LSD radix: each pass streams 16-byte (lo, row)
     * elements sequentially instead of double-indirecting through an
     * index permutation (keys[idx[i]] per pass is a cache miss per
     * element; this is ~4x faster at memtable sizes). Stable by lo. */
    sortkv_ent *cur = (sortkv_ent *)malloc((size_t)n * sizeof(sortkv_ent));
    sortkv_ent *alt = (sortkv_ent *)malloc((size_t)n * sizeof(sortkv_ent));
    if (!cur || !alt) { free(cur); free(alt); return -1; }
    for (int64_t i = 0; i < n; i++) {
        cur[i].lo = keys_in[2 * i + 1]; /* KEY_DTYPE: hi first, lo second */
        cur[i].row = (uint32_t)i;
    }
    uint64_t counts[256];
    for (int pass = 0; pass < 8; pass++) {
        int shift = pass * 8;
        uint8_t first = (uint8_t)(cur[0].lo >> shift);
        int constant = 1;
        memset(counts, 0, sizeof(counts));
        for (int64_t i = 0; i < n; i++) {
            uint8_t b = (uint8_t)(cur[i].lo >> shift);
            counts[b]++;
            constant &= (b == first);
        }
        if (constant) continue;
        uint64_t pos = 0;
        uint64_t starts[256];
        for (int b = 0; b < 256; b++) { starts[b] = pos; pos += counts[b]; }
        for (int64_t i = 0; i < n; i++) {
            uint8_t b = (uint8_t)(cur[i].lo >> shift);
            alt[starts[b]++] = cur[i];
        }
        sortkv_ent *t = cur; cur = alt; alt = t;
    }
    for (int64_t i = 0; i < n; i++) {
        int64_t j = (int64_t)cur[i].row;
        keys_out[2 * i] = keys_in[2 * j];
        keys_out[2 * i + 1] = keys_in[2 * j + 1];
        vals_out[i] = vals_in[j];
    }
    free(cur);
    free(alt);
    return 0;
}

/* Stable k-way merge of lo-major sorted (16-byte key, u32 value) runs:
 * the flush/compaction fold for runs that are ALREADY sorted (insert-time
 * sorted memtable batches, compaction chunk streams). Equal-lo keys drain
 * the EARLIEST run first and keep within-run order — exactly the order a
 * stable sort of the runs' concatenation produces, so output bytes are
 * identical to hostops_sort_kv on the concatenated input (byte-equality
 * is property-tested from Python).
 *
 * Selection gallops: after picking the earliest minimal run r, its whole
 * prefix strictly below (or tying, when r precedes the tying run) the
 * best other head is block-copied — pre-sorted and dup-heavy inputs then
 * cost ~memcpy instead of a per-row heap. runs_keys rows are KEY_DTYPE
 * (hi u64 first, lo u64 second). */
/* Selection runs over a binary min-heap of run heads keyed (lo, run) —
 * lexicographic, so ties surface the EARLIEST run, preserving the
 * stability contract above. The runner-up (the gallop bound) is the
 * smaller of the root's two children: in a binary min-heap the
 * second-smallest element is always a child of the root. At k = 64 this
 * replaces two O(k) head scans per gallop segment with O(log k)
 * sift-downs — the wide single-pass fold's selection cost.
 *
 * The _bloom variant fuses Bloom-filter population into the output
 * copy: seg_ends[nseg] are cumulative output-row boundaries (the
 * compaction writer's table spans, emitted by the caller in the same
 * pass that sizes the merge — table-boundary splits no longer need a
 * re-scan), seg_words[s] points at table s's filter words (NULL skips
 * that span, e.g. the trailing partial table that stays lazily built),
 * seg_masks[s] is its bit mask. Bits are set from the just-copied
 * output rows while they are still cache-hot, so the separate
 * per-table streaming bloom pass disappears. */
typedef struct { uint64_t lo; int64_t run; } merge_head;

static inline int head_lt(merge_head a, merge_head b) {
    return a.lo < b.lo || (a.lo == b.lo && a.run < b.run);
}

/* tidy: bound=runs_keys:k,runs_vals:k,ns:k,seg_ends:nseg,seg_words:nseg,seg_masks:nseg — the run and segment descriptor arrays are caller-sized to exactly k and nseg; keys_out/vals_out are sized to the total row count (caller contract, lsm/store.py) */
int hostops_merge_kv_bloom(
    int64_t k, const uint64_t **runs_keys, const uint32_t **runs_vals,
    const int64_t *ns, uint64_t *keys_out, uint32_t *vals_out,
    int64_t nseg, const int64_t *seg_ends,
    uint64_t *const *seg_words, const uint64_t *seg_masks
) {
    if (k <= 0) return 0;
    if (k > 64) return -1;
    int64_t idx[64];
    merge_head heap[64];
    int64_t hn = 0;
    for (int64_t r = 0; r < k; r++) {
        idx[r] = 0;
        if (ns[r] <= 0) continue;
        merge_head h = { runs_keys[r][1], r };
        int64_t i = hn++; /* tidy: range=i:0..63,hn:1..64 — one push per run, and k <= 64 was checked above */
        while (i > 0) { /* sift up */
            int64_t p = (i - 1) >> 1;
            if (!head_lt(h, heap[p])) break;
            heap[i] = heap[p];
            i = p;
        }
        heap[i] = h;
    }
    int64_t out = 0;
    while (hn > 0) { /* tidy: range=hn:0..64 — pops never outnumber the k <= 64 pushes */
        int64_t r = heap[0].run; /* tidy: range=r:0..<k — heap entries carry run indices in [0, k) */
        int64_t j = idx[r];
        int64_t end = ns[r];
        if (hn == 1) {
            j = end; /* last live run: drain it */
        } else {
            merge_head m2 = heap[1];
            if (hn > 2 && head_lt(heap[2], m2)) m2 = heap[2];
            /* Take r's prefix while its key precedes every other head:
             * strictly smaller lo, or a tie with a LATER run (stability:
             * the earlier run's equal keys all come first). */
            if (r < m2.run) {
                while (j < end && runs_keys[r][2 * j + 1] <= m2.lo) j++;
            } else {
                while (j < end && runs_keys[r][2 * j + 1] < m2.lo) j++;
            }
        }
        int64_t cnt = j - idx[r];
        memcpy(keys_out + 2 * out, runs_keys[r] + 2 * idx[r],
               (size_t)cnt * 16);
        memcpy(vals_out + out, runs_vals[r] + idx[r],
               (size_t)cnt * sizeof(uint32_t));
        idx[r] = j;
        out += cnt;
        if (j >= end) {
            heap[0] = heap[--hn];
        } else {
            heap[0].lo = runs_keys[r][2 * j + 1];
            heap[0].run = r;
        }
        merge_head h = heap[0];
        int64_t i = 0;
        for (;;) { /* sift down */
            int64_t c = 2 * i + 1;
            if (c >= hn) break;
            if (c + 1 < hn && head_lt(heap[c + 1], heap[c])) c++;
            if (!head_lt(heap[c], h)) break;
            heap[i] = heap[c];
            i = c;
        }
        heap[i] = h;
    }
    /* Segmented Bloom pass over the finished output, still inside this
     * call while the chunk is cache-hot. Kept OUT of the heap loop: the
     * filter words are a large random-access array, and interleaving
     * their cache misses with the selection loop stalled it; here the
     * hash phase streams sequentially and prefetches each word a block
     * ahead of the set phase. Bits are identical to the inline form. */
    for (int64_t s = 0, p = 0; s < nseg && p < out; s++) {
        int64_t lim = seg_ends[s] < out ? seg_ends[s] : out;
        uint64_t *words = seg_words[s];
        if (words && lim > p) {
            uint64_t bm = seg_masks[s];
            uint64_t b1s[256], b2s[256];
            int64_t i = p;
            while (i < lim) {
                int64_t n = lim - i < 256 ? lim - i : 256;
                for (int64_t t = 0; t < n; t++) {
                    uint64_t h1, h2;
                    /* keys_out rows: hi first, lo second */
                    bloom_hash2(keys_out[2 * (i + t) + 1],
                                keys_out[2 * (i + t)], &h1, &h2);
                    b1s[t] = h1 & bm;
                    b2s[t] = h2 & bm;
                    __builtin_prefetch(&words[b1s[t] >> 6], 1);
                    __builtin_prefetch(&words[b2s[t] >> 6], 1);
                }
                for (int64_t t = 0; t < n; t++) {
                    words[b1s[t] >> 6] |= 1ull << (b1s[t] & 63);
                    words[b2s[t] >> 6] |= 1ull << (b2s[t] & 63);
                }
                i += n;
            }
        }
        if (lim > p) p = lim;
    }
    return 0;
}

int hostops_merge_kv(
    int64_t k, const uint64_t **runs_keys, const uint32_t **runs_vals,
    const int64_t *ns, uint64_t *keys_out, uint32_t *vals_out
) {
    return hostops_merge_kv_bloom(k, runs_keys, runs_vals, ns,
                                  keys_out, vals_out, 0, 0, 0, 0);
}

/* ------------------------------------------- sorted-set row intersects */

/* First index >= key in a[lo..n), found by galloping (doubling) from lo
 * then binary search inside the located block — O(log gap) instead of
 * O(log n), which is what makes probing a long run with a short sorted
 * candidate list cheap (scan_merge.zig's probe(), re-shaped for arrays). */
/* tidy: range=lo:0..0xffffffff,n:0..0xffffffff; bound=a:n — callers pass segment row counts (< 4G rows per table) */
static inline int64_t gallop_lower_u32(
    const uint32_t *a, int64_t lo, int64_t n, uint32_t key
) {
    int64_t step = 1, hi = lo;
    while (hi < n && a[hi] < key) {
        lo = hi + 1;
        hi += step;
        step <<= 1;
    }
    if (hi > n) hi = n;
    /* invariant: a[lo-1] < key (or lo at start), a[hi] >= key (or hi==n) */
    while (lo < hi) {
        int64_t mid = lo + ((hi - lo) >> 1);
        if (a[mid] < key) lo = mid + 1; else hi = mid; /* tidy: allow=c-index-bound — lo <= mid < hi <= n by the gallop cap above; the lo < hi relation is outside the interval domain */
    }
    return lo;
}

/* Intersection of two ascending u32 arrays (dups allowed in either; the
 * output is the unique common values, ascending). Gallops on whichever
 * side is ahead, so cost is O(min(na, nb) * log(gap)) — the short side
 * drives. Returns the output count (out must hold min(na, nb)). */
/* tidy: range=na:0..0xffffffff,nb:0..0xffffffff; bound=a:na,b:nb — out is sized min(na, nb) by the caller (lsm/scan.py), a relational contract the write below documents */
int64_t hostops_intersect_u32(
    int64_t na, const uint32_t *a, int64_t nb, const uint32_t *b,
    uint32_t *out
) {
    int64_t i = 0, j = 0, k = 0;
    while (i < na && j < nb) {
        uint32_t va = a[i], vb = b[j];
        if (va == vb) {
            out[k++] = va;
            while (i < na && a[i] == va) i++;
            while (j < nb && b[j] == vb) j++;
        } else if (va < vb) {
            i = gallop_lower_u32(a, i + 1, na, vb); /* tidy: range=i:0..0xffffffff — gallop returns an index in [lo, n] */
        } else {
            j = gallop_lower_u32(b, j + 1, nb, va); /* tidy: range=j:0..0xffffffff — gallop returns an index in [lo, n] */
        }
    }
    return k;
}

/* Membership probe: for each candidate cand[i] present in the ascending
 * run seg[0..ns), set hit[i] = 1 (hits accumulate across calls — the
 * caller ORs one probe per fence-selected segment, then compresses).
 * Returns the number of NEWLY set marks so the caller can stop probing
 * further segments once every candidate is accounted for. */
/* tidy: range=nc:0..0xffffffff,ns:0..0xffffffff; bound=cand:nc,hit:nc,seg:ns — candidate/hit arrays share length nc; seg is one table segment */
int64_t hostops_gallop_mark_u32(
    int64_t nc, const uint32_t *cand, int64_t ns, const uint32_t *seg,
    uint8_t *hit
) {
    int64_t j = 0, fresh = 0;
    for (int64_t i = 0; i < nc; i++) {
        if (hit[i]) continue;
        uint32_t c = cand[i];
        j = gallop_lower_u32(seg, j, ns, c); /* tidy: range=j:0..0xffffffff — gallop returns an index in [lo, n] */
        if (j >= ns) break;
        if (seg[j] == c) {
            hit[i] = 1;
            fresh++;
        }
    }
    return fresh;
}

/* ------------------------------------------------- fast-path staging */

/* One pass over raw 128-byte wire Transfer records doing everything the
 * Python dispatcher staged in five separate numpy passes: in-batch
 * duplicate-id detection (hash set), bloom membership pre-filter, account
 * id -> slot map lookups, the full fast-path validation ladder
 * (host_kernel.validate + the dispatcher's host rungs, merged at exact
 * precedence via nonzero-minimum), and exact-kernel routing flags.
 *
 * Record layout (types.TRANSFER_DTYPE, byte offsets):
 *   0 id_lo  8 id_hi  16 dr_lo  24 dr_hi  32 cr_lo  40 cr_hi
 *   48 amount_lo  56 amount_hi  64 pending_id_lo  72 pending_id_hi
 *   104 user_data_32(u32) 108 timeout(u32) 112 ledger(u32)
 *   116 code(u16) 118 flags(u16) 120 timestamp(u64)
 *
 * Result codes are the wire-contract values of
 * results.CreateTransferResult (cross-checked at shim load time by
 * native/__init__.py).
 */
enum {
    R_TIMESTAMP_MUST_BE_ZERO = 3,
    R_RESERVED_FLAG = 4,
    R_ID_MUST_NOT_BE_ZERO = 5,
    R_ID_MUST_NOT_BE_INT_MAX = 6,
    R_DR_ID_ZERO = 8,
    R_DR_ID_MAX = 9,
    R_CR_ID_ZERO = 10,
    R_CR_ID_MAX = 11,
    R_ACCOUNTS_MUST_BE_DIFFERENT = 12,
    R_PENDING_ID_MUST_BE_ZERO = 13,
    R_TIMEOUT_RESERVED = 17,
    R_AMOUNT_MUST_NOT_BE_ZERO = 18,
    R_LEDGER_MUST_NOT_BE_ZERO = 19,
    R_CODE_MUST_NOT_BE_ZERO = 20,
    R_DEBIT_ACCOUNT_NOT_FOUND = 21,
    R_CREDIT_ACCOUNT_NOT_FOUND = 22,
    R_SAME_LEDGER = 23,
    R_TRANSFER_SAME_LEDGER = 24,
    R_OVERFLOWS_TIMEOUT = 53,
};

#define F_LINKED   (1u << 0)
#define F_PENDING  (1u << 1)
#define F_POST     (1u << 2)
#define F_VOID     (1u << 3)
#define F_BAL_DR   (1u << 4)
#define F_BAL_CR   (1u << 5)
#define F_EXACT    (F_LINKED | F_POST | F_VOID | F_BAL_DR | F_BAL_CR)
#define AF_LIMIT_OR_HISTORY ((1u << 1) | (1u << 2) | (1u << 3))

#define LADDER(c, cond, val) do { if ((c) == 0 && (cond)) (c) = (val); } while (0)

/* Reusable duplicate-detection scratch (epoch-tagged: no per-call clear). */
typedef struct { uint64_t lo, hi; uint32_t epoch; } dup_slot;
/* _Thread_local: ctypes releases the GIL during calls, so two state
 * machines driven from different threads must not share scratch. */
static _Thread_local dup_slot *g_dup = 0;
static _Thread_local uint64_t g_dup_cap = 0;
static _Thread_local uint32_t g_dup_epoch = 0;

/* Returns a bitmask: bit0 has_dup, bit1 exact_needed, bit2 any bloom
 * maybe, bit3 any post/void, bit4 any linked. Negative on alloc failure. */
int hostops_ct_stage(
    const uint8_t *events, int64_t n, int64_t stride,
    uint64_t ts_base,           /* timestamp of event 0 */
    void *account_map,          /* u128map id -> slot (may be NULL) */
    const uint32_t *acc_ledger, /* slot-indexed */
    const uint32_t *acc_flags,
    const uint64_t *bloom_words, uint64_t bloom_mask, /* words NULL = skip */
    uint32_t *code,      /* merged fast-path ladder (fast batches only) */
    uint32_t *host_code, /* dispatcher host rungs alone (exact-path input) */
    int64_t *dr_slot, int64_t *cr_slot,
    uint64_t *amt_lo, uint64_t *amt_hi,
    uint8_t *pend_out, uint8_t *maybe_out
) {
    uint64_t cap = 64;
    while (cap < (uint64_t)n * 2) cap <<= 1;
    if (cap > g_dup_cap || g_dup_epoch == 0xFFFFFFFFu) {
        free(g_dup);
        g_dup = (dup_slot *)calloc(cap, sizeof(dup_slot));
        if (!g_dup) { g_dup_cap = 0; return -1; }
        g_dup_cap = cap;
        g_dup_epoch = 0;
    }
    uint64_t dmask = g_dup_cap - 1;
    uint32_t epoch = ++g_dup_epoch;
    const u128map *m = (const u128map *)account_map;
    int out_flags = 0;

    for (int64_t i = 0; i < n; i++) {
        const uint8_t *r = events + i * stride;
        uint64_t id_lo, id_hi, dr_lo, dr_hi, cr_lo, cr_hi, a_lo, a_hi;
        uint64_t p_lo, p_hi, ts_field;
        uint32_t timeout, ledger;
        uint16_t tcode, flags;
        memcpy(&id_lo, r + 0, 8);  memcpy(&id_hi, r + 8, 8);
        memcpy(&dr_lo, r + 16, 8); memcpy(&dr_hi, r + 24, 8);
        memcpy(&cr_lo, r + 32, 8); memcpy(&cr_hi, r + 40, 8);
        memcpy(&a_lo, r + 48, 8);  memcpy(&a_hi, r + 56, 8);
        memcpy(&p_lo, r + 64, 8);  memcpy(&p_hi, r + 72, 8);
        memcpy(&timeout, r + 108, 4); memcpy(&ledger, r + 112, 4);
        memcpy(&tcode, r + 116, 2);   memcpy(&flags, r + 118, 2);
        memcpy(&ts_field, r + 120, 8);
        amt_lo[i] = a_lo; amt_hi[i] = a_hi;
        int pend = (flags & F_PENDING) != 0;
        pend_out[i] = (uint8_t)pend;
        if (flags & F_EXACT) out_flags |= 2;
        if (flags & (F_POST | F_VOID)) out_flags |= 8;
        if (flags & F_LINKED) out_flags |= 16;

        /* duplicate-id hash set */
        {
            uint64_t j = hash128(id_lo, id_hi) & dmask;
            for (;;) {
                dup_slot *s = &g_dup[j];
                if (s->epoch != epoch) {
                    s->lo = id_lo; s->hi = id_hi; s->epoch = epoch;
                    break;
                }
                if (s->lo == id_lo && s->hi == id_hi) { out_flags |= 1; break; }
                j = (j + 1) & dmask;
            }
        }
        /* bloom membership pre-filter */
        if (bloom_words) {
            uint64_t h1, h2;
            bloom_hash2(id_lo, id_hi, &h1, &h2);
            uint64_t b1 = h1 & bloom_mask, b2 = h2 & bloom_mask;
            uint8_t mb = (uint8_t)(((bloom_words[b1 >> 6] >> (b1 & 63)) & 1)
                                 & ((bloom_words[b2 >> 6] >> (b2 & 63)) & 1));
            maybe_out[i] = mb;
            if (mb) out_flags |= 4;
        } else {
            maybe_out[i] = 0;
        }
        /* account slot lookups */
        int64_t ds = -1, cs = -1;
        if (m) {
            uint64_t j = hash128(dr_lo, dr_hi) & m->mask;
            for (;;) {
                const map_slot *s = &m->slots[j];
                if (!s->used) break;
                if (s->lo == dr_lo && s->hi == dr_hi) { ds = s->val; break; }
                j = (j + 1) & m->mask;
            }
            j = hash128(cr_lo, cr_hi) & m->mask;
            for (;;) {
                const map_slot *s = &m->slots[j];
                if (!s->used) break;
                if (s->lo == cr_lo && s->hi == cr_hi) { cs = s->val; break; }
                j = (j + 1) & m->mask;
            }
        }
        dr_slot[i] = ds; cr_slot[i] = cs;
        if (ds >= 0 && (acc_flags[ds] & AF_LIMIT_OR_HISTORY)) out_flags |= 2;
        if (cs >= 0 && (acc_flags[cs] & AF_LIMIT_OR_HISTORY)) out_flags |= 2;

        /* host-rung ladder (dispatcher order, post/void events excluded
         * from the account-id rungs — they branch to their own ladder) */
        int is_pv = (flags & (F_POST | F_VOID)) != 0;
        uint32_t hc = 0;
        LADDER(hc, ts_field != 0, R_TIMESTAMP_MUST_BE_ZERO);
        if (!is_pv) {
            LADDER(hc, dr_lo == 0 && dr_hi == 0, R_DR_ID_ZERO);
            LADDER(hc, dr_lo == ~0ull && dr_hi == ~0ull, R_DR_ID_MAX);
            LADDER(hc, cr_lo == 0 && cr_hi == 0, R_CR_ID_ZERO);
            LADDER(hc, cr_lo == ~0ull && cr_hi == ~0ull, R_CR_ID_MAX);
            LADDER(hc, dr_lo == cr_lo && dr_hi == cr_hi,
                   R_ACCOUNTS_MUST_BE_DIFFERENT);
        }
        /* kernel-rung ladder (host_kernel.validate order) */
        uint32_t kc = 0;
        LADDER(kc, (flags & 0xFFC0u) != 0, R_RESERVED_FLAG);
        LADDER(kc, id_lo == 0 && id_hi == 0, R_ID_MUST_NOT_BE_ZERO);
        LADDER(kc, id_lo == ~0ull && id_hi == ~0ull, R_ID_MUST_NOT_BE_INT_MAX);
        LADDER(kc, p_lo != 0 || p_hi != 0, R_PENDING_ID_MUST_BE_ZERO);
        LADDER(kc, !pend && timeout != 0, R_TIMEOUT_RESERVED);
        LADDER(kc, a_lo == 0 && a_hi == 0, R_AMOUNT_MUST_NOT_BE_ZERO);
        LADDER(kc, ledger == 0, R_LEDGER_MUST_NOT_BE_ZERO);
        LADDER(kc, tcode == 0, R_CODE_MUST_NOT_BE_ZERO);
        LADDER(kc, ds < 0, R_DEBIT_ACCOUNT_NOT_FOUND);
        LADDER(kc, cs < 0, R_CREDIT_ACCOUNT_NOT_FOUND);
        if (kc == 0 && ds >= 0 && cs >= 0) {
            uint32_t dl = acc_ledger[ds], cl = acc_ledger[cs];
            LADDER(kc, dl != cl, R_SAME_LEDGER);
            LADDER(kc, ledger != dl, R_TRANSFER_SAME_LEDGER);
        }
        {
            uint64_t ts = ts_base + (uint64_t)i;
            uint64_t tns = (uint64_t)timeout * 1000000000ull;
            LADDER(kc, tns > ~0ull - ts, R_OVERFLOWS_TIMEOUT);
        }
        /* nonzero-minimum merge (results are precedence-ordered) */
        uint32_t c;
        if (hc == 0) c = kc;
        else if (kc == 0) c = hc;
        else c = hc < kc ? hc : kc;
        code[i] = c;
        host_code[i] = hc;
    }
    return out_flags;
}

/* Build lo-major stable-sorted (key, value) arrays for memtable insertion
 * straight from raw wire records — replaces pack_keys + concat + radix
 * argsort + gather numpy passes. Column 2 (off2 >= 0) appends a second
 * key per record AFTER all first keys (the Python concat order), with the
 * same value sequence. Values are val_base + (i % n). out_keys is
 * KEY_DTYPE layout: (hi u64, lo u64) pairs. Returns 0, or -1 on alloc
 * failure. */
int hostops_build_sorted_kv(
    const uint8_t *recs, int64_t n, int64_t stride,
    int64_t off_lo1, int64_t off_hi1,
    int64_t off_lo2, int64_t off_hi2,
    uint32_t val_base,
    uint8_t *out_keys, uint32_t *out_vals
) {
    int64_t m = off_lo2 >= 0 ? 2 * n : n;
    uint64_t *lo = (uint64_t *)malloc((size_t)m * 8);
    uint64_t *hi = (uint64_t *)malloc((size_t)m * 8);
    uint32_t *idx = (uint32_t *)malloc((size_t)m * 4);
    if (!lo || !hi || !idx) { free(lo); free(hi); free(idx); return -1; }
    for (int64_t i = 0; i < n; i++) {
        const uint8_t *r = recs + i * stride;
        memcpy(&lo[i], r + off_lo1, 8);
        memcpy(&hi[i], r + off_hi1, 8);
        if (off_lo2 >= 0) {
            memcpy(&lo[n + i], r + off_lo2, 8);
            memcpy(&hi[n + i], r + off_hi2, 8);
        }
    }
    if (hostops_argsort_u64(m, lo, idx) != 0) {
        free(lo); free(hi); free(idx); return -1;
    }
    for (int64_t i = 0; i < m; i++) {
        uint32_t j = idx[i];
        memcpy(out_keys + i * 16, &hi[j], 8);
        memcpy(out_keys + i * 16 + 8, &lo[j], 8);
        out_vals[i] = val_base + (uint32_t)(j < n ? j : j - n);
    }
    free(lo); free(hi); free(idx);
    return 0;
}

/* Unsorted sibling of hostops_build_sorted_kv: extract (key, value)
 * arrays in record order (column-1 block then column-2 block, the Python
 * concat order) with no sort — for memtables whose flush re-sorts anyway. */
int hostops_extract_kv(
    const uint8_t *recs, int64_t n, int64_t stride,
    int64_t off_lo1, int64_t off_hi1,
    int64_t off_lo2, int64_t off_hi2,
    uint32_t val_base,
    uint8_t *out_keys, uint32_t *out_vals
) {
    for (int64_t i = 0; i < n; i++) {
        const uint8_t *r = recs + i * stride;
        memcpy(out_keys + i * 16, r + off_hi1, 8);
        memcpy(out_keys + i * 16 + 8, r + off_lo1, 8);
        out_vals[i] = val_base + (uint32_t)i;
        if (off_lo2 >= 0) {
            memcpy(out_keys + (n + i) * 16, r + off_hi2, 8);
            memcpy(out_keys + (n + i) * 16 + 8, r + off_lo2, 8);
            out_vals[n + i] = val_base + (uint32_t)i;
        }
    }
    return 0;
}

/* ------------------------------------------------------- u128 posting */

typedef unsigned __int128 u128;

typedef struct {
    u128 d_pend, d_post, c_pend, c_post;
} post_delta;

/* Reusable posting scratch, split into a compact probe table (slot id +
 * epoch + dense index — 16 bytes per probe line vs the old 80-byte
 * struct) and a dense delta array indexed by discovery order. Epoch tags
 * skip per-call clearing; phases 2-3 walk only the dense entries. The
 * old per-call multi-MB calloc + full-capacity sweep dominated this
 * function's cost. */
typedef struct { int64_t slot; uint32_t epoch; uint32_t dense; } post_probe;
static _Thread_local post_probe *g_post_probe = 0;
static _Thread_local post_delta *g_post_delta = 0;
static _Thread_local int64_t *g_post_dense_slot = 0;
static _Thread_local uint64_t g_post_cap = 0;
static _Thread_local uint32_t g_post_epoch = 0;

/* Exact two-phase balance posting over four (rows, 4)-u32-limb tables
 * (little-endian limbs: value = l0 + l1<<32 + l2<<64 + l3<<96).
 *
 * Phase 1 accumulates per-slot u128 deltas (open addressing on slot id)
 * with overflow tracking; phase 2 checks every touched account's new
 * debits/credits (pending, posted, and their sum — the reference's
 * overflows_debits/credits rungs, state_machine.zig:1308-1324) and only
 * then writes. Returns 1 on any overflow (tables untouched), else 0.
 */
int hostops_post_u128(
    uint32_t *dp, uint32_t *dpo, uint32_t *cp, uint32_t *cpo,
    int64_t n,
    const int64_t *dr, const int64_t *cr,
    const uint64_t *amt_lo, const uint64_t *amt_hi,
    const uint8_t *pend_mask, const uint8_t *post_mask
) {
    uint64_t cap = 64;
    while (cap < (uint64_t)n * 4) cap <<= 1; /* 2n distinct max, load <= 0.5 */
    if (cap > g_post_cap || g_post_epoch == 0xFFFFFFFFu) {
        free(g_post_probe); free(g_post_delta); free(g_post_dense_slot);
        g_post_probe = (post_probe *)calloc(cap, sizeof(post_probe));
        g_post_delta = (post_delta *)malloc((cap / 2) * sizeof(post_delta));
        g_post_dense_slot = (int64_t *)malloc((cap / 2) * sizeof(int64_t));
        if (!g_post_probe || !g_post_delta || !g_post_dense_slot) {
            free(g_post_probe); free(g_post_delta); free(g_post_dense_slot);
            g_post_probe = 0; g_post_delta = 0; g_post_dense_slot = 0;
            g_post_cap = 0;
            return -1;
        }
        g_post_cap = cap;
        g_post_epoch = 0;
    }
    uint64_t mask = g_post_cap - 1;
    post_probe *probe = g_post_probe;
    post_delta *delta = g_post_delta;
    uint32_t epoch = ++g_post_epoch;
    uint32_t n_dense = 0;

    int overflow = 0;

    #define ACC_FIND(slot_id, out_ptr) do {                                \
        uint64_t _i = mix64((uint64_t)(slot_id)) & mask;                   \
        for (;;) {                                                         \
            if (probe[_i].epoch != epoch) {                                \
                probe[_i].epoch = epoch; probe[_i].slot = (slot_id);       \
                probe[_i].dense = n_dense;                                 \
                g_post_dense_slot[n_dense] = (slot_id);                    \
                post_delta *_d = &delta[n_dense++];                        \
                _d->d_pend = _d->d_post = _d->c_pend = _d->c_post = 0;     \
                (out_ptr) = _d; break;                                     \
            }                                                              \
            if (probe[_i].slot == (slot_id)) {                             \
                (out_ptr) = &delta[probe[_i].dense]; break;                \
            }                                                              \
            _i = (_i + 1) & mask;                                          \
        }                                                                  \
    } while (0)

    for (int64_t i = 0; i < n; i++) {
        int p = pend_mask[i], q = post_mask[i];
        if (!p && !q) continue;
        u128 amt = ((u128)amt_hi[i] << 64) | amt_lo[i];
        post_delta *sd, *sc;
        ACC_FIND(dr[i], sd);
        ACC_FIND(cr[i], sc);
        if (p) {
            u128 v = sd->d_pend + amt; if (v < amt) overflow = 1; sd->d_pend = v;
            v = sc->c_pend + amt; if (v < amt) overflow = 1; sc->c_pend = v;
        } else {
            u128 v = sd->d_post + amt; if (v < amt) overflow = 1; sd->d_post = v;
            v = sc->c_post + amt; if (v < amt) overflow = 1; sc->c_post = v;
        }
    }
    #undef ACC_FIND

    #define LOAD128(tbl, s) ( \
        (u128)(tbl)[(s) * 4 + 0]        | ((u128)(tbl)[(s) * 4 + 1] << 32) | \
        ((u128)(tbl)[(s) * 4 + 2] << 64) | ((u128)(tbl)[(s) * 4 + 3] << 96) )
    #define STORE128(tbl, s, v) do {                     \
        (tbl)[(s) * 4 + 0] = (uint32_t)(v);              \
        (tbl)[(s) * 4 + 1] = (uint32_t)((v) >> 32);      \
        (tbl)[(s) * 4 + 2] = (uint32_t)((v) >> 64);      \
        (tbl)[(s) * 4 + 3] = (uint32_t)((v) >> 96);      \
    } while (0)

    /* Phase 2: validate all, then write all. */
    for (uint32_t t = 0; t < n_dense && !overflow; t++) {
        post_delta *a = &delta[t];
        int64_t s = g_post_dense_slot[t];
        u128 ndp = LOAD128(dp, s) + a->d_pend;
        if (ndp < a->d_pend) overflow = 1;
        u128 ndpo = LOAD128(dpo, s) + a->d_post;
        if (ndpo < a->d_post) overflow = 1;
        u128 ncp = LOAD128(cp, s) + a->c_pend;
        if (ncp < a->c_pend) overflow = 1;
        u128 ncpo = LOAD128(cpo, s) + a->c_post;
        if (ncpo < a->c_post) overflow = 1;
        if (ndp + ndpo < ndp) overflow = 1;   /* overflows_debits  */
        if (ncp + ncpo < ncp) overflow = 1;   /* overflows_credits */
    }
    if (!overflow) {
        for (uint32_t t = 0; t < n_dense; t++) {
            post_delta *a = &delta[t];
            int64_t s = g_post_dense_slot[t];
            u128 v;
            v = LOAD128(dp, s) + a->d_pend;  STORE128(dp, s, v);
            v = LOAD128(dpo, s) + a->d_post; STORE128(dpo, s, v);
            v = LOAD128(cp, s) + a->c_pend;  STORE128(cp, s, v);
            v = LOAD128(cpo, s) + a->c_post; STORE128(cpo, s, v);
        }
    }
    #undef LOAD128
    #undef STORE128
    return overflow;
}

/* The exact kernel's line walk (ops/commit_exact.py build_line_plan).
 * reads[r]: record r (event r % n, debit side r < n) reads its account
 * (a limit flag or a balancing flag on that side); seg[r]: its slot's
 * segment in the batch's sort plan, -1 for none, < nseg. An event that
 * reads a side is walked; its side is on a line where some record reads
 * that segment. Its level is one more than the highest level already on
 * either of its lines, so the events of one level share no line and
 * every line meets its events in event order. Out: order[0..walked) the
 * walked events by (level, event); at[e] the event's place there, -1 not
 * walked; pred[2 * place + side] the place * 2 + side of the walked
 * posting before it on its line, `none` where it is the first, -1 where
 * the side is on no line; sizes[l - 1] the events of level l. Returns the
 * deepest level, -1 where scratch could not be had. */
int32_t hostops_line_plan(int64_t n, const uint8_t *reads, const int32_t *seg,
                          int64_t nseg, int32_t none, int32_t *order, int32_t *at,
                          int32_t *pred, int32_t *sizes) {
    uint8_t *read_seg = (uint8_t *)calloc((size_t)nseg + 1, 1);
    int32_t *last_level = (int32_t *)calloc((size_t)nseg + 1, sizeof(int32_t));
    int32_t *last_rec = (int32_t *)malloc(((size_t)nseg + 1) * sizeof(int32_t));
    int32_t *level = (int32_t *)calloc((size_t)n + 1, sizeof(int32_t));
    int32_t *first = (int32_t *)calloc((size_t)n + 2, sizeof(int32_t));
    int32_t *pred_rec = (int32_t *)malloc(((size_t)2 * n + 1) * sizeof(int32_t));
    int32_t depth = -1;
    if (!read_seg || !last_level || !last_rec || !level || !first || !pred_rec) goto done;
    depth = 0;
    for (int64_t r = 0; r < 2 * n; r++)
        if (reads[r] && seg[r] >= 0) read_seg[seg[r]] = 1;
    for (int64_t g = 0; g < nseg; g++) last_rec[g] = -1;
    for (int64_t e = 0; e < n; e++) {
        at[e] = -1;
        if (!reads[e] && !reads[e + n]) continue;
        int32_t l = 0;
        for (int s = 0; s < 2; s++) {
            int64_t r = e + s * n;
            int32_t g = seg[r];
            if (g >= 0 && read_seg[g]) {
                pred_rec[r] = last_rec[g];  /* both sides read before either moves */
                if (last_level[g] > l) l = last_level[g];
            } else {
                pred_rec[r] = -2;
            }
        }
        l += 1;
        for (int s = 0; s < 2; s++) {
            int64_t r = e + s * n;
            if (pred_rec[r] != -2) {
                last_level[seg[r]] = l;
                last_rec[seg[r]] = (int32_t)r;
            }
        }
        level[e] = l;
        first[l + 1] += 1;
        if (l > depth) depth = l;
    }
    for (int32_t l = 1; l <= depth; l++) {
        sizes[l - 1] = first[l + 1];
        first[l + 1] += first[l];
    }
    for (int64_t e = 0; e < n; e++)
        if (level[e]) { at[e] = first[level[e]]; order[first[level[e]]++] = (int32_t)e; }
    for (int64_t e = 0; e < n; e++) {
        if (!level[e]) continue;
        for (int s = 0; s < 2; s++) {
            int32_t p = pred_rec[e + s * n];
            pred[2 * (int64_t)at[e] + s] =
                p == -2 ? -1 : p == -1 ? none : 2 * at[p % n] + (p >= n);
        }
    }
done:
    free(read_seg); free(last_level); free(last_rec); free(level); free(first); free(pred_rec);
    return depth;
}
