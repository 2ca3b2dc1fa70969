"""Fixture: the clean inverse — the same shapes as the *_bad modules
written the disciplined way. Every pass must return ZERO findings."""

import jax
import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32


@jax.jit
def create_transfers_fast(x):  # tidy: range=x:0..0xFFFF — u16 payloads by contract
    bumped = jnp.where(x[0] > 0, x + 1, x)  # branchless select, no sync
    total = bumped.sum()  # stays on device
    return bumped, total


def pad_batch(events):
    n = len(events)
    n_pad = 1 << max(4, (max(n, 1) - 1).bit_length())
    out = np.zeros(n_pad, dtype=np.asarray(events).dtype)
    out[:n] = events
    return out


def feed(events):
    padded = pad_batch(events)  # bucket-padded: compiles once per bucket
    return create_transfers_fast(padded)


def finish(handle):  # tidy: range=handle:0..0xFFFF — same u16 contract as the kernel
    codes = create_transfers_fast(handle)
    # tidy: allow=host-sync — fixture seam: this IS the sanctioned finish point
    return np.asarray(codes)


# tidy: range=a:0..0xFFFF,b:0..0xFFFF — u16 half-limbs by contract
def widen_add(a, b):
    return a + b  # ≤ 0x1FFFE: proven in-width


@jax.jit
def int_scatter(table, idx, vals):
    return table.at[idx].add(vals)  # integer scatter-add: associative, clean
