"""Fixture: the commit kernel's compile gate.
Findings asserted EXACTLY by tests/test_jaxlint.py — edit in lockstep.

create_transfers_fast is a registered jit entry (tidy/manifest.JIT_ENTRIES):
feeding it batch-sized arrays is a retrace per batch length, which on the
commit thread means a fresh XLA compile inside a request. The sanctioned
shape gate is a pad helper (JAXLINT_PAD_HELPERS, here _pad_slots): every
batch is padded to a pow-2 bucket, so the kernel compiles once per bucket.
"""

import jax
import numpy as np


@jax.jit
def create_transfers_fast(events, slots, amounts, codes):
    return events, codes


def _pad_slots(events, slots):
    n_pad = 1 << max(8, (len(events) - 1).bit_length())
    pe = np.zeros((n_pad, 3), dtype=np.uint32)
    ps = np.zeros((n_pad, 3), dtype=np.uint32)
    return pe, ps


def commit_ungated(events, slots, amounts, codes):
    # retrace-shape fires HERE: batch-sized arrays reach the entry.
    ev = np.zeros((len(events), 3), dtype=np.uint32)
    am = np.asarray(amounts)
    return create_transfers_fast(ev, ev, am, am)


def commit_gated(events, slots, amounts, codes):
    ev, sl = _pad_slots(events, slots)  # pad helper: compile-gated
    am, co = _pad_slots(amounts, codes)
    return create_transfers_fast(ev, sl, am, co)
