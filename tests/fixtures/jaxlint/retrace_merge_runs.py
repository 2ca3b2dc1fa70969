"""Fixture: the device run merge's compile gate.
Findings asserted EXACTLY by tests/test_jaxlint.py — edit in lockstep.

merge_kernel_tiled is a registered jit entry (tidy/manifest.JIT_ENTRIES):
feeding it runtime-sized runs is a retrace per run length, which on the
store thread means a fresh XLA compile inside a beat. The sanctioned
shape gate is _pad_pow2 (JAXLINT_PAD_HELPERS): every run is padded to a
pow-2 bucket of whole tiles, so the kernel compiles once per bucket pair.
"""

import jax
import numpy as np


@jax.jit
def merge_kernel_tiled(keys_a, vals_a, keys_b, vals_b):
    return keys_a, vals_a


def _pad_pow2(keys, vals):
    n_pad = 1 << max(8, (len(keys) - 1).bit_length())
    pk = np.zeros((n_pad, 3), dtype=np.uint32)
    pv = np.zeros((n_pad, 3), dtype=np.uint32)
    return pk, pv


def merge_ungated(keys_a, vals_a, keys_b, vals_b):
    # retrace-shape fires HERE: run-sized arrays reach the entry.
    ka = np.zeros((len(keys_a), 3), dtype=np.uint32)
    kb = np.asarray(keys_b)
    return merge_kernel_tiled(ka, ka, kb, kb)


def merge_gated(keys_a, vals_a, keys_b, vals_b):
    ka, pa = _pad_pow2(keys_a, vals_a)  # pad helper: compile-gated
    kb, pb = _pad_pow2(keys_b, vals_b)
    return merge_kernel_tiled(ka, pa, kb, pb)
