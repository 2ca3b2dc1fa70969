"""Fixture: seeded retrace hazards at jit-entry call sites. Findings
asserted EXACTLY by tests/test_jaxlint.py — edit in lockstep."""

import functools

import jax
import numpy as np


@jax.jit
def create_transfers_fast(x):
    return x * 2


@functools.partial(jax.jit, static_argnames=("max_sweeps",))
def create_transfers_exact(x, max_sweeps=64):
    return x + max_sweeps


def feed(events):
    n = len(events)
    a = create_transfers_fast(events[:n])  # retrace-shape: runtime-bounded slice
    b = create_transfers_fast(np.asarray(events))  # retrace-shape: runtime-sized ctor
    c = create_transfers_exact(a, max_sweeps=n * 2)  # retrace-static-arg: per-batch value
    kw = {"x": b}
    d = create_transfers_fast(**kw)  # retrace-kwargs: dict-ordered args
    return a, b, c, d


def feed_named(events):
    tmp = np.zeros(len(events), dtype=np.uint32)  # retrace-shape fires HERE
    return create_transfers_fast(tmp)  # ... when the named temporary reaches the entry
