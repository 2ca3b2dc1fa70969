"""Fixture: seeded host-sync violations inside a jitted kernel, plus an
un-fenced sync in a host dispatcher. Every finding here is asserted
EXACTLY by tests/test_jaxlint.py — edit in lockstep."""

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def bad_kernel(x):
    if x[0] > 0:  # traced-branch: data-dependent Python control flow
        x = x + 1
    total = float(x.sum())  # host-sync: float() on a traced value
    host = np.asarray(x)  # host-sync: np.asarray materializes the tracer
    first = x[0].item()  # host-sync: .item() syncs
    return x, total, host, first


def bad_dispatch(events):
    out = create_transfers_fast(events)
    out.block_until_ready()  # unfenced-sync: outside the sanctioned seam
    return out


def bad_materialize(events):
    codes = create_transfers_fast(events)
    return bool(codes)  # host-sync: device handle materialized off-seam
