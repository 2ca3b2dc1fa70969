"""Profiler for the exact sweep kernel: sweep-count requirements and
fixed-vs-per-sweep cost split on configs 3/4. Uses the exact same staging,
SortPlan, and static trace flags as bench.py (bench.exact_setup), so the
numbers reflect the production path. Not part of the test suite."""

import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

import bench
from tigerbeetle_tpu.ops import commit_exact

K = 16


def _window(args, s, has_pv, has_chains):
    state, b, host_code, pending, chain_id, plan = args

    @jax.jit
    def window(state):
        def body(st, _):
            st2, *_, bail, _sweeps = commit_exact.create_transfers_exact_impl(
                st, b, host_code, pending, chain_id, plan,
                max_sweeps=s, has_pv=has_pv, has_chains=has_chains,
            )
            return st2, bail

        st, bails = jax.lax.scan(body, state, None, length=K)
        return st, bails.astype(jnp.int32).sum()

    return window


def profile(mix):
    state, b, host_code, pending, chain_id, plan, has_pv, has_chains = (
        bench.exact_setup(mix, scan_len=K)
    )
    args = (state, b, host_code, pending, chain_id, plan)

    # Sweep counts needed: scan K batches, count bails at max_sweeps=s.
    smin = None
    for s in range(1, 17):
        st, nbail = _window(args, s, has_pv, has_chains)(state)
        np.asarray(st.debits_posted)
        print(f"{mix}: max_sweeps={s} bails={int(nbail)}/{K}")
        if int(nbail) == 0:
            smin = s
            break
    if smin is None:
        print(f"{mix}: no convergence within 16 sweeps — timing split skipped")
        return

    # Timing at capped sweep budgets: max_sweeps=0 is the fixed cost
    # (prep + seed + apply); the slope above it is the per-sweep cost.
    for s in (0, 1, 2, smin, MAXS):
        window = _window(args, s, has_pv, has_chains)
        st, _ = window(state)  # warmup/compile
        np.asarray(st.debits_posted)
        t0 = time.perf_counter()
        reps = 4
        for _ in range(reps):
            st, _ = window(st)
        np.asarray(st.debits_posted)
        dt = (time.perf_counter() - t0) / (reps * K) * 1e3
        print(f"{mix}: max_sweeps={s} batch_ms={dt:.3f}")


MAXS = commit_exact.MAX_SWEEPS

if __name__ == "__main__":
    from tigerbeetle_tpu import compilecache

    compilecache.configure()
    for mix in sys.argv[1:] or ["config3", "config4"]:
        profile(mix)
