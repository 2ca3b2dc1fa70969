"""Sharded commit: the create_transfers kernel over a ('dp', 'shard') mesh.

Sharding design (TPU-first, not a translation of the reference's TCP mesh —
that remains the *replication* layer, host-side):

  - `shard` axis: account balance tables are sharded over slots
    (PartitionSpec('shard', None)). Each device owns a contiguous slot
    range and applies only the debit/credit sides that land in its range —
    double-entry posting decomposes cleanly because the debit side touches
    only the debit account's owner and the credit side only the credit
    account's owner.
  - `dp` axis: the event batch is sharded for validation (pure, per-event),
    then the per-event outcome bits + routing fields are all_gathered so
    every shard can apply its local sides. The all_gather payload is small
    (slots + amounts + masks, ~28 B/event) and rides ICI.
  - Account metadata needed by validation (ledger, flags) is replicated —
    it is 8 B/account vs 64 B/account for balances.
  - Overflow bail-out flags are psum'd across the whole mesh, so the host
    sees one scalar, same contract as the single-chip kernel.

Byte-exactness is inherited from the single-chip argument (ops/commit.py):
under fast-path preconditions the posting order is irrelevant (exact
wide-integer adds are associative/commutative), and every validation rung is
computed identically on whichever dp shard owns the event.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tigerbeetle_tpu.ops import commit as commit_ops
from tigerbeetle_tpu.ops.commit import LedgerState, TransferBatch, F_PENDING


def make_mesh(n_devices: int | None = None, dp: int | None = None) -> Mesh:
    """Build a ('dp', 'shard') mesh over the available devices.

    With no arguments, uses all devices with dp chosen as the largest power
    of two ≤ sqrt(n) so both axes are populated when possible.
    """
    devices = jax.devices()
    n = n_devices if n_devices is not None else len(devices)
    assert n <= len(devices), (n, len(devices))
    if dp is None:
        dp = 1
        while dp * 2 * dp * 2 <= n and n % (dp * 2) == 0:
            dp *= 2
    shard = n // dp
    assert dp * shard == n
    dev = np.array(devices[:n]).reshape(dp, shard)
    return Mesh(dev, axis_names=("dp", "shard"))


def state_specs() -> LedgerState:
    return LedgerState(
        debits_pending=P("shard", None),
        debits_posted=P("shard", None),
        credits_pending=P("shard", None),
        credits_posted=P("shard", None),
        ledger=P(None),
        flags=P(None),
    )


def batch_specs() -> TransferBatch:
    return TransferBatch(
        id=P("dp", None),
        dr_slot=P("dp"),
        cr_slot=P("dp"),
        amount=P("dp", None),
        pending_id=P("dp", None),
        timeout=P("dp"),
        ledger=P("dp"),
        code=P("dp"),
        flags=P("dp"),
        timestamp=P("dp", None),
    )


def _place(state: LedgerState, mesh: Mesh) -> LedgerState:
    return LedgerState(*[
        jax.device_put(arr, NamedSharding(mesh, spec))
        for arr, spec in zip(state, state_specs())
    ])


def init_sharded_state(accounts_max: int, mesh: Mesh) -> LedgerState:
    """Zero-initialized ledger state placed with the sharding above."""
    n_shard = mesh.shape["shard"]
    assert accounts_max % n_shard == 0, "accounts_max must divide the shard axis"
    return _place(commit_ops.init_state(accounts_max), mesh)


def make_sharded_commit(mesh: Mesh, accounts_max: int):
    """Returns jitted (state, batch, host_code) -> (state', codes, bail).

    Same contract as ops/commit.create_transfers_fast, but state is sharded
    over `shard` and the batch over `dp`.
    """
    n_shard = mesh.shape["shard"]
    assert accounts_max % n_shard == 0, "accounts_max must divide the shard axis"

    def step(state: LedgerState, b: TransferBatch, host_code: jnp.ndarray):
        # Derive the shard size from the actual local shape — a mismatched
        # accounts_max would otherwise silently drop postings.
        rows_per_shard = state.debits_pending.shape[0]
        assert rows_per_shard == accounts_max // n_shard, (
            "state shape does not match accounts_max"
        )
        # --- dp-sharded validation (state metadata is replicated) ---------
        code, unsupported = commit_ops.validate_simple(state, b)
        code = commit_ops.merge_codes(code, host_code)

        ok = (code == 0) & ~unsupported
        pend = (b.flags & F_PENDING) != 0

        # --- exchange routing info across dp (ICI all_gather) -------------
        def gather(x):
            return jax.lax.all_gather(x, "dp", tiled=True)

        g_dr = gather(b.dr_slot)
        g_cr = gather(b.cr_slot)
        g_amount = gather(b.amount)
        g_post = gather(ok & ~pend)
        g_pend = gather(ok & pend)

        # --- shard-local posting ------------------------------------------
        shard_ix = jax.lax.axis_index("shard").astype(jnp.int32)
        base = shard_ix * rows_per_shard
        dr_local = g_dr - base
        cr_local = g_cr - base
        dr_mine = (g_dr >= base) & (dr_local < rows_per_shard)
        cr_mine = (g_cr >= base) & (cr_local < rows_per_shard)

        new_state, overflow = commit_ops.apply_posting_streamed(
            state, dr_local, cr_local, g_amount,
            dr_pend=g_pend & dr_mine, dr_post=g_post & dr_mine,
            cr_pend=g_pend & cr_mine, cr_post=g_post & cr_mine,
        )
        bail_local = overflow | jnp.any(unsupported)
        # Axis names MUST be an ordered tuple, never a set — collective
        # reduction order is part of the determinism contract (the tidy
        # reduction pass rejects set-valued axis arguments: axis-order).
        bail = jax.lax.psum(bail_local.astype(jnp.uint32), ("dp", "shard")) > 0
        return new_state, code, bail

    sm = shard_map(
        step,
        mesh=mesh,
        in_specs=(state_specs(), batch_specs(), P("dp")),
        out_specs=(state_specs(), P("dp"), P()),
        # The balance outputs ARE replicated across 'dp' (every dp row applies
        # the same gathered updates), but the static VMA checker cannot infer
        # replication through the scatter — disable the check.
        check_vma=False,
    )
    return jax.jit(sm)


def make_sharded_commit_exact(mesh: Mesh, accounts_max: int, with_plan: bool = False):
    """Sharded variant of the exact fixed-point sweep kernel
    (ops/commit_exact.create_transfers_exact): balancing clamps, limit
    flags, linked chains, pending post/void over slot-sharded state.

    The sweep itself is batch-global dependency resolution — its
    parallelism is across the 16k posting lanes, which saturate one chip —
    so it runs REPLICATED on every device; the mesh contributes state
    capacity. Only two touch-points meet the sharded balance tables:

      - base gather: each shard contributes its owned rows' pre-batch
        balances, combined with one psum over 'shard' (one collective of
        4x(2n,4) u32 before the sweep loop);
      - posting: each shard applies the debit/credit sides whose slots it
        owns (masked exact scatter-add/sub), with the overflow flag psum'd
        so bail is identical everywhere.

    Byte-exactness vs the single-chip kernel: the replicated sweep math is
    bitwise-identical (same inputs after the base psum reconstructs the
    same balances), and posting decomposes by slot ownership exactly as in
    make_sharded_commit.
    """
    from tigerbeetle_tpu.ops import commit_exact
    from tigerbeetle_tpu.ops import u128
    from tigerbeetle_tpu.ops.commit_exact import BAL_FIELDS, Observed

    n_shard = mesh.shape["shard"]
    assert accounts_max % n_shard == 0

    def step(state, b, host_code, pending, chain_id, *plan_arg):
        plan = plan_arg[0] if plan_arg else None
        rows = state.debits_pending.shape[0]
        assert rows == accounts_max // n_shard
        shard_ix = jax.lax.axis_index("shard").astype(jnp.int32)
        base_off = shard_ix * rows

        def balance_read(st, rec_slot):
            # Match the single-chip gather bit-for-bit: invalid slots clip
            # to row 0 (commit_exact base gather), whose owning shard
            # contributes its balances — so even failed rows' dr_after/
            # cr_after outputs stay byte-identical to single-chip.
            glob = jnp.clip(rec_slot, 0, accounts_max - 1)
            local = glob - base_off
            mine = (local >= 0) & (local < rows)
            lclip = jnp.clip(local, 0, rows - 1)
            stacked = jnp.stack(
                [
                    jnp.where(mine[:, None], getattr(st, f)[lclip], jnp.uint32(0))
                    for f in BAL_FIELDS
                ]
            )
            gathered = jax.lax.psum(stacked, "shard")  # ONE collective
            return [gathered[i] for i in range(len(BAL_FIELDS))]

        def balance_apply(
            st, eff_dr, eff_cr, amounts, p_amount, add_pend, add_post, sub_pend
        ):
            dr_local = eff_dr - base_off
            cr_local = eff_cr - base_off
            dr_mine = (eff_dr >= 0) & (dr_local >= 0) & (dr_local < rows)
            cr_mine = (eff_cr >= 0) & (cr_local >= 0) & (cr_local < rows)
            dr_ix = jnp.where(dr_mine, dr_local, jnp.int32(-1))
            cr_ix = jnp.where(cr_mine, cr_local, jnp.int32(-1))

            new_dp, o1 = u128.scatter_add(
                st.debits_pending, dr_ix, amounts, add_pend & dr_mine
            )
            new_cp, o2 = u128.scatter_add(
                st.credits_pending, cr_ix, amounts, add_pend & cr_mine
            )
            new_dpo, o3 = u128.scatter_add(
                st.debits_posted, dr_ix, amounts, add_post & dr_mine
            )
            new_cpo, o4 = u128.scatter_add(
                st.credits_posted, cr_ix, amounts, add_post & cr_mine
            )
            new_dp, u1 = u128.scatter_sub(new_dp, dr_ix, p_amount, sub_pend & dr_mine)
            new_cp, u2 = u128.scatter_sub(new_cp, cr_ix, p_amount, sub_pend & cr_mine)
            _, o5 = u128.add(new_dp, new_dpo)
            _, o6 = u128.add(new_cp, new_cpo)
            over_local = (
                jnp.any(o1) | jnp.any(o2) | jnp.any(o3) | jnp.any(o4)
                | jnp.any(o5) | jnp.any(o6) | jnp.any(u1) | jnp.any(u2)
            )
            over = jax.lax.psum(over_local.astype(jnp.uint32), "shard") > 0
            return st._replace(
                debits_pending=new_dp, debits_posted=new_dpo,
                credits_pending=new_cp, credits_posted=new_cpo,
            ), over

        return commit_exact.create_transfers_exact_impl(
            state, b, host_code, pending, chain_id, plan,
            balance_read=balance_read, balance_apply=balance_apply,
            # dp-shard the per-sweep MXU cumsums (bit-identical: u32 adds
            # are associative; cross-slice offsets + result ride
            # all_gathers over ICI). With dp=1 this is a no-op.
            cumsum_axis="dp" if mesh.shape["dp"] > 1 else None,
        )

    obs_spec = Observed(*([P()] * 4))
    pending_spec = commit_exact.PendingInfo(*([P()] * 8))
    in_specs = [state_specs(), TransferBatch(*([P()] * 10)), P(), pending_spec, P()]
    if with_plan:
        # Host-precomputed sort plan, replicated (the sweep is batch-global).
        in_specs.append(commit_exact.SortPlan(*([P()] * 8)))
    sm = shard_map(
        step,
        mesh=mesh,
        # Batch inputs replicated: the sweep is batch-global (see above).
        in_specs=tuple(in_specs),
        out_specs=(state_specs(), P(), P(), obs_spec, obs_spec, P(), P()),
        check_vma=False,
    )
    return jax.jit(sm)


def register_accounts_sharded(
    mesh: Mesh,
    state: LedgerState,
    slots: np.ndarray,
    ledger: np.ndarray,
    flags: np.ndarray,
    mask: np.ndarray,
) -> LedgerState:
    """Install new accounts' replicated metadata (ledger/flags).

    Balances stay zero; only the replicated arrays change, so a plain jitted
    update with preserved shardings suffices.
    """
    return _place(commit_ops.register_accounts(state, slots, ledger, flags, mask), mesh)
