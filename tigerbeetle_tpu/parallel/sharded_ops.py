"""ShardedOps: the StateMachine's ops facade over a device mesh.

Drop-in replacement for the `ops.commit` module interface the host
StateMachine drives (models/state_machine.py `self._ops`): ledger state
lives slot-sharded across a ('dp','shard') mesh (parallel/sharding.py),
the fast and exact kernels run their shard_map variants, and the
gather/scatter helpers ride XLA's GSPMD auto-partitioning. The dispatcher
is unchanged — multi-chip is a constructor argument
(`StateMachine(..., mesh=...)`), not a different code path.
"""

from __future__ import annotations

import numpy as np

from tigerbeetle_tpu.ops import commit as commit_ops
from tigerbeetle_tpu.results import CreateTransferResult as TR
from tigerbeetle_tpu.parallel import sharding


class ShardedOps:
    TransferBatch = commit_ops.TransferBatch

    def __init__(self, mesh, accounts_max: int) -> None:
        self.mesh = mesh
        self.accounts_max = accounts_max
        self._fast = sharding.make_sharded_commit(mesh, accounts_max)
        self._exact = sharding.make_sharded_commit_exact(mesh, accounts_max)
        self._exact_plan = sharding.make_sharded_commit_exact(
            mesh, accounts_max, with_plan=True
        )
        self._dp = mesh.shape["dp"]

    def init_state(self, accounts_max: int):
        assert accounts_max == self.accounts_max
        return sharding.init_sharded_state(accounts_max, self.mesh)

    def track_compiles(self, registry) -> None:
        """Register the mesh-built jit entries with the tidy compile
        registry (tidy/jaxlint.py CompileRegistry) so per-entry
        cache-miss attribution covers the multi-chip path too — the
        module-level defaults only see the single-chip entries."""
        registry.track("sharded.create_transfers_fast", self._fast)
        registry.track("sharded.create_transfers_exact", self._exact)
        registry.track("sharded.create_transfers_exact_plan", self._exact_plan)

    def create_transfers_fast(self, state, b, host_code):
        # The fast step shards the batch over 'dp'; pad to a multiple.
        n = b.flags.shape[0]
        pad = (-n) % self._dp
        if pad:
            def p1(a, fill=0):
                out = np.full((n + pad, *a.shape[1:]), fill, dtype=a.dtype)
                out[:n] = a
                return out

            # Slot fields pad with the -1 sentinel (same convention as
            # state_machine._device_batch) so padded rows can never alias
            # account slot 0 under any slot-validity mask.
            b = commit_ops.TransferBatch(*[
                p1(np.asarray(x), fill=-1 if name in ("dr_slot", "cr_slot") else 0)
                for name, x in zip(commit_ops.TransferBatch._fields, b)
            ])
            # Same never-applied pad code as state_machine._device_batch.
            hc = p1(np.asarray(host_code), fill=int(TR.ID_MUST_NOT_BE_ZERO))
        else:
            hc = host_code
        new_state, codes, bail = self._fast(state, b, hc)
        return new_state, codes[:n] if pad else codes, bail

    def create_transfers_exact(
        self, state, b, host_code, pending, chain_id, plan=None,
        has_pv=True, has_chains=True, lines=None,
    ):
        # has_pv/has_chains and the line walk are single-chip
        # specializations; the sharded kernels are built once with the
        # general (True) trace and settle long lines by the sweeps alone.
        if plan is None:
            return self._exact(state, b, host_code, pending, chain_id)
        return self._exact_plan(state, b, host_code, pending, chain_id, plan)

    def register_accounts(self, state, slots, ledger, flags, mask):
        return sharding.register_accounts_sharded(
            self.mesh, state, slots, ledger, flags, mask
        )

    # Gather/scatter helpers: the single-chip jitted fns compose with
    # sharded inputs via GSPMD (cross-shard gathers lower to collectives).
    def read_balances(self, state, slots):
        return commit_ops.read_balances(state, slots)

    def write_balances(self, state, slots, dp, dpo, cp, cpo):
        new = commit_ops.write_balances(state, slots, dp, dpo, cp, cpo)
        # Re-pin the canonical shardings (a scatter's output sharding can
        # decay to replicated, which would silently densify every table).
        return sharding._place(new, self.mesh)
