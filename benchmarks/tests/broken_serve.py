"""`benchmarks/serve.py` with a fault planted in the program underneath,
chosen by BENCH_FAULT. Used by test_faults.py (on the CPU: `correct` must
come out false for every fault a cell can have) and by control_on_chip.py
(on the chip, at the cells' own size: the control and the faults set the
upper reading of each compared number).

  lossy_scatter    THE CONTROL: balances are posted by a scatter that
                   does not accumulate, so of two events of one batch on
                   one account only one counts: the shortcut a faster
                   posting would be tempted by (`unique_indices`, `.set`).
                   Breaks "every balance exact". The control of the cells
                   on the fast kernel only: the exact kernel notices the
                   pending amounts it lost (a post or void underflows),
                   bails, and the host's serial path answers every batch
                   in its place, rightly and at a crawl.
  backups_lossy_scatter  THE CONTROL of a cluster: `lossy_scatter` on every
                   replica that is a BACKUP when it first traces its
                   commit kernel; the primary is sound. Every code and every
                   answer the primary gives is right; the balances the
                   backups hold are not, and only a read-back from a quorum
                   WITHOUT the primary can tell. Breaks "every replica
                   commits every batch to the byte".
  chains_unlinked  THE CONTROL where batches carry linked chains: every
                   event is its own chain, so the links of a chain that
                   must roll back are applied, the shortcut that spares
                   the kernel its chain bookkeeping. Breaks "a linked
                   chain commits whole or not at all".
  limit_flags_dropped  THE CONTROL where accounts carry a limit flag: the
                   device's flag table is registered empty, so the kernel's
                   balance check never refuses (the host's rows keep their
                   flags: the batches still take the exact route and every
                   account reads back with its flag). Breaks "a transfer
                   that would take a wallet past its credits is refused".
  state_unchanged  the commit kernels return the state they were given
  half_left_out    the kernels' new state is kept for the even account
                   slots only: half of the work left out
  code_altered     event 0 of every batch is answered with a failure code
                   though the kernel applied it: an answer altered where
                   it is produced
  store_altered    lookup_transfers returns the first transfer of every
                   answer with its amount off by one
  ops_relabelled   the replies of every second session (in the order they
                   first sent transfers) carry their op raised by 2^20: every op is
                   still unique and every session's own order holds, but
                   the order the replies state puts all of one half's
                   requests before the other half's, also those answered
                   before the others were sent. Breaks "committed in the
                   one order, and in real-time order"; the ledger itself
                   is sound, so where no answer depends on the order only
                   `realtime_order_violations` can tell
  stale_reads      THE CONTROL where the traffic holds reads: a
                   `lookup_accounts` that stands directly behind a
                   `create_transfers` batch in the commit order answers
                   from the balances as they stood BEFORE that batch, the
                   shortcut a read served beside the commit window would
                   be tempted by (it does not wait for the batch in flight
                   ahead of it). A read behind another read sees
                   everything, so the read-back after the drain does too.
                   Breaks "a lookup_accounts returns every balance as the
                   transfers committed before it left it". Every code, the
                   final balances and the stored rows are right: only
                   `read_mismatches` can tell
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, os.path.dirname(HERE))


def plant(fault: str) -> None:
    import jax
    import jax.numpy as jnp

    from tigerbeetle_tpu.models import state_machine
    from tigerbeetle_tpu.ops import commit, u128

    def mix(new, old, keep_new):
        return type(new)(*[jnp.where(keep_new(n), n, o) for n, o in zip(new, old)])

    if fault in ("lossy_scatter", "backups_lossy_scatter"):
        sound = u128.scatter_add

        def scatter_add(table, slots, values, mask):
            halves = u128.split_u16(values)
            halves = jnp.where(mask[:, None], halves, jnp.zeros_like(halves))
            safe = jnp.where(mask, slots, table.shape[0]).astype(jnp.int32)
            acc = jnp.zeros((table.shape[0], 2 * table.shape[1]), dtype=jnp.uint32)
            acc = acc.at[safe].set(halves, mode="drop")  # the last write wins
            delta, delta_over = u128.combine_u16(acc)
            new_table, over = u128.add(table, delta)
            return new_table, (over | delta_over)

        if fault == "lossy_scatter":
            u128.scatter_add = scatter_add
        else:
            # Which scatter a replica's kernels get is settled when they are traced, at its
            # first batch of transfers: by then the cluster has a primary.
            from tigerbeetle_tpu.vsr import replica as vsr_replica

            replicas, said, init = [], set(), vsr_replica.Replica.__init__

            def remember(self, *args, **kw):
                init(self, *args, **kw)
                replicas.append(self)

            def by_role(table, slots, values, mask):
                me = replicas[-1]
                lossy = not me.is_primary
                note = (f"NOTE: backups_lossy_scatter: replica {me.replica} traces its kernel in "
                        f"view {me.view} as {'a BACKUP: lossy' if lossy else 'the PRIMARY: sound'}")
                if note not in said:  # (a kernel posts four times)
                    said.add(note)
                    print(note, file=sys.stderr, flush=True)
                return (scatter_add if lossy else sound)(table, slots, values, mask)

            vsr_replica.Replica.__init__ = remember
            u128.scatter_add = by_role
    elif fault in ("state_unchanged", "half_left_out", "code_altered"):
        def keep(new_state, old_state):
            if fault == "state_unchanged":
                return old_state
            if fault == "half_left_out":
                even = lambda leaf: (jnp.arange(leaf.shape[0]) % 2 == 0).reshape(  # noqa: E731
                    (-1,) + (1,) * (leaf.ndim - 1))
                return mix(new_state, old_state, even)
            return new_state

        fast, exact = commit.create_transfers_fast, commit.create_transfers_exact

        @jax.jit
        def broken_fast(state, b, host_code):
            new_state, codes, bail = fast(state, b, host_code)
            if fault == "code_altered":
                codes = codes.at[0].set(jnp.where(codes[0] == 0, 18, codes[0]))
            return keep(new_state, state), codes, bail

        def broken_exact(state, *args, **kw):
            new_state, codes, *rest = exact(state, *args, **kw)
            if fault == "code_altered":
                codes = codes.at[0].set(jnp.where(codes[0] == 0, 18, codes[0]))
            return (keep(new_state, state), codes, *rest)

        commit.create_transfers_fast = broken_fast
        commit.create_transfers_exact = broken_exact
    elif fault == "chains_unlinked":
        import numpy as np

        from tigerbeetle_tpu.ops import commit_exact

        exact = commit.create_transfers_exact

        def unlinked_exact(state, b, host_code, pending, chain_id, plan=None, **kw):
            alone = np.arange(len(chain_id), dtype=np.int32)
            plan = commit_exact.build_sort_plan(
                np.asarray(b.flags), np.asarray(b.dr_slot), np.asarray(b.cr_slot),
                pending.dr_slot, pending.cr_slot, alone, pending.group,
                int(state.ledger.shape[0]))
            return exact(state, b, host_code, pending, alone, plan, **kw)

        commit.create_transfers_exact = unlinked_exact
    elif fault == "limit_flags_dropped":
        import numpy as np

        register = commit.register_accounts

        def register_accounts(state, slots, ledger, flags, mask):
            return register(state, slots, ledger, np.zeros_like(flags), mask)

        commit.register_accounts = register_accounts
    elif fault == "ops_relabelled":
        from tigerbeetle_tpu.vsr import header as hdr
        from tigerbeetle_tpu.vsr.header import Command, Operation

        seen = {}  # client id -> its place among the clients that sent transfers

        def raised(fields: dict) -> dict:
            if fields.get("operation") != Operation.CREATE_TRANSFERS:
                return fields
            place = seen.setdefault(int(fields["client"]), len(seen))
            return {**fields, "op": int(fields["op"]) + (1 << 20)} if place % 2 else fields

        # (a reply is sealed inline or, on the staged path, by the replica's builder)
        make_sealed, build_one = hdr.make_sealed, hdr.ReplyBuilder.build_one

        def relabelled(command, cluster=0, body=b"", **fields):
            if command == Command.REPLY:
                fields = raised(fields)
            return make_sealed(command, cluster, body=body, **fields)

        hdr.make_sealed = relabelled
        hdr.ReplyBuilder.build_one = lambda self, spec: build_one(self, raised(spec))
    elif fault == "stale_reads":
        fast, exact = commit.create_transfers_fast, commit.create_transfers_exact
        sm = state_machine.StateMachine
        accounts, transfers = sm.lookup_accounts, sm.lookup_transfers
        before = []  # the state the newest commit kernel was given, until a read has passed

        def remembering(kernel):
            def commit_kernel(state, *args, **kw):
                before[:] = [state]
                return kernel(state, *args, **kw)
            return commit_kernel

        def lookup_accounts(self, ids_lo, ids_hi):
            if not before:
                return accounts(self, ids_lo, ids_hi)
            live, self.state = self.state, before.pop()
            try:
                return accounts(self, ids_lo, ids_hi)
            finally:
                self.state = live

        def lookup_transfers(self, ids_lo, ids_hi):
            before.clear()
            return transfers(self, ids_lo, ids_hi)

        commit.create_transfers_fast = remembering(fast)
        commit.create_transfers_exact = remembering(exact)
        sm.lookup_accounts, sm.lookup_transfers = lookup_accounts, lookup_transfers
    elif fault == "store_altered":
        real = state_machine.StateMachine.lookup_transfers

        def lookup_transfers(self, ids_lo, ids_hi):
            out = real(self, ids_lo, ids_hi).copy()
            if len(out):
                out["amount_lo"][0] += 1
            return out

        state_machine.StateMachine.lookup_transfers = lookup_transfers
    else:
        raise SystemExit(f"unknown BENCH_FAULT {fault!r}")


if __name__ == "__main__":
    from benchmarks import serve

    plant(os.environ["BENCH_FAULT"])
    sys.exit(serve.main(sys.argv[1:]))
