"""Ask the TPU's compiler, without a TPU, whether it accepts the served
path's kernels at production widths (`on-chip-measurement` guide,
section 2, rehearsal 3): state tables for accounts_max = 2^20
(`production`) and 2^24 (`production_16m`), and the n = 8192 batch bucket
an 8190-event message pads to. libtpu compiles for a v5e that is
DESCRIBED, not attached.

A compile that passes is not a chip run — nothing executes, so nothing
here says anything about results or times. What it catches, at no chip
time: a program the chip's compiler refuses, or one whose temporaries
do not fit the chip's 16 GB.

All of these live in THIS one file and describe the topology inside a
module-scoped fixture — never at import — because only one process may
load libtpu: a second test file could land on another worker, whose
fixture would then skip every test in silence.
"""

import os

import numpy as np
import pytest

import jax
from jax.sharding import SingleDeviceSharding

from tigerbeetle_tpu.constants import PRODUCTION, PRODUCTION_16M
from tigerbeetle_tpu.models.state_machine import BALANCE_READ_CHUNK
from tigerbeetle_tpu.ops import commit as commit_ops
from tigerbeetle_tpu.ops import commit_exact

# Account slots on the device, by preset: 2^20, and 2^24 (a 1.125 GiB state,
# beside which a program's transient accumulators and un-donated copy must fit).
TABLES = pytest.mark.parametrize(
    "a", [PRODUCTION.accounts_max, PRODUCTION_16M.accounts_max], ids=["2^20", "2^24"])
N = 8192  # the batch bucket: PRODUCTION.batch_max, 8190 events, pads to it
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu from describing it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device is written to the persistent
    # cache but cannot be read back without a chip (the next one would
    # warn and compile again): keep it off around these tests.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    """The same pytree with every array leaf replaced by its shape on
    the described chip (there is no device to hold an array)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _compile(fn, one_chip, *args, **static):
    compiled = fn.lower(*_shapes(args, one_chip), **static).compile()
    mem = compiled.memory_analysis()
    resident = (
        mem.temp_size_in_bytes + mem.argument_size_in_bytes
        + mem.output_size_in_bytes
    )
    assert resident < HBM_BYTES, mem
    return compiled


def _ledger_state(a):
    return jax.eval_shape(lambda: commit_ops.init_state(a))


def _transfer_batch(n):
    u32 = lambda *shape: np.zeros(shape, np.uint32)
    return commit_ops.TransferBatch(
        id=u32(n, 4), dr_slot=np.zeros(n, np.int32),
        cr_slot=np.zeros(n, np.int32), amount=u32(n, 4),
        pending_id=u32(n, 4), timeout=u32(n), ledger=u32(n), code=u32(n),
        flags=u32(n), timestamp=u32(n, 2),
    )


@TABLES
def test_create_transfers_fast(one_chip, a):
    _compile(
        commit_ops.create_transfers_fast, one_chip,
        _ledger_state(a), _transfer_batch(N), np.zeros(N, np.uint32),
    )


@TABLES
@pytest.mark.parametrize("has_pv,has_chains,walk", [
    (True, True, False), (False, False, False), (False, True, False), (True, False, False),
    (False, False, True),
], ids=["pv+chains", "plain", "chains", "pv", "plain+walk"])
def test_create_transfers_exact(one_chip, has_pv, has_chains, walk, a):
    """All four corners of the static-flag square the state machine
    compiles (has_pv / has_chains follow the batch's content): settlement
    batches carry both, TPC-B's chains alone; and the plain batch whose
    lines are walked (SmallBank's: a `LinePlan`, whose shapes follow the
    batch bucket alone)."""
    i32 = lambda *shape: np.zeros(shape, np.int32)
    pending = commit_exact.PendingInfo(
        found=np.zeros(N, bool), amount=np.zeros((N, 4), np.uint32),
        dr_slot=i32(N), cr_slot=i32(N),
        timestamp=np.zeros((N, 2), np.uint32), timeout=np.zeros(N, np.uint32),
        base_fulfillment=i32(N), group=i32(N),
    )
    plan = commit_exact.SortPlan(
        perm=i32(2 * N), inv_perm=i32(2 * N), head_pos=i32(2 * N),
        sub_head_pos=i32(2 * N), f_perm=i32(N), f_inv_perm=i32(N),
        f_head_pos=i32(N), f_sub_head_pos=i32(N),
    )
    lines = commit_exact.LinePlan(
        order=i32(N + commit_exact.LINE_WIDTH), start=i32(N), count=i32(N),
        pred=i32(N + commit_exact.LINE_WIDTH, 2), at=i32(N),
    ) if walk else None
    compiled = _compile(
        commit_exact.create_transfers_exact, one_chip,
        _ledger_state(a), _transfer_batch(N), np.zeros(N, np.uint32),
        pending, i32(N), plan, lines,
        has_pv=has_pv, has_chains=has_chains,
    )
    # The v5e program carries the sweep count out beside the bail flag:
    # (state, codes, amounts, dr_after, cr_after, bail, sweeps).
    *_, bail, sweeps = compiled.out_info
    assert (bail.shape, bail.dtype) == ((), np.bool_)
    assert (sweeps.shape, sweeps.dtype) == ((), np.int32)
    # The kernel's work follows the batch, not the table: no transient the
    # size of a balance table (the dense post built eleven of them, 5.96 GB
    # at 2^24). What is table-sized is the un-donated state, in and out.
    mem = compiled.memory_analysis()
    if a == PRODUCTION_16M.accounts_max:
        assert mem.temp_size_in_bytes < mem.argument_size_in_bytes, (
            f"temp {mem.temp_size_in_bytes} B, arguments {mem.argument_size_in_bytes} B, "
            f"outputs {mem.output_size_in_bytes} B"
        )


@TABLES
def test_the_balance_access_entries(one_chip, a):
    """`register_accounts` and `write_balances` at the batch bucket, and
    `read_balances` at the bucket (a `lookup_accounts` request: the one
    program the benchmark's `compiles_in_window` reads) and at the
    checkpoint's one shape (`snapshot.append_balances`: the touched slots,
    or every slot, in chunks of `BALANCE_READ_CHUNK`)."""
    u32 = lambda *shape: np.zeros(shape, np.uint32)
    slots = np.zeros(N, np.int32)
    state = _ledger_state(a)
    _compile(commit_ops.register_accounts, one_chip,
             state, slots, u32(N), u32(N), np.zeros(N, bool))
    _compile(commit_ops.write_balances, one_chip,
             state, slots, u32(N, 4), u32(N, 4), u32(N, 4), u32(N, 4))
    for k in (N, BALANCE_READ_CHUNK):
        compiled = _compile(commit_ops.read_balances, one_chip, state, np.zeros(k, np.int32))
        assert [o.shape for o in compiled.out_info] == [(k, 4)] * 4
