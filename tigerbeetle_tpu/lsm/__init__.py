"""The LSM tier: durable grid-backed tables, indexes, and the object log.

Mirrors the reference's LSM forest (/root/reference/src/lsm/) TPU-first:
  - lsm/tree.py   — DurableIndex: sorted tables on grid blocks (index block
                    + data blocks), leveled compaction streamed through the
                    host's C k-way merge (lsm/store.py).
  - lsm/log.py    — DurableLog: append-only object store (commit order ==
                    timestamp key order, so the object tree needs no sort).
  - lsm/store.py  — U128Index: the in-RAM sorted-run index (account id →
                    slot; bounded by accounts_max) + pack_keys helpers.
Backed by io/grid.py (write-once checksummed blocks + EWAH free set).
Host code only: no module here imports ops/ or jax.
"""

from tigerbeetle_tpu.lsm.log import DurableLog  # noqa: F401
from tigerbeetle_tpu.lsm.store import KEY_DTYPE, NOT_FOUND, U128Index, pack_keys  # noqa: F401
from tigerbeetle_tpu.lsm.tree import DurableIndex  # noqa: F401
