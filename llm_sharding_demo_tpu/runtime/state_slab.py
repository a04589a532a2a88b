"""The state slab: per-ROW state snapshots beside the paged pool.

The paged pool (``runtime.kv_pool``) stores what a model caches per
POSITION, in blocks. A family whose layers (some of them) keep a state
that belongs to a row and not to a position (``models.row_state``:
``gdn_moe``'s linear-attention matrices and convolution tails) has
nothing to page: the state is one fixed-size record a row, rewritten
whole by every step.

- a **live row's** record is a LANE of its batch's working cache
  (``KVCache.state``, lanes on axis 1): there from the seed's prefill
  or the join (``iterbatch._admit_cache``) to the row's end, as the K/V
  planes are. It takes no slot here and no call moves it; a preempted
  row's is rebuilt by recompute;
- the slab holds the prefix store's **snapshots**: ``slots`` records,
  each leaf one device array ``[layers, slots, ...]`` with the slot on
  axis 1, where the engine's caches have the batch. A snapshot is the
  state at the boundary a prefix-store entry was registered for, keyed
  by the entry's content key: taken when the entry is inserted, copied
  out (never handed over) on a hit, freed when the entry is dropped:
  LRU eviction, the capacity trim and pool pressure alike, through the
  allocator's ``_on_prefix_drop`` hook. Blocks of a deeper entry are
  shared structurally with the shallower one's; a state cannot be, so
  every entry owns its snapshot.

Slot lifecycle (docs/ARCHITECTURE.md has it beside the blocks')::

    free -> snapshot  (an entry registered; immutable)
         -> free      (the entry dropped)

Both movers carry ONE record: gathering several slots at once copied
the whole slab twice where a leaf's minor axes are ``[256, 128]``
(0.63-0.92 GB of temporaries at 2-16 ids on a v5e;
tests/test_tpu_compile_engine.py).

Host accounting (free list, snapshot map, counters) lives under
``_lock``; the device arrays are rebound only under ``_dev_lock``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import graftmem, graftsched, graftscope
from ..utils.metrics import CompileWatch

# Static-analysis contract (tools/graftcheck): every ``jax.jit`` site in
# this module, by holding attribute.
JIT_ENTRY_POINTS = ("_gather", "_scatter")

# Observability contract (tools/graftcheck scope pass): both movers'
# dispatches are timed into the graftscope ring (one program each: the
# slot is a traced operand).
PROFILED_SCOPES = ("_gather", "_scatter")

# Donation contract (tools/graftcheck sanitize pass): the scatter
# consumes the slab's arrays (arg 0); ``self.data`` is re-bound from its
# output under ``_dev_lock``.
DONATED_ARGS = {"_scatter": (0,)}

# HBM-ledger contract (tools/graftcheck memory pass + utils/graftmem):
# the slab's arrays are its one long-lived device holding; sizes are
# constant across the donated scatter, so registration at construction
# is the whole lifecycle.
MEMORY_LEDGER = {"data": "state_slab"}

# Lock-discipline contract (tools/graftcheck locks pass).
GUARDED_STATE = {
    "_free": "_lock", "_snap": "_lock", "in_use": "_lock",
    "peak": "_lock", "restores": "_lock", "evictions": "_lock",
    "rows_gathered": "_lock", "rows_scattered": "_lock",
    "data": "_dev_lock",
}

# A restore reads the snapshot map under the device lock, so that the
# slot it found cannot be freed, retaken and rewritten before the copy
# is dispatched; nothing takes them the other way round.
LOCK_ORDER = ("_dev_lock", "_lock")

# ``_dev_lock`` serializes device work by design (the arrays are donated
# through every scatter).
DEVICE_LOCKS = ("_dev_lock",)


def _gather_state_impl(data, slot):
    return tuple(jax.lax.dynamic_slice_in_dim(x, slot, 1, axis=1)
                 for x in data)


def _scatter_state_impl(data, state, slot):
    return tuple(
        jax.lax.dynamic_update_slice_in_dim(x, s.astype(x.dtype), slot,
                                            axis=1)
        for x, s in zip(data, state))


class StateSlab:
    """``slots`` per-row state records on the device, their free list,
    and the snapshots the prefix store keeps in them."""

    def __init__(self, leaves: Sequence[Tuple[tuple, object]], slots: int):
        """``leaves``: ``(shape, dtype)`` of each leaf of one row's
        state, batch axis left out (``models.row_state``)."""
        if slots < 1:
            raise ValueError(f"slots={slots} must be >= 1")
        self.slots = slots
        self.data = tuple(
            jnp.zeros(shape[:1] + (slots,) + shape[1:], dtype)
            for shape, dtype in leaves)
        self.bytes_per_slot = sum(x.nbytes for x in self.data) // slots
        self._lock = graftsched.lock("state_slab.StateSlab._lock")
        self._dev_lock = graftsched.rlock("state_slab.StateSlab._dev_lock")
        self._free: List[int] = list(range(slots - 1, -1, -1))
        self._snap: Dict[bytes, int] = {}
        self.in_use = 0
        self.peak = 0
        self.restores = 0
        self.evictions = 0
        # records the movers carried (a restore out of its slot, a
        # snapshot into its slot), counted on the host: times
        # ``bytes_per_slot`` the bytes the store's boundaries moved
        self.rows_gathered = 0
        self.rows_scattered = 0
        graftmem.track(self, "data", "state_slab", self.data)
        self._gather = graftscope.instrument(
            jax.jit(_gather_state_impl), "state_slab._gather")
        self._scatter = graftscope.instrument(
            jax.jit(_scatter_state_impl, donate_argnums=(0,)),
            "state_slab._scatter")
        self._compile_watches = (
            CompileWatch("state_slab", self._gather),
            CompileWatch("state_slab", self._scatter))

    # -- accounting ----------------------------------------------------------

    def alloc(self) -> Optional[int]:
        """One free slot, or ``None``: the store evicts an entry, whose
        snapshot then comes back, and asks again."""
        with self._lock:
            if not self._free:
                return None
            self.in_use += 1
            self.peak = max(self.peak, self.in_use)
            return self._free.pop()

    def free(self, slot: Optional[int]) -> None:
        if slot is None:
            return
        with self._lock:
            if slot in self._free or not 0 <= slot < self.slots:
                raise ValueError(f"free of slot {slot}: not allocated")
            self._free.append(slot)
            self.in_use -= 1

    def note_compiles(self) -> None:
        for w in self._compile_watches:
            w.check()

    # -- snapshots (the prefix store's) --------------------------------------

    def snapshot(self, key: bytes, slot: int, state: tuple) -> None:
        """Keep ``state`` (one row) in ``slot`` (from ``alloc``) as the
        snapshot of the store entry ``key``."""
        with self._dev_lock:
            self.data = self._scatter(self.data, state, np.int32(slot))
        with self._lock:
            self.rows_scattered += 1
            old = self._snap.pop(key, None)
            self._snap[key] = slot
        self.free(old)

    def restore(self, key: bytes) -> Optional[tuple]:
        """A COPY of the snapshot of ``key`` as one row's state, or
        ``None`` if the entry has none (dropped since the lookup)."""
        with self._dev_lock:
            with self._lock:
                slot = self._snap.get(key)
                if slot is None:
                    return None
                self.restores += 1
                self.rows_gathered += 1
            return self._gather(self.data, np.int32(slot))

    def drop(self, keys) -> None:
        """The store dropped these entries: their snapshots go with
        them (the allocator's ``_on_prefix_drop`` hook)."""
        for key in keys:
            with self._lock:
                slot = self._snap.pop(key, None)
                if slot is not None:
                    self.evictions += 1
            self.free(slot)

    def stats(self) -> dict:
        with self._lock:
            return {"state.slots": self.slots, "state.in_use": self.in_use,
                    "state.peak": self.peak,
                    "state.snapshots": len(self._snap),
                    "state.restores": self.restores,
                    "state.evictions": self.evictions,
                    "state.rows_gathered": self.rows_gathered,
                    "state.rows_scattered": self.rows_scattered,
                    "state.row_bytes": self.bytes_per_slot}
