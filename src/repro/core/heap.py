"""Node heap: structure-of-arrays storage for B+Tree node buffers.

The paper allocates fixed 8 KB node buffers in pinned host memory and
addresses them physically (Section 3.1).  Here a *physical slot* is a row
across a set of packed numpy arrays — the layout the TPU read path and the
Pallas kernels consume directly.  Buffers are never mutated after they are
published to readers except for the leaf fast path (log append), exactly
mirroring the paper: structural changes allocate fresh slots and swap a LID
mapping (Section 3.4); the in-place log append is made safe by MVCC version
filtering (Section 3.2).

The 64-bit packed (size, lock, seqno) word of the paper's header is kept as
``lockword``: bit 63 = lock bit, bits 32..62 = sequence number, low 32 bits =
bytes-used stand-in (item count).  ``try_lock`` implements the
compare-and-swap-with-expected-seqno protocol of Section 3.4.
"""
from __future__ import annotations

import numpy as np

from .config import HoneycombConfig
from .schema import FIELD_NAMES, NODE_SCHEMA

INTERIOR, LEAF = 0, 1
NULL = -1

# log entry op codes (paper Section 3.1: inserted/updated items or delete
# markers)
LOG_INSERT, LOG_UPDATE, LOG_DELETE = 0, 1, 2

_LOCK_BIT = np.int64(1) << np.int64(63)
_SEQ_SHIFT = np.int64(32)
_SEQ_MASK = (np.int64(1) << np.int64(31)) - np.int64(1)


class NodeHeap:
    """Slab of node buffers with a free list."""

    def __init__(self, cfg: HoneycombConfig, capacity: int = 1024):
        self.cfg = cfg
        self.capacity = 0
        self._free: list[int] = []
        # rows whose packed arrays changed since the last device sync — the
        # unit of host->accelerator delta transfer (paper: one node buffer)
        self.dirty: set[int] = set()
        # bumped when the arrays are reallocated (growth): resident device
        # snapshots have the old shapes and need a full republish
        self.generation = 0
        self._alloc_arrays(capacity)

    # -- storage -------------------------------------------------------------
    def _alloc_arrays(self, capacity: int):
        c = self.cfg
        old = self.capacity

        def grow(name, shape, dtype, fill=0):
            new = np.full((capacity, *shape), fill, dtype=dtype)
            if old:
                new[:old] = getattr(self, name)
            setattr(self, name, new)

        # every device-visible per-node field comes from the one layout
        # schema (core/schema.py) — same names, order, host dtypes and NULL
        # fills the packed node image is defined over.  svals lane 0 holds
        # the child LID on interior nodes; svallen doubles as overflow tag.
        for spec in NODE_SCHEMA:
            grow(spec.name, spec.shape(c), np.dtype(spec.host), spec.fill)
        # host-only lock/seqno word (Section 3.4): never crosses the bus,
        # so it lives outside the schema
        grow("lockword", (), np.int64)

        self._free.extend(range(capacity - 1, old - 1, -1))
        self.capacity = capacity
        self.generation += 1

    # device-visible per-node fields, in schema/layout order
    ARRAY_FIELDS = FIELD_NAMES

    # -- alloc / free ----------------------------------------------------------
    def alloc(self) -> int:
        if not self._free:
            self._alloc_arrays(self.capacity * 2)
        slot = self._free.pop()
        self.dirty.add(slot)       # caller fills the buffer next
        return slot

    def free(self, slot: int):
        self._wipe(slot)
        self.dirty.add(slot)
        self._free.append(slot)

    def mark_dirty(self, slot: int):
        """Record an in-place mutation of a published buffer (log append,
        sibling relink) for the next delta sync."""
        self.dirty.add(slot)

    def _wipe(self, s: int):
        self.ntype[s] = 0
        self.nitems[s] = 0
        self.version[s] = 0
        self.oldptr[s] = NULL
        self.left_child[s] = NULL
        self.lsib[s] = NULL
        self.rsib[s] = NULL
        self.lockword[s] = 0
        self.n_shortcuts[s] = 0
        self.nlog[s] = 0
        self.skeylen[s] = 0
        self.svallen[s] = 0

    @property
    def live_slots(self) -> int:
        return self.capacity - len(self._free)

    # -- lock word (Section 3.4) ----------------------------------------------
    def seqno(self, s: int) -> int:
        return int((self.lockword[s] >> _SEQ_SHIFT) & _SEQ_MASK)

    def is_locked(self, s: int) -> bool:
        return bool(self.lockword[s] & _LOCK_BIT)

    def try_lock(self, s: int, expected_seqno: int) -> bool:
        """CAS(lock=0, seqno=expected) -> lock=1.  Single host process, so a
        plain check-and-set is an atomic CAS; the protocol (restart on seqno
        mismatch) is what the tests exercise."""
        if self.is_locked(s) or self.seqno(s) != expected_seqno:
            return False
        self.lockword[s] |= _LOCK_BIT
        return True

    def unlock_bump(self, s: int):
        """Paper: size/seqno/lock packed in one word so the update is a single
        store — here: clear lock, increment seqno."""
        seq = (self.seqno(s) + 1) & int(_SEQ_MASK)
        self.lockword[s] = (np.int64(seq) << _SEQ_SHIFT)

    def unlock(self, s: int):
        self.lockword[s] &= ~_LOCK_BIT


class OverflowHeap:
    """Out-of-node value storage (paper Section 3.1: a value too long for
    the node lives outside it).  A value longer than the inline width
    (``val_words`` lanes, 16 B by default) takes one slot of
    ``cfg.overflow_words`` words; the node keeps the slot id in lane 0.
    A slot holds the value's bytes as they are, so a slot row copied back
    from the device value image (``TreeSnapshot.values``) is the value.

    Slots are immutable once written and recycled through GC only after
    the epoch window has passed them.  ``fresh`` holds the slots allocated
    since the last sync (the value image's delta, as ``NodeHeap.dirty`` is
    the node image's); growth doubles the capacity and bumps
    ``generation``, which forces a full republish of the value image."""

    def __init__(self, cfg: HoneycombConfig, capacity: int = 256):
        self.cfg = cfg
        self.slot_bytes = cfg.overflow_words * 4
        self.vals = np.zeros((capacity, cfg.overflow_words), np.uint32)
        self.lens = np.zeros((capacity,), np.int32)
        self._free = list(range(capacity - 1, -1, -1))
        self.fresh: set[int] = set()
        self.generation = 0
        self.allocs = 0            # values ever stored (0: no value image)

    def check(self, n: int):
        """Refuse a value no slot can hold."""
        if n > self.slot_bytes:
            raise ValueError(
                f"value of {n} B is longer than an overflow slot of "
                f"{self.slot_bytes} B (overflow_words={self.cfg.overflow_words})")

    def alloc(self, data: bytes) -> int:
        self.check(len(data))
        if not self._free:
            cap = len(self.lens)
            self.vals = np.concatenate([self.vals, np.zeros_like(self.vals)])
            self.lens = np.concatenate([self.lens, np.zeros_like(self.lens)])
            self._free.extend(range(2 * cap - 1, cap - 1, -1))
            self.generation += 1
        slot = self._free.pop()
        row = self.vals[slot].view(np.uint8)
        row[len(data):] = 0
        row[:len(data)] = np.frombuffer(data, np.uint8)
        self.lens[slot] = len(data)
        self.fresh.add(slot)
        self.allocs += 1
        return slot

    def read(self, slot: int) -> bytes:
        return self.vals[slot].view(np.uint8)[:int(self.lens[slot])].tobytes()

    def free(self, slot: int):
        self.lens[slot] = 0
        self._free.append(slot)
