"""StoreShard — one device's slice of the store (tree + resident snapshot).

This is the per-device unit the sharded serving stack is built from: a host
B+Tree writer (``HoneycombTree``), the MVCC/epoch machinery, an interior
cache, and the accelerator read path, all bound to a DOUBLE-BUFFERED
resident device snapshot kept in sync by the incremental delta subsystem
(see core/pipeline.py for the pipeline design):

  * ``begin_export()`` / ``flip()`` — the two halves of the
    host->accelerator synchronization point (the PCIe DMA + page-table
    command analogue).  ``begin_export`` *stages*: the first export
    publishes the packed heap arrays wholesale; afterwards only *dirty
    node rows* plus the batched page-table commands and the read version
    are scattered — asynchronously — into the STANDBY buffer, so sync
    traffic scales with write volume, not store size, and in-flight read
    batches keep answering from the untouched active snapshot.  ``flip``
    *publishes*: an atomic epoch advance that makes the standby active
    (``epoch`` counts flips); old-epoch snapshots are functional device
    copies and keep answering at their pinned read version.
  * ``export_snapshot()`` ≡ ``begin_export(); flip()`` — the serial
    composition, byte-for-byte what the pre-pipeline code did.
    ``SyncStats`` meters both sync modes, plus a log-entry *wire-format*
    estimate (key+value+op per write) so benchmarks can compare dirty-row
    accounting against the paper's append-only log-block encoding.
  * ``cfg.sync_policy`` — when the sync happens: lazily before device reads
    ("on_read"), after every K writes ("every_k"), or only when explicitly
    requested ("explicit", stale-but-consistent reads).  Under "explicit"
    the shard pins an accelerator epoch for the resident snapshot so host
    fallbacks can run at the snapshot's read version (GC keeps the old
    buffers alive until the next export).
  * ``get_batch()/scan_batch()`` — wait-free accelerated reads against the
    shard's snapshot, epoch-stamped so GC never reclaims a buffer an
    in-flight batch might read.  Batch lengths are padded to power-of-two
    buckets so the jit cache stays bounded under ragged per-shard batches.

``HoneycombStore`` (core/store.py) is a single StoreShard behind the public
facade; ``ShardedHoneycombStore`` (core/router.py) range-partitions the
keyspace across many of them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .api import (OPS_BY_KIND, WIRE_ENTRY_OVERHEAD, Delete, Routing,
                  wire_entry_nbytes)
from .btree import HoneycombTree
from .cache import InteriorCache
from .config import HoneycombConfig, bucket_pow2
from .keys import pack_keys
from .pipeline import PipelineStats
from .read_path import (NODE_FIELDS, GetResult, LegacySnapshotDelta,
                        LegacyTreeSnapshot, ScanResult, SnapshotDelta,
                        TreeSnapshot, ValueDelta, apply_snapshot_delta,
                        attach_cache_image, batched_get, batched_scan)
from .schema import NARROWED_FIELDS, NodeImageLayout
from .telemetry import CLOCK, samples_from, span
from repro.kernels import ops as kernel_ops
# EpochSan seams (repro/analysis/epochsan.py): get() is None unless the
# sanitizer is enabled, so each hook costs one call + None test
from ..analysis import epochsan as _epochsan


def _u32_rows(x: jax.Array) -> jax.Array:
    """One result field as u32 words, one row per lane (i32 bit-cast,
    bools as 0/1)."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint32)
    elif x.dtype != jnp.uint32:
        x = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return x.reshape(x.shape[0], -1)


def _pack_read(res, meters: jax.Array) -> jax.Array:
    """A read batch's answer as ONE flat u32 buffer: row b holds lane b's
    result fields side by side, in field order, and the three meters
    [vmem_hits, heap_gathers, lb_routed] follow the last row.  The host
    copies it back in one transfer (``_unpack_read``), paying the copy's
    fixed price once rather than once per field."""
    rows = jnp.concatenate([_u32_rows(f) for f in res], axis=1)
    return jnp.concatenate([rows.reshape(-1), jax.lax.bitcast_convert_type(
        meters.astype(jnp.int32), jnp.uint32)])


def _packed(read: Callable, metered: bool) -> Callable:
    """``read`` with its answer packed by ``_pack_read`` in the same
    program (the reference path has no meters: zeros).  The wrapper keeps
    ``read``'s name, so the program is still ``jit_<read>``: the
    benchmark's read-kernel metrics find its device time by that name."""
    @functools.wraps(read)
    def run(*args, **kw):
        out = read(*args, **kw)
        res, meters = out if metered else (out, jnp.zeros(3, jnp.int32))
        return _pack_read(res, meters)
    return run


def _read_fields(result_type, cfg: HoneycombConfig):
    """(per-lane shape, host dtype) of each field of a packed result, in
    field order; bool fields come back as u32 0/1."""
    m, kw, vw = cfg.max_scan_items, cfg.key_words, cfg.val_words
    i32, u32 = np.int32, np.uint32
    if result_type is ScanResult:
        return (((), i32), ((m, kw), u32), ((m,), i32), ((m, vw), u32),
                ((m,), i32), ((), u32))
    return (((), u32), ((vw,), u32), ((), i32))


def _field_offsets(result_type, cfg: HoneycombConfig):
    """Word offset of each field in a packed result row, and the row's
    width in words."""
    sizes = [int(np.prod(shape)) for shape, _ in _read_fields(result_type,
                                                              cfg)]
    offs = np.cumsum([0] + sizes).tolist()
    return offs[:-1], offs[-1]


def _value_positions(result_type, cfg: HoneycombConfig) -> int:
    """Values per lane of a result: one per GET, ``max_scan_items`` per
    SCAN."""
    return cfg.max_scan_items if result_type is ScanResult else 1


def gather_values(packed: jax.Array, values: jax.Array, *, result_type,
                  cfg: HoneycombConfig) -> jax.Array:
    """Append a read batch's out-of-node values to its packed answer
    (``_pack_read``'s buffer): one row of ``overflow_words`` words per
    value position, lane-major (a GET lane's value, a SCAN lane's item
    slots), holding the value image row of the slot in the value's lane 0
    where the value is longer than the inline width, zeros elsewhere.
    The batch still comes back in one copy; the host takes the value
    bytes from the rows (``_fetch_read``).  Its own program
    (``jit_gather_values``), so the trace times it apart from the read
    kernel."""
    offs, width = _field_offsets(result_type, cfg)
    items = _value_positions(result_type, cfg)
    lanes = (packed.shape[0] - 3) // width
    rows = packed[:lanes * width].reshape(lanes, width)
    va = offs[result_type._fields.index("vals")]
    la = offs[result_type._fields.index("vallens")]
    slot = rows[:, va:va + items * cfg.val_words].reshape(
        lanes, items, cfg.val_words)[..., 0].astype(jnp.int32)
    length = jax.lax.bitcast_convert_type(rows[:, la:la + items], jnp.int32)
    long = length > cfg.max_inline_val_bytes
    got = values[jnp.where(long, slot, 0)]
    got = jnp.where(long[..., None], got, jnp.uint32(0))
    return jnp.concatenate([packed, got.reshape(-1)])


def scatter_values(values: jax.Array, slots: jax.Array,
                   rows: jax.Array) -> jax.Array:
    """The value image with one sync's new slots written.  Functional, as
    the node image's scatter: snapshots that in-flight batches hold keep
    their image."""
    return values.at[slots].set(rows)


def _unpack_read(buf: np.ndarray, result_type, cfg: HoneycombConfig):
    """Split a host copy of ``_pack_read``'s buffer into ``result_type``
    of zero-copy numpy views, and the meters (i32[3])."""
    offs, width = _field_offsets(result_type, cfg)
    lanes = (buf.size - 3) // width
    rows = buf[:lanes * width].reshape(lanes, width)
    out = [rows[:, at:at + int(np.prod(shape))].view(dtype)
           .reshape((lanes,) + shape)
           for (shape, dtype), at in zip(_read_fields(result_type, cfg), offs)]
    return result_type._make(out), buf[-3:].view(np.int32)


# jit the accelerator entry points once per (config, snapshot-shape): the
# eager op-by-op dispatch otherwise accumulates thousands of tiny LLVM JIT
# dylibs across a benchmark run (vm.max_map_count exhaustion).  Each
# returns ``_pack_read``'s one buffer.
_jit_get = jax.jit(_packed(batched_get, False), static_argnames="cfg")
_jit_scan = jax.jit(_packed(batched_scan, False), static_argnames="cfg")
# the fused read path (ONE traversal dispatch per batch, cache tier pinned
# in VMEM — kernels/fused_read.py): compiled Pallas on TPU, the jnp oracle
# everywhere else (XLA:CPU lowers it; interpret-mode parity is tested).
# The kernel backend is resolved when the first batch traces
# (kernels/ops.py), never at import.
_jit_get_fused = jax.jit(_packed(kernel_ops.batched_get_fused, True),
                         static_argnames=("cfg", "lb_fraction", "backend"))
_jit_scan_fused = jax.jit(_packed(kernel_ops.batched_scan_fused, True),
                          static_argnames=("cfg", "lb_fraction", "backend"))
# the delta-sync scatter; NOT donated — old snapshots held by in-flight
# batches must keep answering at their read version.
_jit_apply_delta = jax.jit(apply_snapshot_delta,
                           static_argnames=("backend", "cfg"))
# the value image: the gather after each read program of a store that
# holds out-of-node values, and the scatter of a sync's new value slots
_jit_gather_values = jax.jit(gather_values,
                             static_argnames=("result_type", "cfg"))
_jit_scatter_values = jax.jit(scatter_values)


def apply_value_delta(values: jax.Array | None,
                      vd: ValueDelta) -> jax.Array:
    """The value image after one sync's ``ValueDelta``."""
    if vd.slots is None:
        return vd.rows
    return _jit_scatter_values(values, vd.slots, vd.rows)


def sync_backend() -> str | None:
    """Backend of the sync scatters, decided at dispatch: the compiled
    Pallas kernels on a TPU (kernels/delta_scatter.py), elsewhere None —
    the jnp scatter XLA lowers (``apply_snapshot_delta``)."""
    return "pallas" if kernel_ops.default_backend() == "pallas" else None

# snapshot fields narrowed to int32 on device (host keeps 64-bit authority)
# — derived from the one layout schema, not hand-kept
_I32_FIELDS = NARROWED_FIELDS

_now = CLOCK            # THE injectable monotonic clock (core/telemetry.py)


@dataclasses.dataclass
class SyncStats:
    snapshots: int = 0            # exports that refreshed the device image
    full_syncs: int = 0           # wholesale republishes
    delta_syncs: int = 0          # incremental scatters
    bytes_synced: int = 0         # host->device array traffic (both modes)
    pagetable_commands: int = 0   # accumulated PCIe page-table updates
    read_version_updates: int = 0  # accumulated PCIe read-version writes
    delta_rows: int = 0           # dirty node rows scattered (cumulative)
    delta_fraction: float = 0.0   # dirty fraction at the last sync
    log_entries: int = 0          # writes accepted (one log entry each)
    log_wire_bytes: int = 0       # append-only wire-format estimate
    #   (key+value+WIRE_ENTRY_OVERHEAD per write) — the paper's log-block
    #   byte accounting, alongside the dirty-row accounting above
    image_dma_count: int = 0      # node-image DMA invocations: the packed
    #   layout issues exactly ONE per dirty node (one per whole image on a
    #   full publish); legacy issues one per field per node — the counter
    #   the layout refactor exists to collapse
    image_bytes: int = 0          # node-image payload bytes (both layouts
    #   carry image_words * 4 per node; the DMA *count* is what differs)
    log_replays: int = 0          # follower stagings applied by replaying
    #   the epoch's op wire stream on device (log_replay_scatter) instead
    #   of re-issuing the primary's image-row DMAs — the log-shipped feed
    value_slots_synced: int = 0   # out-of-node value slots uploaded into
    #   the device value image (the whole image on a full value publish)
    value_bytes_synced: int = 0   # their bytes (also in bytes_synced)

    def merge(self, other: "SyncStats"):
        """Accumulate another shard's counters (router aggregation)."""
        for f in dataclasses.fields(self):
            if f.name == "delta_fraction":
                self.delta_fraction = max(self.delta_fraction,
                                          other.delta_fraction)
            else:
                setattr(self, f.name,
                        getattr(self, f.name) + getattr(other, f.name))

    def collect(self):
        """Registry samples (core/telemetry.py collect protocol):
        ``sync_*`` counters, ``sync_delta_fraction`` as a gauge."""
        return samples_from(self, "sync", "shard",
                            gauges=("delta_fraction",))


@dataclasses.dataclass
class StagedSync:
    """One ``begin_export`` staging as it crossed the "bus" — the artifact a
    follower replica replays (core/replica.py).

    ``kind`` is "full" or "delta"; ``delta`` carries the dirty-row +
    page-table scatter for delta stagings (None for full publishes) — a
    packed ``SnapshotDelta`` (one image row per dirty node) or a
    ``LegacySnapshotDelta`` (per-field blocks), matching ``cfg.layout``;
    ``snapshot`` is the staged standby itself, which doubles as the catch-up
    source for followers that fell out of sync; ``nbytes`` is the traffic
    this staging metered and ``delta_rows`` the unpadded dirty-row count, so
    per-replica feeding costs O(replicas x dirty_rows) can be accounted
    exactly; ``image_dmas``/``image_bytes`` are the staging's node-image
    DMA invocations and payload bytes (what each follower replay re-issues);
    ``read_version`` is what the standby answers at once flipped;
    ``values`` is a delta staging's value-image delta (None when the
    epoch wrote no value slot; a full staging's snapshot carries its whole
    value image).
    """
    kind: str
    snapshot: TreeSnapshot | LegacyTreeSnapshot
    delta: SnapshotDelta | LegacySnapshotDelta | None
    nbytes: int
    delta_rows: int
    read_version: int
    image_dmas: int = 0
    image_bytes: int = 0
    # the log-shipped feed unit: present iff the epoch was replayable (all
    # writes took the leaf fast path — no splits/GC/pt moves/overflow
    # values) and log capture is on.  None means followers must take the
    # image delta (the metered per-epoch fallback).
    log_payload: "LogPayload | None" = None
    values: ValueDelta | None = None


@dataclasses.dataclass
class LogPayload:
    """One sync epoch's writes, encoded ONCE for every follower lane.

    ``wire`` is the op stream in the exact core/api.py wire format
    (``len(wire)`` equals the epoch's ``SyncStats.log_wire_bytes`` growth —
    encoder and meter share ``wire_entry_nbytes``).  The sidecar vectors
    carry each write's fast-path placement — physical leaf row, log slot,
    backptr, order hint, version delta — which the primary derived from
    its pre-epoch tree state; shipping them (4 B x 5 per entry) spares
    every follower re-deriving placements from a host tree it does not
    have, and keeps replay a pure device scatter.  ``nbytes`` is what one
    follower edge actually moves: wire + sidecar."""
    wire: bytes
    rows: np.ndarray          # [E] int32 physical leaf slot per entry
    slots: np.ndarray         # [E] int32 log slot index per entry
    backptrs: np.ndarray      # [E] int32 sorted-block back pointers
    hints: np.ndarray         # [E] int32 log order hints
    vdeltas: np.ndarray       # [E] int64 version deltas (narrow on device)
    entries: int
    read_version: int
    wire_nbytes: int
    nbytes: int


class StoreShard:
    """One range-shard of the store: its own tree, resident device snapshot,
    incremental delta sync and SyncStats."""

    def __init__(self, cfg: HoneycombConfig | None = None,
                 heap_capacity: int = 1024, shard_id: int = 0):
        self.cfg = cfg or HoneycombConfig()
        self.shard_id = shard_id
        self.tree = HoneycombTree(self.cfg, heap_capacity)
        self.cache = InteriorCache(self.cfg)
        # Section 5: a page-table command for a LID invalidates that LID's
        # cache entry — every remap/free notifies the interior cache, so a
        # stale physical address can never serve from the metadata table
        self.tree.pt.on_remap = self.cache.invalidate
        self.sync_stats = SyncStats()
        self._snapshot: TreeSnapshot | None = None
        self._snapshot_dirty = True
        self._writes_since_sync = 0
        self._sync_deferred = False
        # counter watermarks so multi-sync runs accumulate (not overwrite)
        self._pt_commands_seen = 0
        self._rv_updates_seen = 0
        # array generations the resident snapshot was published against;
        # growth changes shapes and forces a full republish
        self._heap_gen = -1
        self._pt_gen = -1
        self._values_gen = -1        # overflow heap generation, likewise
        # read version the resident snapshot answers at; under "explicit"
        # an accelerator epoch pins it so GC keeps old buffers alive and
        # host fallbacks stay linearizable with the stale device image
        self._snapshot_rv: int | None = None
        self._snapshot_pin: tuple[int, int] | None = None
        # double-buffered snapshot: begin_export() stages the next epoch
        # into the standby buffer (async scatter); flip() publishes it
        self.epoch = 0                    # flips published so far
        self.pipeline_stats = PipelineStats()
        self._standby: TreeSnapshot | None = None
        self._standby_rv: int | None = None
        self._standby_pin: tuple[int, int] | None = None
        # replication hooks (core/replica.py): a ReplicaGroup wires these so
        # EVERY staging/flip — facade-driven, scheduler-driven, or a policy
        # auto-sync — feeds the follower replicas the same payload.  Unset
        # (the unreplicated store) they cost one None check per sync.
        # last_staged describes the CURRENTLY staged (unflipped) standby
        # only; flip() clears it.
        self.last_staged: StagedSync | None = None
        self.on_staged: Callable[[StagedSync], None] | None = None
        self.on_flip: Callable[[], None] | None = None
        self._staged_delta: SnapshotDelta | None = None
        self._staged_values: ValueDelta | None = None
        # log-shipped feed capture (core/replica.py sets log_capture when
        # followers ride the "log" feed; the unreplicated store pays one
        # bool check per write).  The epoch log holds (op, placement) per
        # write since the last staging; any write that missed the leaf
        # fast path — or carried an overflow-length value, or a GC pass —
        # poisons the epoch, and its staging falls back to the image delta.
        self.log_capture = False
        self._epoch_log: list = []
        self._epoch_replayable = True
        self._staged_pt_cmds = 0

    # ------------------------------------------------------------- writes
    def put(self, key: bytes, value: bytes, thread: int = 0):
        self.tree.put(key, value, thread)
        self._note_write(key, value, "put")

    def update(self, key: bytes, value: bytes, thread: int = 0):
        self.tree.update(key, value, thread)
        self._note_write(key, value, "update")

    def delete(self, key: bytes, thread: int = 0):
        self.tree.delete(key, thread)
        self._note_write(key, b"", "delete")

    def _note_write(self, key: bytes, value: bytes, kind: str = "put"):
        self._snapshot_dirty = True
        self._writes_since_sync += 1
        self.sync_stats.log_entries += 1
        # the op wire encoder's exact size (core/api.py) — the meter and
        # encode_wire() share one accounting and can never drift
        self.sync_stats.log_wire_bytes += wire_entry_nbytes(key, value)
        if self.log_capture:
            # capture BEFORE any policy auto-sync below, so the staging
            # that this very write triggers still carries it
            self._capture_op(key, value, kind)
        if (self.cfg.sync_policy == "every_k"
                and self._writes_since_sync >= self.cfg.sync_every_k
                and not self._sync_deferred):
            self.export_snapshot()

    def _capture_op(self, key: bytes, value: bytes, kind: str):
        """Append this write to the epoch log for the log-shipped feed.
        A write that missed the fast path (split/merge/underflow — the
        tree shape changed) or stored an overflow-length value (the
        overflow slot id is not derivable from the wire value) poisons
        the epoch: its staging ships the image delta instead."""
        placement = self.tree.last_placement
        if placement is None or len(value) > self.cfg.max_inline_val_bytes:
            self._epoch_replayable = False
            self._epoch_log.clear()
            return
        if self._epoch_replayable:
            op = Delete(key) if kind == "delete" \
                else OPS_BY_KIND[kind](key, value)
            self._epoch_log.append((op, placement))

    @contextlib.contextmanager
    def deferred_sync(self):
        """Suspend automatic policy syncs ("every_k") for a write burst the
        caller will close with ONE batched sync (scheduler.run)."""
        self._sync_deferred = True
        try:
            yield
        finally:
            self._sync_deferred = False

    # ---------------------------------------------------- host-side reads
    def get(self, key: bytes) -> bytes | None:
        return self.tree.get(key)

    def scan(self, lo: bytes, hi: bytes, max_items: int | None = None):
        return self.tree.scan(lo, hi, max_items)

    # ------------------------------------------------------------ routing
    @property
    def serving_version(self) -> int:
        """Read version of the active snapshot — what a device batch that
        just dispatched here answered at (0 before the first publish)."""
        return self._snapshot_rv if self._snapshot_rv is not None else 0

    def routing(self) -> Routing:
        """The single-shard wiring for the service/scheduler (core/api.py):
        everything routes to shard 0, no replica spreading, reads stamped
        with the active snapshot's read version."""
        return Routing(
            shard_of=lambda key: 0,
            replica_of=None,
            report=lambda shard: (0, self.serving_version),
            live_version=lambda shard: int(self.tree.versions.read_version()))

    # ------------------------------------------------- snapshot mechanics
    def begin_export(self, force: bool = False, full: bool = False) -> bool:
        """Stage the host->accelerator sync into the STANDBY buffer (the
        async half of the PCIe analogue).

        After the first wholesale publish, only dirty node rows + batched
        page-table commands + the read version cross the "bus"; ``full=True``
        forces a wholesale republish (benchmarks use it to meter the
        non-amortized traffic), ``force=True`` re-stages even when clean.
        The scatter is enqueued asynchronously; the ACTIVE snapshot keeps
        answering in-flight reads untouched until ``flip()`` publishes the
        standby.  Returns True when a standby was (re)staged."""
        if ((self._snapshot is not None or self._standby is not None)
                and not self._snapshot_dirty and not force and not full):
            return False   # clean, and some epoch (staged or active) exists
        t0 = _now()
        t = self.tree
        h = t.heap
        stats = self.sync_stats
        # accumulate command counters as deltas: multi-sync runs must report
        # total traffic, not the last sync's snapshot of the counters
        stats.pagetable_commands += t.pt.sync_commands - self._pt_commands_seen
        self._pt_commands_seen = t.pt.sync_commands
        stats.read_version_updates += (t.versions.device_updates
                                       - self._rv_updates_seen)
        self._rv_updates_seen = t.versions.device_updates
        stats.snapshots += 1

        # an unflipped standby accumulates further deltas; otherwise the
        # active snapshot is the scatter base
        base = self._standby if self._standby is not None else self._snapshot
        dirty = h.dirty
        frac = len(dirty) / h.capacity
        can_delta = (base is not None and not full
                     and self._heap_gen == h.generation
                     and self._pt_gen == t.pt.generation
                     and frac <= self.cfg.delta_full_threshold)
        # the interior-cache update rides along with the sync DMA: refresh
        # BEFORE publishing so the staged snapshot carries the epoch's cache
        # frontier (cache_lids) and its VMEM tier mirrors the standby
        with span("sync.refresh"):
            self.cache.refresh(t)
        bytes0 = stats.bytes_synced
        dmas0, ibytes0 = stats.image_dma_count, stats.image_bytes
        if can_delta:
            snap = self._publish_delta(base, dirty)
            stats.delta_syncs += 1
            stats.delta_rows += len(dirty)
            stats.delta_fraction = frac
            staged_kind, staged_rows = "delta", len(dirty)
        else:
            snap = self._publish_full()
            stats.full_syncs += 1
            stats.delta_fraction = 1.0
            staged_kind, staged_rows = "full", 0
        dirty.clear()
        self._heap_gen = h.generation
        self._pt_gen = t.pt.generation
        self._snapshot_dirty = False
        self._writes_since_sync = 0
        self._standby = snap
        # captured host-side (never block on the device scalar): the read
        # version the standby will answer at once flipped
        self._standby_rv = int(t.versions.read_version())
        if self.cfg.sync_policy == "explicit" and self._standby_pin is None:
            # pin an accelerator epoch NOW, while the staged read version is
            # current: garbage deferred from here on stays unreclaimed, so
            # after the flip host fallbacks can still walk version chains
            # back to the standby's read version even if writes landed in
            # the staging window; the pin rolls forward at the next flip
            self._standby_pin = t.epochs.accel_begin_batch(1)
        self.pipeline_stats.staged_exports += 1
        self.pipeline_stats.export_s += _now() - t0
        # replication feed: record what crossed the bus and let the replica
        # group replay it into every follower's standby (after the export
        # meters close, so follower staging never pollutes primary timings)
        self.last_staged = StagedSync(
            kind=staged_kind, snapshot=snap,
            delta=self._staged_delta if staged_kind == "delta" else None,
            nbytes=stats.bytes_synced - bytes0, delta_rows=staged_rows,
            read_version=self._standby_rv,
            image_dmas=stats.image_dma_count - dmas0,
            image_bytes=stats.image_bytes - ibytes0,
            log_payload=self._build_log_payload(staged_kind),
            values=self._staged_values)
        self._staged_delta = None
        self._staged_values = None
        # epoch boundary for the log-shipped feed: whatever happens next
        # belongs to the next staging
        self._epoch_log = []
        self._epoch_replayable = True
        san = _epochsan.get()
        if san is not None:   # tag the standby; audit the cache frontier
            san.note_staged(self, snap)
        if self.on_staged is not None:
            self.on_staged(self.last_staged)
        return True

    def _build_log_payload(self, staged_kind: str) -> LogPayload | None:
        """Encode the epoch's writes ONCE as the wire stream + placement
        sidecar every follower edge ships (the log-shipped feed unit).
        None — the per-epoch fallback — when capture is off, the staging
        was a full publish (bases regress/reshape), the epoch saw a
        non-fast-path write or GC, or page-table commands rode the delta
        (tree shape changed: a log replay could not reproduce them)."""
        if (not self.log_capture or staged_kind != "delta"
                or not self._epoch_replayable or self._staged_pt_cmds):
            return None
        log = self._epoch_log
        E = len(log)
        wire = b"".join(op.encode_wire() for op, _ in log)
        rows = np.fromiter((p[0] for _, p in log), np.int32, E)
        slots = np.fromiter((p[1] for _, p in log), np.int32, E)
        backptrs = np.fromiter((p[2] for _, p in log), np.int32, E)
        hints = np.fromiter((p[3] for _, p in log), np.int32, E)
        vdeltas = np.fromiter((p[4] for _, p in log), np.int64, E)
        sidecar = (rows.nbytes + slots.nbytes + backptrs.nbytes
                   + hints.nbytes + vdeltas.nbytes)
        return LogPayload(
            wire=wire, rows=rows, slots=slots, backptrs=backptrs,
            hints=hints, vdeltas=vdeltas, entries=E,
            read_version=self._standby_rv, wire_nbytes=len(wire),
            nbytes=len(wire) + sidecar)

    def flip(self) -> TreeSnapshot | None:
        """Publish the staged standby as the active snapshot — the atomic
        epoch advance of the double buffer.  Old-epoch snapshots are
        functional device copies, so batches already in flight finish at
        their pinned read version.  No-op when nothing is staged."""
        if self._standby is None:
            return self._snapshot
        self._snapshot = self._standby
        self._snapshot_rv = self._standby_rv
        self._standby = None
        self._standby_rv = None
        self.epoch += 1
        self.pipeline_stats.flips += 1
        old_pin = self._snapshot_pin
        self._snapshot_pin = self._standby_pin
        self._standby_pin = None
        if old_pin is not None:
            self.tree.epochs.accel_complete_batch(*old_pin)
        san = _epochsan.get()
        if san is not None:               # retag the published snapshot
            san.note_flip(self, self._snapshot)
        if self.on_flip is not None:      # replica group: flip the followers
            self.on_flip()
        # the payload only describes the (now published) standby; followers
        # consumed it at staging time — drop it so the delta's device
        # arrays don't outlive the sync on a quiescent store
        self.last_staged = None
        return self._snapshot

    def export_snapshot(self, force: bool = False,
                        full: bool = False) -> TreeSnapshot:
        """Host -> accelerator sync (the PCIe analogue): stage + publish in
        one step — ``begin_export()`` then ``flip()``.  Identical, including
        sync byte counts, to the pre-double-buffer serial behavior."""
        self.begin_export(force=force, full=full)
        return self.flip()   # no-op returning the active snapshot if clean

    def _publish_full(self):
        """Wholesale republish: the whole store crosses the bus — ONE
        contiguous [S, image_words] image DMA on the packed layout, one
        array per field on legacy (same bytes, ~24x the DMA invocations).
        Spans ``hc.sync.pack`` (host images), ``hc.sync.put`` (uploads),
        ``hc.sync.launch`` (the cache-tier program)."""
        t = self.tree
        h = t.heap
        stats = self.sync_stats
        layout = NodeImageLayout.for_config(self.cfg)
        packed = self.cfg.layout == "packed"
        with span("sync.pack"):
            pt_image = t.pt.flush_to_device()
            # pack() marshals every field into contiguous node images —
            # the whole publish is one image transfer (plus the page table)
            img = layout.pack(h) if packed else None
        stats.image_bytes += h.capacity * layout.node_image_bytes

        def dev(a, dtype=None):
            # ALWAYS copy: jnp.asarray is typically zero-copy on the CPU
            # backend, and an aliased snapshot would see in-place host
            # mutations (log appends, GC wipes) — the snapshot must be the
            # immutable device image the paper's DMA produces
            arr = np.asarray(a)
            arr = arr.astype(dtype) if dtype is not None else arr.copy()
            stats.bytes_synced += arr.nbytes
            return jnp.asarray(arr)

        values, _ = self._stage_values(None)
        if packed:
            stats.bytes_synced += h.capacity * layout.node_image_bytes
            stats.image_dma_count += 1
            with span("sync.put"):
                snap = TreeSnapshot(
                    image=jnp.asarray(img),
                    pagetable=dev(pt_image),
                    root_lid=jnp.int32(t.root_lid),
                    read_version=jnp.int32(t.versions.read_version()),
                    cache_lids=jnp.asarray(self.cache.device_lids()))
            # materialize the VMEM cache tier device-side from the image
            # just shipped — only the ~KB LID vector crossed the bus
            with span("sync.launch"):
                return attach_cache_image(snap, self.cfg)._replace(
                    values=values)
        stats.image_dma_count += len(NODE_FIELDS)
        with span("sync.put"):
            fields = {f: dev(getattr(h, f),
                             np.int32 if f in _I32_FIELDS else None)
                      for f in NODE_FIELDS}
            return LegacyTreeSnapshot(
                pagetable=dev(pt_image),
                root_lid=jnp.int32(t.root_lid),
                read_version=jnp.int32(t.versions.read_version()),
                values=values, **fields)

    def _stage_values(self, base: jax.Array | None):
        """The value image a staged snapshot carries over ``base`` (the
        scatter base's image; None on a full publish), and the
        ``ValueDelta`` that made it, for followers.

        Nothing moves while the store has never held an out-of-node value,
        nor when no slot was allocated since the last sync.  The whole
        image moves on a full publish, on the first value publish and
        after the host heap grew (a generation change); otherwise only
        the new slots, padded to a power-of-two bucket with idempotent
        repeats.  Span ``hc.sync.values``."""
        ovf = self.tree.overflow
        if not ovf.allocs:
            return None, None
        whole = base is None or self._values_gen != ovf.generation
        if not whole and not ovf.fresh:
            return base, None
        with span("sync.values"):
            if whole:
                n = len(ovf.lens)
                # a copy: the CPU backend's asarray would alias the heap
                vd = ValueDelta(slots=None, rows=jnp.asarray(ovf.vals.copy()))
            else:
                n = len(ovf.fresh)
                slots = self._pad_index(
                    np.fromiter(sorted(ovf.fresh), np.int32, n),
                    bucket_pow2(n))
                vd = ValueDelta(slots=jnp.asarray(slots),
                                rows=jnp.asarray(ovf.vals[slots]))
            values = apply_value_delta(base, vd)
        stats = self.sync_stats
        stats.value_slots_synced += n
        stats.value_bytes_synced += n * ovf.slot_bytes
        stats.bytes_synced += n * ovf.slot_bytes
        ovf.fresh.clear()
        self._values_gen = ovf.generation
        return values, vd

    def _publish_delta(self, base, dirty: set[int]):
        """Incremental sync: scatter the ``dirty`` node rows and pending
        page-table commands over ``base`` (the standby-in-progress, or the
        active snapshot when none is staged).  Transfers (and meters)
        O(dirty) bytes instead of O(store); the host-side gathers below
        copy out of the heap eagerly, so later host mutations/GC wipes can
        never reach a staged standby.

        Packed layout: each dirty node is marshalled into ONE contiguous
        image row and issued as a single DMA (``image_dma_count`` grows by
        exactly len(dirty) — the acceptance invariant); legacy ships the
        same bytes as one row block per field (~24 DMAs per node).  Spans
        ``hc.sync.pack`` (sort, padding, host images), ``hc.sync.put``
        (uploads), ``hc.sync.launch`` (the scatter program)."""
        t = self.tree
        h = t.heap
        stats = self.sync_stats
        layout = NodeImageLayout.for_config(self.cfg)
        packed = self.cfg.layout == "packed"
        with span("sync.pack"):
            rows = np.fromiter(sorted(dirty), np.int32, len(dirty))
            pt_lids, pt_phys = t.pt.take_pending()
            # pending LID moves mean the tree shape changed under this
            # epoch — a log replay cannot reproduce them, so the feed must
            # fall back
            self._staged_pt_cmds = len(pt_lids)
            # pad to bucketed sizes with idempotent repeats (duplicate
            # indices carry identical data); when empty, row/lid 0 rewrites
            # itself with its current contents (clean rows match the device
            # image)
            rows_p = self._pad_index(rows, bucket_pow2(len(rows)))
            lids_p = self._pad_index(pt_lids, bucket_pow2(len(pt_lids)))
            phys_p = t.pt.device_image[lids_p]
            if packed:
                img = layout.pack(h, rows_p)
                cache_lids = self.cache.device_lids()
            else:
                host_fields = {}
                for f in NODE_FIELDS:
                    arr = getattr(h, f)[rows_p]
                    if f in _I32_FIELDS:
                        arr = arr.astype(np.int32)
                    host_fields[f] = arr
        # both layouts move image_words * 4 bytes per UNPADDED dirty node
        # (every device field is one u32 word per element); the accounting
        # is identical by construction — only the DMA count differs
        node_bytes = len(rows) * layout.node_image_bytes
        nbytes = pt_lids.nbytes + pt_phys.nbytes + node_bytes
        stats.image_bytes += node_bytes
        with span("sync.put"):
            if packed:
                stats.image_dma_count += len(rows)   # ONE DMA per dirty node
                delta = SnapshotDelta(
                    rows=jnp.asarray(rows_p), image=jnp.asarray(img),
                    pt_lids=jnp.asarray(lids_p), pt_phys=jnp.asarray(phys_p),
                    root_lid=jnp.int32(t.root_lid),
                    read_version=jnp.int32(t.versions.read_version()),
                    cache_lids=jnp.asarray(cache_lids))
            else:
                stats.image_dma_count += len(rows) * len(NODE_FIELDS)
                delta = LegacySnapshotDelta(
                    rows=jnp.asarray(rows_p),
                    pt_lids=jnp.asarray(lids_p), pt_phys=jnp.asarray(phys_p),
                    root_lid=jnp.int32(t.root_lid),
                    read_version=jnp.int32(t.versions.read_version()),
                    **{f: jnp.asarray(a) for f, a in host_fields.items()})
        stats.bytes_synced += nbytes
        self._staged_delta = delta   # replayable by follower replicas
        values, self._staged_values = self._stage_values(base.values)
        with span("sync.launch"):
            nxt = _jit_apply_delta(base._replace(values=None), delta,
                                   backend=sync_backend(), cfg=self.cfg)
        return nxt._replace(values=values)

    @staticmethod
    def _pad_index(idx: np.ndarray, size: int) -> np.ndarray:
        idx = np.asarray(idx, np.int32)
        if len(idx) == 0:
            return np.zeros(size, np.int32)
        return np.concatenate(
            [idx, np.full(size - len(idx), idx[-1], np.int32)])

    # ------------------------------------------------- accelerated reads
    def _read_backend_for(self, snap) -> str:
        """Effective backend for one device dispatch.  The fused megakernel
        path needs a packed snapshot with the cache tier attached; legacy
        layouts, cache-less snapshots (e.g. a delta applied without cfg) and
        ``cfg.read_backend="reference"`` all serve through the staged jnp
        reference path."""
        if (self.cfg.read_backend == "fused"
                and isinstance(snap, TreeSnapshot)
                and snap.cache_lids is not None
                and snap.cache_image is not None):
            return "fused"
        return "reference"

    def _fetch_read(self, packed: jax.Array, result_type, lanes: int,
                    values: bool):
        """A batch's one device->host copy (``_pack_read``'s buffer, with
        ``gather_values``' rows appended when ``values``; inside the
        caller's ``hc.read.fetch`` span): the result as zero-copy views of
        it, and the value rows as a [lanes, positions, overflow_words]
        view (None without values); the meters are folded into
        CacheStats."""
        buf = np.asarray(packed)
        rows = None
        if values:
            n = lanes * _field_offsets(result_type, self.cfg)[1] + 3
            buf, rows = buf[:n], buf[n:].reshape(
                lanes, _value_positions(result_type, self.cfg),
                self.cfg.overflow_words)
        res, meters = _unpack_read(buf, result_type, self.cfg)
        ps = self.pipeline_stats
        ps.read_batches += 1
        ps.read_copies += 1
        self._note_read_meters(meters)
        return res, rows

    def _launch_read(self, jit_read, snap, args, result_type, **kw):
        """Launch one read program on ``snap``'s node image and, for a
        snapshot with a value image, ``gather_values`` after it (span
        ``hc.read.gather``).  Returns the packed buffer and whether it
        carries value rows."""
        values = snap.values
        with span("read.launch"):
            packed = jit_read(snap._replace(values=None), *args,
                              cfg=self.cfg, **kw)
        if values is None:
            return packed, False
        with span("read.gather"):
            return _jit_gather_values(packed, values, result_type=result_type,
                                      cfg=self.cfg), True

    def _note_read_meters(self, meters: np.ndarray):
        """Fold one batch's device meters, views of its one packed copy,
        into CacheStats (the dispatching shard accounts follower-served
        batches too; the reference path's meters are zeros)."""
        s = self.cache.stats
        s.vmem_hits += int(meters[0])
        s.heap_gathers += int(meters[1])
        s.lb_routed += int(meters[2])

    def _snapshot_for_read(self) -> TreeSnapshot:
        """The snapshot device batches execute against.  "explicit" policy
        reads the resident (possibly stale, always consistent) snapshot;
        the other policies sync lazily here."""
        if self.cfg.sync_policy == "explicit" and self._snapshot is not None:
            return self._snapshot
        return self.export_snapshot()

    def _fallback_read_version(self) -> int | None:
        """Read version for host fallbacks of device requests.  Under
        "explicit" the device image may be stale: fall back at the
        SNAPSHOT's read version (the epoch pin keeps those buffers alive),
        never the live tree — otherwise a truncated SCAN could observe
        writes the rest of its batch cannot (a linearizability hole)."""
        if self.cfg.sync_policy == "explicit" and self._snapshot_rv is not None:
            return self._snapshot_rv
        return None   # snapshot was just exported: live == snapshot version

    def get_batch(self, keys: Sequence[bytes]) -> list[bytes | None]:
        """Batched GET on the accelerator path, epoch-stamped."""
        keys = list(keys)
        if not keys:
            return []
        return self._device_get(self._snapshot_for_read(), keys)

    def _device_get(self, snap: TreeSnapshot,
                    keys: list[bytes]) -> list[bytes | None]:
        """Execute one dense GET batch against ``snap`` — the active
        snapshot, or a follower replica's device image (core/replica.py
        serves followers through the primary's dispatch machinery).
        Spans ``hc.read.pack`` / ``launch`` / ``fetch`` / ``decode``."""
        san = _epochsan.get()
        if san is not None:   # reads may never see an unflipped standby
            san.check_read(self, snap)
        ps = self.pipeline_stats
        with span("read.pack", ps, "pack_s"):
            # pad ragged batches (router sub-batches) to power-of-two
            # buckets so each (cfg, shapes) compiles once per bucket, not
            # per length
            padded = keys + [keys[0]] * (bucket_pow2(len(keys)) - len(keys))
            ps.dispatched_lanes += len(keys)
            ps.padded_lanes += len(padded)
            lanes, lens = pack_keys(padded, self.cfg.key_words)
            lanes, lens = jnp.asarray(lanes), jnp.asarray(lens)
        rb = self._read_backend_for(snap)
        kernel_ops.record_read_dispatch("get", rb, self.cfg)
        lo, hi = self.tree.epochs.accel_begin_batch(len(keys))
        try:
            if rb == "fused":
                packed, gathered = self._launch_read(
                    _jit_get_fused, snap, (lanes, lens), GetResult,
                    lb_fraction=self.cfg.lb_fraction)
            else:
                packed, gathered = self._launch_read(
                    _jit_get, snap, (lanes, lens), GetResult)
            with span("read.fetch", ps, "fetch_s"):
                (found, vals, vlens), rows = self._fetch_read(
                    packed, GetResult, len(padded), gathered)
        finally:
            self.tree.epochs.accel_complete_batch(lo, hi)
        with span("read.decode", ps, "decode_s"):
            return [self._decode_value(vals[i], int(vlens[i]), rows, (i, 0))
                    if found[i] else None for i in range(len(keys))]

    def scan_batch(self, ranges: Sequence[tuple[bytes, bytes]]
                   ) -> list[list[tuple[bytes, bytes]]]:
        """Batched SCAN on the accelerator path.  Requests the device path
        could not complete (leaf budget/slots) fall back to the host — the
        paper likewise executes some SCANs on CPU cores (Section 6.3).
        Fallbacks run at the snapshot's read version (see
        ``_fallback_read_version``)."""
        ranges = list(ranges)
        if not ranges:
            return []
        snap = self._snapshot_for_read()
        return self._device_scan(snap, ranges, self._fallback_read_version())

    def _device_scan(self, snap: TreeSnapshot,
                     ranges: list[tuple[bytes, bytes]],
                     fallback_rv: int | None
                     ) -> list[list[tuple[bytes, bytes]]]:
        """Execute one dense SCAN batch against ``snap`` (active snapshot or
        a follower replica's image); truncated requests fall back to the
        host tree at ``fallback_rv``.  Spans ``hc.read.pack`` / ``launch``
        / ``fetch`` / ``decode``, and ``hc.read.host_scan`` around the
        batch's fallbacks."""
        san = _epochsan.get()
        if san is not None:   # reads may never see an unflipped standby
            san.check_read(self, snap)
        ps = self.pipeline_stats
        with span("read.pack", ps, "pack_s"):
            pad = [ranges[0]] * (bucket_pow2(len(ranges)) - len(ranges))
            padded = ranges + pad
            ps.dispatched_lanes += len(ranges)
            ps.padded_lanes += len(padded)
            lo_l, lo_n = pack_keys([r[0] for r in padded], self.cfg.key_words)
            hi_l, hi_n = pack_keys([r[1] for r in padded], self.cfg.key_words)
            args = (jnp.asarray(lo_l), jnp.asarray(lo_n),
                    jnp.asarray(hi_l), jnp.asarray(hi_n))
        rb = self._read_backend_for(snap)
        kernel_ops.record_read_dispatch("scan", rb, self.cfg)
        slo, shi = self.tree.epochs.accel_begin_batch(len(ranges))
        try:
            if rb == "fused":
                packed, gathered = self._launch_read(
                    _jit_scan_fused, snap, args, ScanResult,
                    lb_fraction=self.cfg.lb_fraction)
            else:
                packed, gathered = self._launch_read(
                    _jit_scan, snap, args, ScanResult)
            with span("read.fetch", ps, "fetch_s"):
                (count, keys, klens, vals, vlens, trunc), rows = \
                    self._fetch_read(packed, ScanResult, len(padded),
                                     gathered)
        finally:
            self.tree.epochs.accel_complete_batch(slo, shi)
        out: list = [None] * len(ranges)
        fallbacks = []
        with span("read.decode", ps, "decode_s"):
            for b in range(len(ranges)):
                if trunc[b]:
                    fallbacks.append(b)
                    continue
                items = []
                for j in range(int(count[b])):
                    k = keys[b, j].astype(">u4").tobytes()[: int(klens[b, j])]
                    items.append((k, self._decode_value(
                        vals[b, j], int(vlens[b, j]), rows, (b, j))))
                out[b] = items
        if fallbacks:
            with span("read.host_scan"):
                for b in fallbacks:
                    lo, hi = ranges[b]
                    out[b] = self.tree.scan(lo, hi, read_version=fallback_rv)
            ps.host_scans += len(fallbacks)
        return out

    def _decode_value(self, lanes: np.ndarray, length: int,
                      rows: np.ndarray | None, at: tuple[int, int]) -> bytes:
        """One answer's value: inline from its lanes, or out of node from
        the batch's gathered value rows (``rows[at]``).  Every snapshot
        that holds a long value carries the value image; the live host
        heap is never read here, since its slot may have been reused
        since the snapshot's epoch."""
        if length <= self.cfg.max_inline_val_bytes:
            return lanes.astype(">u4").tobytes()[:length]
        if rows is None:
            raise RuntimeError(
                f"a {length} B value was read from a snapshot without a "
                "value image")
        self.pipeline_stats.device_values += 1
        return rows[at].view(np.uint8)[:length].tobytes()

    # ------------------------------------------------------------- misc
    def collect_garbage(self) -> int:
        san = _epochsan.get()
        # audit the collect against the PRE-collect epoch window: nothing
        # a pinned accelerator/CPU epoch still covers may be reclaimed
        guard = san.gc_begin(self) if san is not None else None
        n = self.tree.gc.collect()
        if guard is not None:
            san.gc_end(self, guard)
        if n:
            # GC wipes freed slots (marking them dirty) and queues LID
            # frees — row mutations no wire entry describes, so the
            # epoch's staging must ship the image delta
            self._epoch_replayable = False
            self._epoch_log.clear()
        return n

    @property
    def stats(self):
        return self.tree.stats

    @property
    def cache_stats(self):
        """The interior cache's meters (Section 5 metadata-table probes
        plus the fused read path's vmem/heap split) — named so the facade
        family shares one accessor (telemetry wiring, router aggregation;
        a ``ReplicaGroup`` reaches it through the primary fallthrough)."""
        return self.cache.stats
