"""Replication subsystem — follower shards fed by the primary's deltas.

Honeycomb's export path already produces exactly the artifact a replica
needs: a resident device snapshot plus an incremental delta stream (paper
Sections 3-4).  Reads scale with accelerator lanes while writes stay on the
CPU (Sections 3.4/5), so for the read-dominated workloads the paper targets
the natural next scaling axis is to serve each range-shard from MORE THAN
ONE device image and spread read batches over them — the same offload shape
"Reliable Replication Protocols on SmartNICs" (Katebzadeh et al.) puts on
the NIC data path, and exactly where F2 (Kanellis et al.) shows skewed
read-heavy workloads win.

Design
======

**FollowerReplica** — a device-resident copy of the primary's snapshot with
its own buffers (its own device lane): an active image, a standby image,
its own ``SyncStats`` and an epoch/read-version watermark.  A follower has
NO tree of its own — it is fed exclusively by the primary's staged sync
payloads (``StagedSync``, core/shard.py):

  * under the LOG feed (``ReplicationConfig.feed="log"``, the default) a
    replayable delta epoch ships its ``LogPayload`` — the epoch's writes
    wire-encoded ONCE by the core/api.py codec plus a 24 B/entry placement
    sidecar — and the follower applies it with the ``log_replay_scatter``
    Pallas kernel: each entry's ~(key_words + val_words + 6) words scatter
    into the follower's packed image at static ``NodeImageLayout``
    offsets.  Per follower the feed costs O(log_wire_bytes), typically
    tens of bytes per write, instead of re-issuing the primary's
    5 KB-per-dirty-node image-row DMAs — the same slow-bus argument that
    drives Honeycomb's own batching, applied to the replication fan-out;
  * an epoch whose tree shape changed (split/root growth/GC moves/pending
    page-table commands, or an overflow-length value) has NO wire-replay
    representation, so it falls back per-epoch to the image-row delta,
    with the epoch's new out-of-node value slots (``StagedSync.values``)
    scattered into the follower's own value image —
    metered as ``FeedStats.log_fallback_epochs`` so benchmarks report the
    fallback fraction.  ``feed="delta"`` pins every epoch to the image
    delta (the pre-log feed, kept as the byte-accounting reference);
  * a "full" payload (first export, heap growth, dirty fraction over the
    delta threshold) device-copies the primary's staged standby;
  * a follower that missed a payload (paused, attached late, or cut off
    behind a paused relay) is OUT OF SYNC: neither deltas nor log replays
    apply to its base, so it catches up with a full copy at the next
    reachable staging (or ``resync_follower``), and until then its
    published read version lags and the router never serves it.

**Relay tree** (``FeedTopology(fanout, depth)``, core/config.py) — with
``depth >= 1`` the one encoded payload routes primary -> up to ``fanout``
relays -> their children instead of primary -> everyone: each follower
receives its bytes from ``topology.parents()``'s parent edge, so the
feeder's egress (``FeedStats.primary_egress_bytes``) is O(fanout) while
downstream edges are metered as ``relay_hop_bytes``.  Relays are ordinary
followers that forward the payload they received; a PAUSED relay cuts off
its whole subtree (descendants miss the payload, go out of sync, and are
routed around by the freshness rule until a live path lets them take a
full catch-up).  ``depth=0`` is the flat O(replicas)-egress feed.

**ReplicaGroup** — one primary ``StoreShard`` plus N-1 followers behind the
shard facade (attribute access falls through to the primary, so a group is
drop-in wherever a shard was).  The group wires the primary's ``on_staged``
/ ``on_flip`` hooks, so a replication round is exactly the epoch pipeline's
sync: ``begin_export`` encodes the epoch once and stages the SAME payload
into every reachable follower's standby (each replay an independently
enqueued device op), and ``flip`` publishes the whole group — whichever
path triggered it (facade export, scheduler stage_export, or an "every_k"
policy auto-sync).  ``FeedStats`` meters the whole transport: feed bytes
by edge class, epochs by feed kind, and catch-up traffic.

**Freshness rule (no stale reads).**  Writes always go to the primary.  A
dispatched read batch is pinned to a replica whose published read version
covers the version the group currently serves (the primary's active
snapshot read version — the scheduler's admitted read version after
stage_export).  A lagging follower is SKIPPED — the batch silently serves
from the primary instead (metered as ``lagging_skips``) — so spread reads
are indistinguishable from primary reads: linearizable, never stale.
``replica_lag_epochs`` / ``replica_staleness`` meter each follower's epoch
and read-version lag.

**Equivalence invariant (mirroring PR 2's shards=1 and PR 3's serial
mode).**  ``replicas=1, policy="primary_only"`` is operation-for-operation
identical to the unreplicated store, including sync byte counts: the
follower list is empty, the hooks are no-ops, and every read delegates
straight to the primary (enforced by tests/test_replica.py).

The read-spreading POLICY (round_robin / least_loaded / primary_only)
lives in the router (core/router.py, ``replica_for_dispatch``); the group
only enforces freshness and executes the batch against the chosen image
through the primary's dispatch machinery (``_device_get``/``_device_scan``
— key packing, pow2 bucket padding, GC epoch pins, value decode).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .api import Routing, decode_wire_stream
from ..analysis import epochsan as _epochsan
from .config import HoneycombConfig, ReplicationConfig, bucket_pow2
from .heap import LOG_DELETE, LOG_INSERT, LOG_UPDATE
from .read_path import TreeSnapshot, attach_cache_image
from .schema import NodeImageLayout
from .shard import (LogPayload, StagedSync, StoreShard, SyncStats,
                    _jit_apply_delta, apply_value_delta, sync_backend)
from .telemetry import CLOCK, merge_stats, samples_from

_now = CLOCK            # THE injectable monotonic clock (core/telemetry.py)

# wire op kind -> heap log op code (the decode half of the feed)
_LOG_CODES = {"put": LOG_INSERT, "update": LOG_UPDATE, "delete": LOG_DELETE}


@functools.partial(jax.jit, static_argnames=("offs", "backend"))
def _jit_log_replay(image, rows, slots, entries, offs, backend):
    from repro.kernels import ops as kernel_ops
    return kernel_ops.log_replay_scatter(image, rows, slots, entries,
                                         offs=offs, backend=backend)


# rebuild a follower's VMEM cache tier from its own replayed image (the
# log feed ships no cache rows — replayable epochs preserve the tree
# shape, so the base's cache_lids frontier stays valid and only the row
# CONTENTS must be re-gathered)
_jit_attach_cache = jax.jit(attach_cache_image, static_argnames="cfg")


@dataclasses.dataclass
class FeedStats:
    """Transport meters of one ReplicaGroup's replication feed (summed
    across shards by ``router.aggregate_stats``).  Byte counters meter
    EDGES (one increment per follower delivery); epoch counters meter
    STAGINGS (one increment per ``begin_export`` that fed followers)."""
    feed_bytes: int = 0           # total bytes over all feed edges
    wire_bytes: int = 0           # exact op wire stream bytes shipped
    log_bytes: int = 0            # edge bytes of log-replay deliveries
    fallback_bytes: int = 0       # edge bytes of image deltas shipped on
    #   fallback epochs (log feed only; the fallback-fraction numerator)
    primary_egress_bytes: int = 0  # bytes on primary->child edges — the
    #   feeder bandwidth the relay tree bounds at O(fanout)
    relay_hop_bytes: int = 0      # bytes on relay->child edges
    log_feed_epochs: int = 0      # stagings shipped as a log payload
    log_fallback_epochs: int = 0  # log-feed stagings that had to ship the
    #   image delta (tree shape changed / GC / overflow value)
    delta_feed_epochs: int = 0    # stagings shipped as deltas by choice
    #   (feed="delta")
    full_feed_epochs: int = 0     # full-publish stagings
    full_catchups: int = 0        # out-of-sync followers refed a full copy
    catchup_bytes: int = 0        # bytes those full catch-ups moved

    def collect(self):
        """Registry samples (core/telemetry.py collect protocol):
        ``replication_*`` counters for every feed-transport meter."""
        return samples_from(self, "replication", "replica")


def _snapshot_nbytes(snap) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(snap))


def _image_feed_bytes(snap: TreeSnapshot, cfg) -> int:
    """Node-image payload bytes of device-copying a whole snapshot into a
    follower, which is ONE contiguous image DMA (core/schema.py)."""
    layout = NodeImageLayout.for_config(cfg)
    return snap.image.shape[0] * layout.node_image_bytes


class FollowerReplica:
    """One follower's device-resident state: its own active/standby snapshot
    buffers, SyncStats, and epoch/read-version watermark.  Fed only by the
    primary's ``StagedSync`` payloads; never written directly."""

    def __init__(self, replica_id: int, cfg: HoneycombConfig,
                 in_sync: bool = True):
        self.replica_id = replica_id
        self.cfg = cfg                 # layout schema for cache re-attach
        self.sync_stats = SyncStats()
        self.epoch = 0                 # primary epoch at our last publish
        self.paused = False            # fault injection / maintenance
        # True iff our scatter base equals the primary's scatter base, i.e.
        # we applied every payload since the last full copy — only then may
        # a delta payload be replayed here
        self.in_sync = in_sync
        self.snapshot: TreeSnapshot | None = None
        self.snapshot_rv: int | None = None
        self._standby: TreeSnapshot | None = None
        self._standby_rv: int | None = None
        self.served_ops = 0

    def stage(self, payload: StagedSync) -> tuple[int, bool]:
        """Replay one primary staging into our standby buffer: re-apply the
        delta scatter on our own base when in sync, otherwise device-copy
        the primary's staged standby (full catch-up).  Returns the bytes
        this delivery moved over our feed edge and whether it was full."""
        base = self._standby if self._standby is not None else self.snapshot
        stats = self.sync_stats
        stats.snapshots += 1
        if payload.kind == "delta" and self.in_sync and base is not None:
            # independent device scatter per replica: O(dirty_rows) traffic
            # (one image-row DMA per dirty node), plus the epoch's new value
            # slots into our own value image
            self._standby = _jit_apply_delta(
                base._replace(values=None), payload.delta,
                backend=sync_backend(), cfg=self.cfg)._replace(
                    values=self._next_values(base.values, payload.values))
            stats.delta_syncs += 1
            stats.delta_rows += payload.delta_rows
            stats.bytes_synced += payload.nbytes
            stats.image_dma_count += payload.image_dmas
            stats.image_bytes += payload.image_bytes
            nbytes, was_full = payload.nbytes, False
        else:
            # full feed: first publish, primary full republish, or catch-up
            # after a missed payload (a delta would land on the wrong base)
            self._standby = jax.tree.map(jnp.copy, payload.snapshot)
            stats.full_syncs += 1
            nbytes = (payload.nbytes if payload.kind == "full"
                      else _snapshot_nbytes(payload.snapshot))
            stats.bytes_synced += nbytes
            stats.image_dma_count += 1
            stats.image_bytes += _image_feed_bytes(payload.snapshot, self.cfg)
            self.in_sync = True
            was_full = True
        self._standby_rv = payload.read_version
        san = _epochsan.get()
        if san is not None:
            san.note_staged(self, self._standby)
        return nbytes, was_full

    @staticmethod
    def _next_values(values, vd):
        """Our value image after a delta staging's ``ValueDelta``: the
        epoch's new slots scattered into our own image, or a copy of the
        primary's whole image (the value image's first publish or a heap
        growth), so that no image is shared between replicas."""
        if vd is None:
            return values
        if vd.slots is None:
            return jnp.copy(vd.rows)
        return apply_value_delta(values, vd)

    def stage_log(self, payload: StagedSync, marshalled) -> int:
        """Replay one staging from its LOG payload: scatter the epoch's
        marshalled wire entries into our own standby image with the
        ``log_replay_scatter`` kernel — O(entry words) device traffic, no
        image-row DMAs (the feed's whole point; ``image_dma_count`` and
        ``image_bytes`` do NOT move).  By induction our base image equals
        the primary's scatter base, so the replayed standby is
        bit-identical to the primary's staged standby (tested).  Only
        callable in sync with an existing base; returns edge bytes."""
        lp = payload.log_payload
        base = self._standby if self._standby is not None else self.snapshot
        stats = self.sync_stats
        stats.snapshots += 1
        if marshalled is None:           # forced epoch with zero writes:
            image = base.image           # only the read version advances
        else:
            rows, slots, entries, offs = marshalled
            image = _jit_log_replay(base.image, rows, slots, entries, offs,
                                    sync_backend())
        # a replayable epoch wrote no value slot: the value image stays
        snap = base._replace(
            image=image, read_version=jnp.int32(lp.read_version), values=None)
        snap = _jit_attach_cache(snap, cfg=self.cfg)._replace(
            values=base.values)
        self._standby = snap
        self._standby_rv = payload.read_version
        stats.log_replays += 1
        stats.log_entries += lp.entries
        stats.log_wire_bytes += lp.wire_nbytes
        stats.bytes_synced += lp.nbytes
        san = _epochsan.get()
        if san is not None:
            san.note_staged(self, self._standby)
        return lp.nbytes

    def flip(self, primary_epoch: int) -> bool:
        """Publish the staged standby; no-op when nothing is staged (the
        follower keeps lagging and the router keeps skipping it)."""
        if self._standby is None:
            return False
        self.snapshot = self._standby
        self.snapshot_rv = self._standby_rv
        self._standby = None
        self._standby_rv = None
        self.epoch = primary_epoch
        san = _epochsan.get()
        if san is not None:
            san.note_flip(self, self.snapshot)
        return True


class ReplicaGroup:
    """One primary ``StoreShard`` plus N-1 ``FollowerReplica``s behind the
    shard facade.  Writes and host reads hit the primary (attribute
    fallthrough); device read batches can be pinned to any FRESH replica;
    every sync staging/flip feeds the whole group."""

    def __init__(self, primary: StoreShard,
                 replication: ReplicationConfig | None = None):
        self.primary = primary
        self.replication = replication or ReplicationConfig()
        fresh = (primary._snapshot is None and primary._standby is None)
        self.followers = [FollowerReplica(i + 1, primary.cfg, in_sync=fresh)
                          for i in range(self.replication.replicas - 1)]
        self.lagging_skips = 0         # batches redirected off a stale follower
        self.replication_s = 0.0       # wall time spent feeding followers
        self.feed_stats = FeedStats()
        # relay tree: follower id -> feeding parent id (0 = primary); ids
        # ascend level by level, so walking followers in order always
        # visits a parent before its children
        self._parents = self.replication.topology.parents(len(self.followers))
        # capture costs the unreplicated store nothing: the flag stays
        # False with no followers
        self._log_enabled = (self.replication.feed == "log"
                             and bool(self.followers))
        primary.log_capture = self._log_enabled
        self._primary_served = 0       # device requests the primary served
        # read-spreading policy state (the pick lives HERE; the router
        # delegates): round_robin cursor, and least_loaded's pick-time
        # assignment counts so submit-time bursts still spread
        self._rr = 0
        self._assigned = [0] * self.replication.replicas
        # (replica_served, serving_version) of the latest device batch —
        # the stamp the scheduler reads right after each dispatch
        self.last_dispatch: tuple[int, int] = (0, 0)
        primary.on_staged = self._on_primary_staged
        primary.on_flip = self._on_primary_flip
        if not fresh and self.followers and primary._snapshot is not None:
            for f in self.followers:   # late attach: full-copy the active
                f.stage(StagedSync("full", primary._snapshot, None,
                                   _snapshot_nbytes(primary._snapshot), 0,
                                   primary._snapshot_rv))
                f.flip(primary.epoch)
                f.in_sync = primary._standby is None

    def __getattr__(self, name: str):
        # facade fallthrough: anything not replica-specific is the primary's
        # (put/get/scan/deferred_sync/export_snapshot/sync_stats/tree/...)
        if name == "primary" or name.startswith("__"):
            raise AttributeError(name)
        return getattr(self.primary, name)

    @property
    def n_replicas(self) -> int:
        return 1 + len(self.followers)

    # --------------------------------------------------- replication feed
    def _marshal_log_payload(self, lp: LogPayload):
        """Decode the one encoded wire stream and marshal it into the
        dense device block ``log_replay_scatter`` consumes — ONCE per
        staging, shared by every follower lane (each lane still runs its
        own independently enqueued replay).  Entries pad to the shared
        pow2 bucket schedule with idempotent repeats of the last record."""
        if lp.entries == 0:
            return None
        layout = NodeImageLayout.for_config(self.primary.cfg)
        ops = decode_wire_stream(lp.wire)
        blk = layout.pack_log_entries(
            ops, [_LOG_CODES[op.KIND] for op in ops],
            lp.backptrs, lp.hints, lp.vdeltas)
        size = bucket_pow2(lp.entries)
        rows = StoreShard._pad_index(lp.rows, size)
        slots = StoreShard._pad_index(lp.slots, size)
        if size > lp.entries:
            blk = np.concatenate(
                [blk, np.repeat(blk[-1:], size - lp.entries, axis=0)])
        return (jnp.asarray(rows), jnp.asarray(slots), jnp.asarray(blk),
                layout.log_replay_offsets())

    def _on_primary_staged(self, payload: StagedSync) -> None:
        """Feed one staging to the group through the relay tree: encode
        the log payload's device block once, then deliver parent-first —
        a follower whose parent is paused or itself undelivered misses the
        payload (out of sync until a reachable staging full-copies it).
        Every edge's bytes are metered into ``FeedStats`` by edge class."""
        t0 = _now()
        fs = self.feed_stats
        lp = payload.log_payload
        marshalled = None
        if self.followers:
            if payload.kind == "full":
                fs.full_feed_epochs += 1
            elif lp is not None:
                fs.log_feed_epochs += 1
                marshalled = self._marshal_log_payload(lp)
            elif self._log_enabled:
                fs.log_fallback_epochs += 1
            else:
                fs.delta_feed_epochs += 1
        delivered = {0}
        for f in self.followers:
            parent = self._parents.get(f.replica_id, 0)
            if f.paused or parent not in delivered:
                f.in_sync = False      # missed payload: next feed is full
                continue
            can_replay = (lp is not None and f.in_sync
                          and (f._standby is not None
                               or f.snapshot is not None))
            if can_replay:
                nbytes = f.stage_log(payload, marshalled)
                fs.wire_bytes += lp.wire_nbytes
                fs.log_bytes += nbytes
            else:
                nbytes, was_full = f.stage(payload)
                if was_full and payload.kind != "full":
                    fs.full_catchups += 1
                    fs.catchup_bytes += nbytes
                elif self._log_enabled and payload.kind == "delta":
                    fs.fallback_bytes += nbytes
            fs.feed_bytes += nbytes
            if parent == 0:
                fs.primary_egress_bytes += nbytes
            else:
                fs.relay_hop_bytes += nbytes
            delivered.add(f.replica_id)
        self.replication_s += _now() - t0

    def _on_primary_flip(self) -> None:
        """Publish the group: every follower with a staged standby flips to
        the primary's new epoch; paused followers fall behind.  A follower
        that missed an intermediate staging (in_sync False) must NOT
        publish its older standby under the new epoch — its lag meters
        would read caught-up while its content is stale — so it also waits
        for the full catch-up feed."""
        for f in self.followers:
            if not f.paused and f.in_sync:
                f.flip(self.primary.epoch)

    # ------------------------------------------------- fault injection /
    # lag control (tests, maintenance drains)
    def pause_follower(self, replica: int) -> None:
        self.followers[replica - 1].paused = True

    def resume_follower(self, replica: int) -> None:
        self.followers[replica - 1].paused = False

    def resync_follower(self, replica: int) -> None:
        """Immediate full catch-up from the primary's ACTIVE snapshot
        (metered as a full sync); the follower serves again right away."""
        f = self.followers[replica - 1]
        snap = self.primary._snapshot
        if snap is None:
            return
        f.snapshot = jax.tree.map(jnp.copy, snap)
        f.snapshot_rv = self.primary._snapshot_rv
        f._standby = None
        f._standby_rv = None
        f.epoch = self.primary.epoch
        # deltas only resume if the primary has nothing staged mid-air
        # (an unflipped standby is a base we did not copy)
        f.in_sync = self.primary._standby is None
        f.sync_stats.snapshots += 1
        f.sync_stats.full_syncs += 1
        nbytes = _snapshot_nbytes(snap)
        f.sync_stats.bytes_synced += nbytes
        f.sync_stats.image_dma_count += 1
        f.sync_stats.image_bytes += _image_feed_bytes(snap, self.primary.cfg)
        # an admin resync is a primary-direct full catch-up on the feed
        self.feed_stats.full_catchups += 1
        self.feed_stats.catchup_bytes += nbytes
        self.feed_stats.feed_bytes += nbytes
        self.feed_stats.primary_egress_bytes += nbytes

    # ------------------------------------------------- replica dispatch
    def replica_for_dispatch(self) -> int:
        """Read-spreading policy pick for the next read batch —
        ``primary_only`` always serves the primary, ``round_robin`` rotates
        over the currently ELIGIBLE replicas, ``least_loaded`` picks the
        eligible replica with the fewest pick-time assignments.  The pick
        is a ROUTING decision only; dispatch still enforces the freshness
        rule (a lagging follower is skipped, never served stale)."""
        if (self.replication.policy == "primary_only"
                or self.n_replicas == 1):
            return 0
        elig = self.eligible_replicas()        # always contains the primary
        if self.replication.policy == "round_robin":
            r = elig[self._rr % len(elig)]
            self._rr += 1
            return r
        # least_loaded: fewest batches assigned so far (assignment counts
        # move at pick time, so a burst of submit-time picks still spreads)
        r = min(elig, key=self._assigned.__getitem__)
        self._assigned[r] += 1
        return r

    def routing(self) -> Routing:
        """Single-shard replicated wiring for the service (core/api.py):
        shard 0 everywhere, the group's own read-spreading pick, reads
        stamped with the serving replica + its snapshot read version."""
        return Routing(
            shard_of=lambda key: 0,
            replica_of=((lambda shard: self.replica_for_dispatch())
                        if self.n_replicas > 1 else None),
            report=lambda shard: self.last_dispatch,
            live_version=lambda shard: int(
                self.primary.tree.versions.read_version()))

    def eligible_replicas(self) -> list[int]:
        """Replica indices a read batch may be pinned to right now: the
        primary always, plus every follower that is unpaused and whose
        published read version covers the serving version.  The router's
        spreading policies pick over this set so dead/lagging lanes are
        routed around at pick time (the dispatch-time freshness check in
        ``_serving_follower`` still backstops races)."""
        return [0] + [i for i, f in enumerate(self.followers, start=1)
                      if not f.paused and self._covers(f)]

    def _covers(self, f: FollowerReplica) -> bool:
        """Freshness rule: the follower's published read version must cover
        what the group currently serves (the primary's active snapshot read
        version) — otherwise a spread read could observe stale state."""
        need = self.primary._snapshot_rv
        return (f.snapshot is not None and need is not None
                and f.snapshot_rv is not None and f.snapshot_rv >= need)

    def _serving_follower(self, replica: int | None,
                          n: int) -> FollowerReplica | None:
        """Resolve a dispatch to a follower, or None for the primary —
        enforcing the freshness rule (a lagging follower is skipped, the
        batch serves from the primary, and the skip is metered)."""
        if not replica or not self.followers:
            self._primary_served += n
            return None
        if self.primary.cfg.sync_policy != "explicit":
            # lazy-sync policies: freshen the whole group first, exactly as
            # the primary's own read path would (no-op when clean)
            self.primary.export_snapshot()
        f = self.followers[(replica - 1) % len(self.followers)]
        if not self._covers(f):
            self.lagging_skips += 1
            self._primary_served += n
            return None
        f.served_ops += n
        return f

    @property
    def replica_ops(self) -> list[int]:
        """Requests served per replica (primary first) — the least_loaded
        policy's signal and the read-spread imbalance meter."""
        return [self._primary_served] + [f.served_ops for f in self.followers]

    def get_batch(self, keys, replica: int | None = None):
        keys = list(keys)
        if not keys:
            return []
        f = self._serving_follower(replica, len(keys))
        if f is None:
            res = self.primary.get_batch(keys)
            self.last_dispatch = (0, self.primary.serving_version)
            return res
        san = _epochsan.get()
        if san is not None:   # re-derive the freshness rule at dispatch
            san.check_follower_dispatch(self, f)
        res = self.primary._device_get(f.snapshot, keys)
        self.last_dispatch = (f.replica_id,
                              f.snapshot_rv if f.snapshot_rv is not None
                              else 0)
        return res

    def scan_batch(self, ranges, replica: int | None = None):
        ranges = list(ranges)
        if not ranges:
            return []
        f = self._serving_follower(replica, len(ranges))
        if f is None:
            res = self.primary.scan_batch(ranges)
            self.last_dispatch = (0, self.primary.serving_version)
            return res
        san = _epochsan.get()
        if san is not None:   # re-derive the freshness rule at dispatch
            san.check_follower_dispatch(self, f)
        # eligibility pinned the follower at the primary snapshot's read
        # version, so truncated-scan host fallbacks use the primary's rule
        res = self.primary._device_scan(f.snapshot, ranges,
                                        self.primary._fallback_read_version())
        self.last_dispatch = (f.replica_id,
                              f.snapshot_rv if f.snapshot_rv is not None
                              else 0)
        return res

    # ------------------------------------------------------------- meters
    @property
    def replica_lag_epochs(self) -> list[int]:
        """Per-follower epoch lag behind the primary (0 = fully caught up)."""
        return [self.primary.epoch - f.epoch for f in self.followers]

    @property
    def replica_staleness(self) -> list[int]:
        """Per-follower read-version lag behind the primary's published
        snapshot (staleness in read-versions, 0 = serving-fresh)."""
        need = self.primary._snapshot_rv
        if need is None:
            return [0] * len(self.followers)
        return [need - (f.snapshot_rv if f.snapshot_rv is not None else 0)
                for f in self.followers]

    @property
    def replication_stats(self) -> SyncStats:
        """Aggregate follower SyncStats — the replication amplification the
        delta feed generated on top of the primary's own sync traffic."""
        return merge_stats((f.sync_stats for f in self.followers),
                           SyncStats)

    @property
    def replication_bytes(self) -> int:
        return sum(f.sync_stats.bytes_synced for f in self.followers)

    @property
    def per_replica_sync_stats(self) -> list[SyncStats]:
        return ([self.primary.sync_stats]
                + [f.sync_stats for f in self.followers])
