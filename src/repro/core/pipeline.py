"""Epoch-pipelined execution engine — design notes and stage meters.

Honeycomb's throughput comes from keeping every stage of the serving path
busy at once: the FPGA answers reads from a resident snapshot while the
host batches writes and streams the next delta over PCIe (request
parallelism + batched synchronization, paper Sections 3-4).  The original
``OutOfOrderScheduler.run()`` modeled that cycle *serially* — apply all
writes, one blocking sync, then dispatch reads — so the device sat idle
for the whole sync and the host sat idle for the whole read phase.  This
module defines the pipelined replacement.

Design
======

**Double-buffered resident snapshots (core/shard.py).**  Each
``StoreShard`` keeps an *active* snapshot (the epoch in-flight read
batches execute against, pinned at its read version) and stages the next
epoch into a *standby* buffer:

  * ``begin_export()`` — the staging half of the old ``export_snapshot()``:
    meter the sync, gather the dirty rows + page-table commands on the
    host, and enqueue the delta scatter into the standby buffer.  The
    scatter is dispatched asynchronously; nothing blocks, and the active
    snapshot keeps answering untouched.
  * ``flip()`` — the publish half: an atomic epoch advance that makes the
    standby the new active.  The old active's arrays are functional device
    copies, so batches already in flight finish at their pinned read
    version; under ``sync_policy="explicit"`` the accelerator-epoch pin
    (acquired at staging time, when the standby's read version was
    captured) rolls forward here so GC keeps old-version chains walkable
    for host fallbacks — two flips plus a ``collect_garbage()`` later, an
    old-epoch snapshot still answers at its read version (tested).
  * ``export_snapshot()`` ≡ ``begin_export(); flip()`` — the serial
    composition, byte-for-byte identical to the pre-pipeline behavior.

**Explicit scheduler stages (core/scheduler.py).**  ``run()`` is now a
composition of three public stages — ``stage_admit`` (apply host writes in
submission order, per-shard policy syncs deferred), ``stage_export``
(stage per-shard deltas into standby buffers and flip each dirty shard
independently), ``stage_dispatch`` (consume ``ready_batches()``) — so
callers can interleave stages of consecutive epochs (admit epoch N+1
while epoch N's scatters drain on the device queue).

**Two pipeline modes.**

  * ``pipeline="serial"`` reproduces the pre-refactor sequence op-for-op
    (same results, same ``SyncStats`` byte counts — tested): one facade
    ``export_snapshot()`` covering every dirty shard, then reads.  It also
    models the blocking PCIe barrier the serial design implies —
    ``jax.block_until_ready`` on the freshly synced snapshots before any
    read dispatches — and meters that wait as ``sync_stall_s``.
  * ``pipeline="pipelined"`` stages every dirty shard's standby
    (asynchronous scatter enqueue), flips each shard independently, and
    dispatches read batches immediately: shard A's reads execute while
    shard B's scatter is still in the device queue, and the only stall is
    the host-side staging time.  Results and sync byte counts are
    identical to serial mode by construction (reads always execute
    against the flipped epoch); only the overlap differs.

Sanitizer seams
===============

The stage boundaries above are exactly where the epoch protocol can be
violated, so they double as EpochSan interposition points
(repro/analysis/epochsan.py, enabled via ``HONEYCOMB_EPOCHSAN=1``):
``begin_export`` tags the staged standby and audits the interior-cache
frontier against PageTable remaps, ``flip`` retags the published
snapshot, ``_device_get``/``_device_scan`` reject dispatches against an
unflipped standby, the scheduler's ``stage_export`` asserts every staged
standby was published before reads dispatch, ``collect_garbage`` audits
reclamation against the pinned epoch window, and the replica group's
dispatch re-derives the follower freshness rule.  Off, each seam costs
one module call returning None.

Meters
======

``PipelineStats`` carries per-stage wall time and occupancy:
``sync_stall_s`` (host time in the sync barrier — serial mode's
``block_until_ready``, pipelined mode's flip — the quantity pipelining
exists to remove), ``admit_s``/``export_s``/``dispatch_s`` stage timings,
the read-dispatch split ``pack_s``/``fetch_s``/``decode_s``, flip/stage
counts, and device-lane occupancy (real requests vs ``bucket_pow2``-padded
lanes).  Shards meter their staging/flip side and the read split, the
router aggregates them, and the scheduler meters the stage loop;
benchmarks report both (``benchmarks/ycsb.py --pipeline``,
``benchmarks/latency.py``).  Every stage time is also a program span on
the profiler's clock (``telemetry.span``, named ``hc.<stage>``).
"""
from __future__ import annotations

import dataclasses

PIPELINE_MODES = ("serial", "pipelined")


@dataclasses.dataclass
class PipelineStats:
    """Per-stage timing/occupancy meters for the epoch pipeline."""
    runs: int = 0               # scheduler run() epochs completed
    admit_s: float = 0.0        # host write-apply stage wall time
    export_s: float = 0.0       # standby staging wall time (host side)
    dispatch_s: float = 0.0     # read-batch dispatch stage wall time
    sync_stall_s: float = 0.0   # sync barrier wall time before any read
    #   of the epoch could dispatch (serial block_until_ready; the
    #   pipelined flip, ~0)
    pack_s: float = 0.0         # read batches: padding, key packing and
    #   lane uploads (shard)
    fetch_s: float = 0.0        # read batches: the one device->host copy
    #   of each batch's packed results and meters, the wait for the device
    #   included (shard)
    decode_s: float = 0.0       # read batches: decoding results (shard)
    staged_exports: int = 0     # begin_export calls that staged a standby
    flips: int = 0              # epoch publishes
    dispatched_lanes: int = 0   # real requests inside device batches
    padded_lanes: int = 0       # bucket_pow2 device lanes those occupied
    host_scans: int = 0         # device SCAN requests the host tree had to
    #   answer (leaf/item budget exhausted — paper Section 6.3)
    read_batches: int = 0       # device read batches dispatched (shard)
    read_copies: int = 0        # device->host copies those batches made
    device_values: int = 0      # out-of-node values a device read batch
    #   served from the device value image (``gather_values``, shard)

    def merge(self, other: "PipelineStats"):
        """Accumulate another meter (router aggregation over shards)."""
        for f in dataclasses.fields(self):
            setattr(self, f.name,
                    getattr(self, f.name) + getattr(other, f.name))

    @property
    def lane_occupancy(self) -> float:
        """Real requests / padded device lanes (1.0 = no padding waste)."""
        return (self.dispatched_lanes / self.padded_lanes
                if self.padded_lanes else 0.0)

    @property
    def stall_fraction(self) -> float:
        """sync_stall_s over total staged wall time — the serial barrier's
        share of the epoch; pipelining drives it toward zero."""
        busy = self.admit_s + self.export_s + self.dispatch_s
        return self.sync_stall_s / busy if busy > 0 else 0.0

    def collect(self):
        """Registry samples (core/telemetry.py collect protocol):
        ``pipeline_*`` counters plus the two derived-ratio gauges.  The
        registering layer labels which surface this is (``src="store"``
        for the shard-side staging meters, ``src="scheduler"`` for the
        epoch-stage meters)."""
        from .telemetry import samples_from
        return samples_from(self, "pipeline", "pipeline",
                            derived=("lane_occupancy", "stall_fraction"))
