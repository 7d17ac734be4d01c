"""Shared benchmark machinery: workload generators (YCSB-style), store
builders, timing, and the byte-cost model.

Not a device measurement: these sections time whatever backend JAX runs
on, and off a TPU that is XLA:CPU — a number taken there says how fast
the CPU path is, not the chip.  What they reproduce is the analytic
bytes-per-operation model (hardware-independent; it reproduces the 5x
bytes claim) and the meters (sync bytes, DMA counts, cache hits).
TDP constants for cost-performance come from the paper (Section 6.3):
127 W CPU-only server, +40 W FPGA board -> 157.9 W for Honeycomb.
"""
from __future__ import annotations

import time

import numpy as np

from repro.baselines.cpu_store import CpuOrderedStore
from repro.core import (FeedTopology, Get, HoneycombConfig, HoneycombService,
                        HoneycombStore, Put, ReplicationConfig, Scan,
                        ShardedHoneycombStore, TelemetryConfig,
                        uniform_int_boundaries)
from repro.core.keys import int_key

TDP_BASELINE_W = 127.0
TDP_HONEYCOMB_W = 157.9

KEY_BYTES = 8

# observability wiring for the scheduled sections (core/telemetry.py):
# every run_scheduled service carries a metrics registry whose snapshot is
# attached to the section record; run.py --metrics raises the sample rate
# so one sampled Perfetto trace lands next to bench_results.json.  The
# bundle of the LAST run_scheduled call is kept for the artifact writers.
TRACE_SAMPLE_RATE = 0.0
LAST_TELEMETRY = None


def zipf_sampler(n: int, theta: float = 0.99, seed: int = 0):
    """Bounded zipfian over [0, n) (YCSB's distribution)."""
    rng = np.random.default_rng(seed)
    w = 1.0 / np.power(np.arange(1, n + 1), theta)
    cdf = np.cumsum(w / w.sum())

    def sample(k: int) -> np.ndarray:
        return np.searchsorted(cdf, rng.random(k)).astype(np.int64)
    return sample


def uniform_sampler(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)

    def sample(k: int) -> np.ndarray:
        return rng.integers(0, n, k)
    return sample


def build_stores(n_items: int = 8192, val_bytes: int = 16,
                 cfg: HoneycombConfig | None = None, seed: int = 0,
                 honeycomb: bool = True, baseline: bool = True,
                 shards: int = 1, replicas: int = 1,
                 replica_policy: str = "round_robin",
                 feed: str = "log", relay_fanout: int = 2,
                 relay_depth: int = 0,
                 force_router: bool = False):
    """Load both stores with the same random-order keys (paper: inserts are
    uniform random).  ``shards > 1`` builds the live range-sharded store
    (uniform split of the int-key space) instead of the single-device
    facade — the sweep axis for the scale-out benchmarks; ``replicas > 1``
    adds follower replicas per shard with ``replica_policy`` read
    spreading (the replication sweep axis).  ``feed`` selects the follower
    feed ("log" ships the epoch's encoded op stream and replays it on
    device; "delta" ships dirty image rows), and ``relay_fanout``/
    ``relay_depth`` shape the relay tree the payload fans out through
    (depth 0 = primary feeds every follower directly).  ``force_router``
    builds the routed facade even at shards=1/replicas=1, so sweeps that
    include the baseline point compare like against like."""
    rng = np.random.default_rng(seed)
    order = rng.permutation(n_items)
    val = bytes(val_bytes)
    if not honeycomb:
        hc = None
    elif shards > 1 or replicas > 1 or force_router:
        hc = ShardedHoneycombStore(
            cfg or HoneycombConfig(), shards=shards,
            boundaries=uniform_int_boundaries(n_items, shards),
            replication=ReplicationConfig(
                replicas=replicas, policy=replica_policy, feed=feed,
                topology=FeedTopology(fanout=relay_fanout,
                                      depth=relay_depth)))
    else:
        hc = HoneycombStore(cfg or HoneycombConfig())
    cp = CpuOrderedStore() if baseline else None
    for i in order:
        if hc:
            hc.put(int_key(int(i)), val)
        if cp:
            cp.put(int_key(int(i)), val)
    if hc:
        hc.export_snapshot()
    return hc, cp


def sync_traffic(store) -> dict:
    """Snapshot of a Honeycomb store's host->device sync meters (delta-sync
    subsystem) for paper-comparable traffic reporting."""
    s = store.sync_stats
    return {"bytes_synced": s.bytes_synced, "snapshots": s.snapshots,
            "full_syncs": s.full_syncs, "delta_syncs": s.delta_syncs,
            "pagetable_commands": s.pagetable_commands,
            "read_version_updates": s.read_version_updates,
            "log_entries": s.log_entries,
            "log_wire_bytes": s.log_wire_bytes,
            # node-image DMA meters (core/schema.py packed layout: ONE
            # contiguous image-row DMA per dirty node; legacy: one per field)
            "image_dma_count": s.image_dma_count,
            "image_bytes": s.image_bytes,
            # replica-amplification traffic (follower feed; 0 for the
            # unreplicated store, which has no replication machinery).
            # feed_bytes splits into primary_egress_bytes (edges out of the
            # primary) + relay_hop_bytes (relay->follower edges);
            # log_fallback_epochs counts delta-shipped epochs under the log
            # feed (tree-shape changes the wire stream can't replay)
            "replication_bytes": getattr(store, "replication_bytes", 0),
            "feed_bytes": getattr(store, "feed_bytes", 0),
            "primary_egress_bytes": getattr(store, "primary_egress_bytes", 0),
            "relay_hop_bytes": getattr(store, "relay_hop_bytes", 0),
            "log_fallback_epochs": getattr(store, "log_fallback_epochs", 0),
            "delta_fraction": s.delta_fraction}


_SYNC_DIFF_KEYS = ("bytes_synced", "snapshots", "full_syncs", "delta_syncs",
                   "pagetable_commands", "read_version_updates",
                   "log_entries", "log_wire_bytes", "image_dma_count",
                   "image_bytes", "replication_bytes", "feed_bytes",
                   "primary_egress_bytes", "relay_hop_bytes",
                   "log_fallback_epochs")


def run_mixed(store, sampler, *, n_ops: int, read_frac: float,
              n_items: int, scan_items: int = 0, batch: int = 256,
              is_honeycomb: bool = True, val: bytes = b"x" * 16,
              seed: int = 1) -> dict:
    """Timed mixed workload.  Reads run through the batched accelerator
    path for Honeycomb and per-op for the CPU baseline (that asymmetry IS
    the systems comparison).  Returns ops/s, latency stats and (for
    Honeycomb) the sync traffic the workload generated."""
    start_sync = sync_traffic(store) if is_honeycomb else None
    sharded = is_honeycomb and hasattr(store, "per_shard_sync_stats")
    start_per = ([s.bytes_synced for s in store.per_shard_sync_stats]
                 if sharded else None)
    start_ops = list(store.shard_ops) if sharded else None
    rng = np.random.default_rng(seed)
    ops = rng.random(n_ops) < read_frac
    keys = sampler(n_ops)
    t0 = time.perf_counter()
    done = 0
    i = 0
    while i < n_ops:
        if ops[i]:                       # read burst -> one device batch
            j = i
            while j < n_ops and ops[j] and j - i < batch:
                j += 1
            ks = [int_key(int(k)) for k in keys[i:j]]
            if scan_items:
                his = [int_key(min(int(k) + scan_items, n_items - 1))
                       for k in keys[i:j]]
                store.scan_batch(list(zip(ks, his)))
            else:
                store.get_batch(ks)
            done += j - i
            i = j
        else:
            store.put(int_key(int(keys[i])), val)
            done += 1
            i += 1
    dt = time.perf_counter() - t0
    out = {"ops_per_s": done / dt, "seconds": dt, "ops": done}
    if is_honeycomb:
        end = sync_traffic(store)
        out["sync"] = {k: end[k] - start_sync[k] for k in _SYNC_DIFF_KEYS}
        out["sync"]["bytes_per_op"] = out["sync"]["bytes_synced"] / max(done, 1)
        if sharded:
            per = [s.bytes_synced - b0 for s, b0 in
                   zip(store.per_shard_sync_stats, start_per)]
            out["sync"]["per_shard_bytes_per_op"] = [
                b / max(done, 1) for b in per]
            # imbalance over THIS run's routed requests only (the lifetime
            # counter would be dominated by the balanced load phase)
            ops = [b - a for a, b in zip(start_ops, store.shard_ops)]
            total = sum(ops)
            out["sync"]["load_imbalance"] = (
                max(ops) / (total / len(ops)) if total else 0.0)
    return out


def run_scheduled(store, sampler, *, n_ops: int, read_frac: float,
                  n_items: int, scan_items: int = 0, batch: int = 64,
                  pipeline: str = "serial", val: bytes = b"x" * 16,
                  seed: int = 1) -> dict:
    """Timed mixed workload driven through the typed service front end
    (``HoneycombService`` — core/api.py): ops submitted as first-class
    messages, one ``drain()`` pipeline epoch per ``batch`` submissions,
    routing self-wired from the store.  Returns ops/s plus the service's
    per-stage meters — the sync-stall-time comparison is THE
    pipelined-vs-serial artifact: serial mode blocks on every epoch's sync
    barrier; pipelined mode overlaps the standby scatters with read
    dispatch."""
    global LAST_TELEMETRY
    start_sync = sync_traffic(store)
    svc = HoneycombService(
        store, batch_size=batch, pipeline=pipeline,
        telemetry=TelemetryConfig(trace_sample_rate=TRACE_SAMPLE_RATE))
    LAST_TELEMETRY = svc.telemetry
    rng = np.random.default_rng(seed)
    reads = rng.random(n_ops) < read_frac
    keys = sampler(n_ops)
    t0 = time.perf_counter()
    for i in range(n_ops):
        k = int(keys[i])
        if not reads[i]:
            svc.submit(Put(int_key(k), val))
        elif scan_items:
            svc.submit(Scan(int_key(k),
                            int_key(min(k + scan_items, n_items - 1)),
                            expected_items=scan_items + 1))
        else:
            svc.submit(Get(int_key(k)))
        if (i + 1) % batch == 0:
            svc.drain()
    svc.drain()                          # flush the tail epoch
    dt = time.perf_counter() - t0
    end = sync_traffic(store)
    st = svc.stats
    return {
        "ops_per_s": n_ops / dt, "seconds": dt, "ops": n_ops,
        "pipeline": pipeline, "epochs": st.runs, "syncs": svc.syncs,
        "sync_stall_s": st.sync_stall_s, "stall_fraction": st.stall_fraction,
        "admit_s": st.admit_s, "export_s": st.export_s,
        "dispatch_s": st.dispatch_s, "lane_occupancy": st.lane_occupancy,
        "sync": {k: end[k] - start_sync[k] for k in _SYNC_DIFF_KEYS},
        # the registry view of the same run — counters/gauges from every
        # wired stats surface (the run.py --metrics table reads THIS, not
        # hand-picked fields)
        "metrics": svc.metrics_snapshot(),
    }


def bytes_model_honeycomb(cfg: HoneycombConfig, height: int) -> int:
    """Bytes fetched per GET per the paper's Section 3.1 accounting:
    header+shortcut+one segment per interior level, + leaf segment + log."""
    per_interior = cfg.header_bytes + cfg.shortcut_bytes + cfg.segment_bytes
    leaf = cfg.header_bytes + cfg.shortcut_bytes + cfg.segment_bytes \
        + cfg.log_bytes
    return per_interior * (height - 1) + leaf


def bytes_model_wholenode(cfg: HoneycombConfig, height: int) -> int:
    """Bytes fetched when whole nodes must be read (no shortcuts)."""
    return cfg.node_bytes * height


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.2f},{derived}")
