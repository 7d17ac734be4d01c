import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Dry-run of the paper's own workload at production scale: the Honeycomb
batched read path (GET/SCAN) compiled for the 16x16 mesh as a range-sharded
store service.

Deployment model — the LIVE ``ShardedHoneycombStore`` (core/router.py), at
mesh scale: the keyspace is range-sharded across all 256 chips — each chip
owns a complete Honeycomb tree for its range (~128M/256 = 500k items for the
paper's store) and serves its slice of the request batch; the router
(serving layer) pre-partitions requests by range, so the read path itself is
collective-free.  Expressed as a shard_map over (data, model) with per-shard
snapshots.

Two halves keep the abstract model honest:
  * the compile analysis sizes ONE shard's snapshot/delta with the same
    per-shard item count the router's uniform boundaries produce, and
    lowers the read path + delta application for the full mesh;
    ``pipeline_occupancy_model()`` lowers the two pipeline stages (standby
    delta scatter, batched read) separately and models the epoch pipeline
    of core/pipeline.py — serial epoch = export + dispatch, pipelined
    epoch = max(stage), with per-stage occupancy;
  * ``live_sharded_smoke()`` drives a small live ShardedHoneycombStore
    through the identical shape (range partition, per-shard delta sync
    plus one pipelined service epoch — typed op messages through
    ``HoneycombService``, core/api.py — with independent per-shard flips,
    cross-shard scan stitching) and reports per-shard sync traffic and
    router load imbalance — the measured twin of the modeled numbers;
    ``live_replicated_smoke()`` adds the replication axis (follower
    replicas fed by the log-shipped wire stream replayed on device —
    falling back to image-row deltas when the tree shape changed —
    round-robin read spreading, lag/amplification/feed meters,
    per-response replica/serving-version stamps — core/replica.py,
    core/api.py).

Usage: PYTHONPATH=src python -m repro.launch.store_dryrun
"""
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.core import (Get, HoneycombConfig, HoneycombService, Put,
                        ReplicationConfig, ShardedHoneycombStore,
                        TelemetryConfig, Update, uniform_int_boundaries)
from repro.core.keys import int_key
from repro.core.read_path import (SnapshotDelta, TreeSnapshot,
                                  apply_snapshot_delta, batched_get,
                                  batched_scan)
from repro.core.schema import NodeImageLayout
from repro.launch import hlo_analysis as hla
from repro.launch.mesh import make_production_mesh


def abstract_snapshot(cfg: HoneycombConfig, n_items: int, shards: int):
    """ShapeDtypeStructs for one shard's tree (paper store: 128M items,
    55% leaf occupancy, 8KB-equivalent nodes).  Shard sizing matches the
    live router's uniform range partition (n_items // shards items each);
    the snapshot is the PACKED node image (core/schema.py — one
    [S, image_words] u32 array, every field at a static word offset)."""
    items_per_shard = n_items // shards
    leaves = math.ceil(items_per_shard / (cfg.node_cap * 0.55))
    interior = math.ceil(leaves / (cfg.node_cap * 0.55)) + 8
    S = leaves + interior + 64          # physical slots incl. old versions
    layout = NodeImageLayout.for_config(cfg)
    sds = jax.ShapeDtypeStruct
    i32 = jnp.int32
    return TreeSnapshot(
        image=sds((S, layout.image_words), jnp.uint32),
        pagetable=sds((S,), i32),
        root_lid=sds((), i32),
        read_version=sds((), i32),
        cache_lids=sds((cfg.cache_slots,), i32),
        cache_image=sds((cfg.cache_slots, layout.image_words), jnp.uint32),
    ), S


def abstract_delta(cfg: HoneycombConfig, snap: TreeSnapshot, dirty_rows: int,
                   pt_commands: int) -> SnapshotDelta:
    """ShapeDtypeStructs for one shard's delta sync: D whole node-image
    rows (ONE contiguous DMA per dirty node) + P batched page-table
    commands + the two scalars."""
    sds = jax.ShapeDtypeStruct
    i32 = jnp.int32
    return SnapshotDelta(
        rows=sds((dirty_rows,), i32),
        image=sds((dirty_rows, snap.image.shape[1]), jnp.uint32),
        pt_lids=sds((pt_commands,), i32), pt_phys=sds((pt_commands,), i32),
        root_lid=sds((), i32), read_version=sds((), i32),
        cache_lids=sds(snap.cache_lids.shape, i32))


def delta_sync_analysis(cfg: HoneycombConfig, snap_abs: TreeSnapshot,
                        dirty_rows: int = 256,
                        pt_commands: int = 64) -> dict:
    """Compile the per-shard delta application and report the PCIe-analogue
    traffic: delta argument bytes vs the wholesale snapshot size."""
    delta_abs = abstract_delta(cfg, snap_abs, dirty_rows, pt_commands)
    lowered = jax.jit(apply_snapshot_delta, static_argnames="cfg").lower(
        snap_abs, delta_abs, cfg=cfg)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    size = lambda tree: sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in jax.tree.leaves(tree))
    full_bytes = size(snap_abs)
    delta_bytes = size(delta_abs)
    return {
        "dirty_rows": dirty_rows, "pagetable_commands": pt_commands,
        "delta_bytes_per_sync": delta_bytes,
        "full_snapshot_bytes": full_bytes,
        "traffic_ratio": delta_bytes / full_bytes,
        "compiled_temp_gb": mem.temp_size_in_bytes / 2 ** 30,
    }


def pipeline_occupancy_model(cfg: HoneycombConfig, snap_abs: TreeSnapshot,
                             batch_per_shard: int = 512,
                             dirty_rows: int = 256,
                             pt_commands: int = 64) -> dict:
    """Compile model of the epoch pipeline (core/pipeline.py): lower ONE
    shard's two device stages — the standby delta scatter (export) and the
    batched GET (dispatch) — and derive what double-buffering buys.

    A serial epoch pays export + dispatch back-to-back (the sync barrier);
    a pipelined epoch pays max(export, dispatch) once the pipe fills,
    because shard A's reads execute while shard B's scatter drains.  Stage
    occupancy is each stage's share of the bottleneck stage."""
    delta_abs = abstract_delta(cfg, snap_abs, dirty_rows, pt_commands)
    no_coll = {"total_bytes": 0}
    c_exp = jax.jit(apply_snapshot_delta, static_argnames="cfg") \
        .lower(snap_abs, delta_abs, cfg=cfg).compile()
    export_rl = hla.roofline(c_exp.cost_analysis(), no_coll, 0.0)
    sds = jax.ShapeDtypeStruct
    keys = sds((batch_per_shard, cfg.key_words), jnp.uint32)
    lens = sds((batch_per_shard,), jnp.int32)
    c_get = jax.jit(batched_get, static_argnames="cfg") \
        .lower(snap_abs, keys, lens, cfg=cfg).compile()
    read_rl = hla.roofline(c_get.cost_analysis(), no_coll, 0.0)
    export_s = max(export_rl.compute_s, export_rl.memory_s)
    read_s = max(read_rl.compute_s, read_rl.memory_s)
    serial_s = export_s + read_s
    pipelined_s = max(export_s, read_s)
    bottleneck = pipelined_s or 1e-30
    return {
        "dirty_rows": dirty_rows, "batch_per_shard": batch_per_shard,
        "export_stage_s": export_s, "read_stage_s": read_s,
        "serial_epoch_s": serial_s, "pipelined_epoch_s": pipelined_s,
        "pipeline_speedup": serial_s / bottleneck,
        "stage_occupancy": {"export": export_s / bottleneck,
                            "read": read_s / bottleneck},
        "bottleneck_stage": "export" if export_s >= read_s else "read",
    }


def _telemetry_report(svc: HoneycombService) -> dict:
    """The smoke's observability artifact (core/telemetry.py): the full
    registry snapshot, the Prometheus exposition (verify.sh parses it and
    asserts key meters), the Chrome trace-event JSON (written next to the
    results by ``main`` — Perfetto-loadable), and the last sampled
    trace's span chain + stamps for the lifecycle assertions."""
    traces = svc.traces()
    last = traces[-1] if traces else None
    return {
        "snapshot": svc.metrics_snapshot(),
        "prometheus": svc.prometheus(),
        "chrome_trace": svc.chrome_trace(),
        "sampled_traces": len(traces),
        "last_trace": ({"kind": last.kind, "spans": last.span_names(),
                        "tags": last.tags} if last else None),
    }


def live_sharded_smoke(shards: int = 4, n_items: int = 1024,
                       batch: int = 64) -> dict:
    """Drive a small LIVE ShardedHoneycombStore through the dry-run's
    deployment shape: uniform range partition, per-shard resident snapshots
    and delta syncs, router-split GET batches, cross-shard SCAN stitching.
    Returns the measured per-shard sync traffic and load imbalance that the
    mesh-scale compile analysis only models."""
    cfg = HoneycombConfig()
    st = ShardedHoneycombStore(
        cfg, heap_capacity=1024, shards=shards,
        boundaries=uniform_int_boundaries(n_items, shards))
    rng = np.random.default_rng(11)
    for i in rng.permutation(n_items):
        st.put(int_key(int(i)), b"v" * 12)
    st.export_snapshot()                     # resident snapshot per shard
    # router-split GET batch + one scan spanning every shard
    keys = [int_key(int(k)) for k in rng.integers(0, n_items, batch)]
    st.get_batch(keys)
    span = st.scan_batch([(int_key(1), int_key(n_items - 2))])[0]
    # write burst confined to one shard -> exactly one delta sync
    snaps0 = [s.snapshots for s in st.per_shard_sync_stats]
    lo_shard = n_items // shards
    for k in range(batch):
        st.update(int_key(k % lo_shard), b"u" * 12)
    st.export_snapshot()
    dirty = [s.snapshots - b for s, b in zip(st.per_shard_sync_stats, snaps0)]
    # one pipelined service epoch (typed op messages, routing self-wired
    # from the store — core/api.py): staged standby scatters + independent
    # per-shard flips + immediate read dispatch (measured twin of
    # pipeline_occupancy_model)
    svc = HoneycombService(
        st, batch_size=batch, pipeline="pipelined",
        telemetry=TelemetryConfig(trace_sample_rate=0.25))
    svc.submit_many(
        op for k in range(batch)
        for op in (Update(int_key(int(rng.integers(0, n_items))), b"p" * 12),
                   Get(int_key(int(rng.integers(0, n_items))))))
    svc.drain()
    # fused read-path invariants: the megakernels served with the cache
    # tier resolving levels from VMEM, and answer what the shard's host
    # tree answers (the snapshot was synced just now, so both are at the
    # same read version)
    span_per_shard = n_items // shards
    for i, sh in enumerate(st.shards):
        pk = [int_key(int(k)) for k in
              rng.integers(i * span_per_shard, (i + 1) * span_per_shard, 16)]
        snap = sh._snapshot_for_read()
        assert sh._device_get(snap, pk) == [sh.get(k) for k in pk], \
            f"fused GET diverged from the host tree on shard {i}"
        pr = [(pk[0], pk[1])]
        assert sh._device_scan(snap, pr, None) == \
            [sh.scan(lo, hi) for lo, hi in pr], \
            f"fused SCAN diverged from the host tree on shard {i}"
    vmem_hits = sum(sh.cache.stats.vmem_hits for sh in st.shards)
    heap_gathers = sum(sh.cache.stats.heap_gathers for sh in st.shards)
    assert vmem_hits > 0, "cache tier never served a descend level"
    agg = st.sync_stats
    ps = st.pipeline_stats
    return {
        "shards": shards, "items": n_items,
        "cross_shard_scan_items": len(span),
        "image_dma_count": agg.image_dma_count,
        "image_bytes": agg.image_bytes,
        "per_shard_bytes_synced": [s.bytes_synced
                                   for s in st.per_shard_sync_stats],
        "per_shard_delta_syncs": [s.delta_syncs
                                  for s in st.per_shard_sync_stats],
        "dirty_shard_syncs_after_confined_burst": dirty,
        "log_wire_bytes": agg.log_wire_bytes,
        "load_imbalance": st.load_imbalance,
        "read_path": {
            "vmem_hits": vmem_hits,
            "heap_gathers": heap_gathers,
            "fused_matches_host_tree": True,     # asserted above
        },
        "pipelined_epoch": {
            "per_shard_epochs": st.per_shard_epochs,
            "staged_exports": ps.staged_exports, "flips": ps.flips,
            "sync_stall_s": svc.stats.sync_stall_s,
            "lane_occupancy": svc.stats.lane_occupancy,
        },
        "telemetry": _telemetry_report(svc),
    }


def live_replicated_smoke(shards: int = 2, replicas: int = 2,
                          n_items: int = 512, batch: int = 64) -> dict:
    """The replication twin of ``live_sharded_smoke``: each shard serves
    from a primary plus follower replicas fed by the primary's log-shipped
    op wire stream, replayed on device by the log_replay_scatter kernel
    (core/replica.py; tree-shape-changing epochs fall back to the image
    delta), with round-robin read spreading through the scheduler's
    (shard, replica, kind, cost) buckets.  Reports per-replica served
    lanes, the feed amplification bytes (with the primary-egress /
    relay-hop split and fallback-epoch count) and the epoch-lag freshness
    meters the mesh-scale model treats as free."""
    cfg = HoneycombConfig()
    st = ShardedHoneycombStore(
        cfg, heap_capacity=1024, shards=shards,
        boundaries=uniform_int_boundaries(n_items, shards),
        replication=ReplicationConfig(replicas=replicas,
                                      policy="round_robin"))
    rng = np.random.default_rng(13)
    for i in rng.permutation(n_items):
        st.put(int_key(int(i)), b"v" * 12)
    st.export_snapshot()                 # primaries + followers resident
    svc = HoneycombService(
        st, batch_size=batch // 2, pipeline="pipelined",
        telemetry=TelemetryConfig(trace_sample_rate=0.25))
    tickets = svc.submit_many(
        op for k in range(batch)
        for op in (Update(int_key(int(rng.integers(0, n_items))), b"r" * 12),
                   Get(int_key(int(rng.integers(0, n_items)))),
                   Get(int_key(int(rng.integers(0, n_items))))))
    svc.drain()
    reads = [t.result() for t in tickets if not t.op.IS_WRITE]
    # settle bursts: an epoch whose updates overflow a leaf log merges the
    # leaf (pending page-table command -> metered fallback to the image
    # delta); the next burst appends into the freshly merged leaves, so
    # within a few rounds an epoch MUST ship over the log feed — a silent
    # regression to delta-only would break the log-shipping claim
    burst = [int_key(0), int_key(n_items - 1)]      # one leaf per shard
    for _ in range(4):
        if st.feed_stats.log_feed_epochs > 0:
            break
        for k in burst * 3:
            st.update(k, b"l" * 12)
        st.export_snapshot()
    fs = st.feed_stats
    assert fs.log_feed_epochs > 0, "log feed never engaged"
    assert fs.log_bytes > 0 and fs.wire_bytes > 0
    log_replays = sum(f.sync_stats.log_replays
                      for sh in st.shards for f in sh.followers)
    assert log_replays > 0, "no follower replayed a log payload on device"
    # followers inherit the cache tier through the feeds (delta applies
    # re-attach it; log replays rebuild it from the replayed image) and
    # their fused reads match the primary's host tree
    vmem_hits = 0
    for sh in st.shards:
        for f in sh.followers:
            snap = f.snapshot
            assert snap is not None and snap.cache_image is not None, \
                "follower lost the cache tier over the feed"
            pk = [int_key(int(k)) for k in rng.integers(0, n_items, 8)]
            got = sh.primary._device_get(snap, pk)
            assert got == [sh.primary.get(k) for k in pk], \
                "follower fused GET diverged from the host tree"
        vmem_hits += sh.cache.stats.vmem_hits
    assert vmem_hits > 0, "cache tier never served a descend level"
    return {
        "shards": shards, "replicas": replicas, "items": n_items,
        "primary_image_dmas": st.sync_stats.image_dma_count,
        "served_replica_lanes": sorted({r.replica for r in reads}),
        "serving_versions": sorted({r.serving_version for r in reads}),
        "per_shard_replica_ops": st.per_shard_replica_ops,
        "replica_load_imbalance": st.replica_load_imbalance,
        "replication_bytes": st.replication_bytes,
        "feed": {
            "feed_bytes": fs.feed_bytes,
            "log_feed_epochs": fs.log_feed_epochs,
            "log_fallback_epochs": fs.log_fallback_epochs,
            "log_bytes": fs.log_bytes,
            "wire_bytes": fs.wire_bytes,
            "fallback_bytes": fs.fallback_bytes,
            "primary_egress_bytes": fs.primary_egress_bytes,
            "relay_hop_bytes": fs.relay_hop_bytes,
            "log_replays": log_replays,
        },
        "primary_sync_bytes": st.sync_stats.bytes_synced,
        "read_path": {
            "vmem_hits": vmem_hits,
            "followers_cache_resident": True,    # asserted above
            "fused_matches_host_tree": True,     # asserted above
        },
        "replica_lag_epochs": st.replica_lag_epochs,
        "replica_staleness": st.replica_staleness,
        "lagging_skips": st.lagging_skips,
        "telemetry": _telemetry_report(svc),
    }


def main(batch_per_shard: int = 512, n_items: int = 128_000_000):
    cfg = HoneycombConfig()   # paper geometry: 64-cap nodes, 8 shortcuts
    mesh = make_production_mesh(multi_pod=False)
    shards = mesh.devices.size
    snap_abs, S = abstract_snapshot(cfg, n_items, shards)

    B = batch_per_shard * shards
    sds = jax.ShapeDtypeStruct
    keys = sds((B, cfg.key_words), jnp.uint32)
    lens = sds((B,), jnp.int32)

    def service(snap, lo, lolen, hi, hilen):
        """One shard: its own tree, its slice of the request batch."""
        res = batched_scan(snap, lo, lolen, hi, hilen, cfg)
        get = batched_get(snap, lo, lolen, cfg)
        return res.count, res.vals, get.found

    # every chip holds a DIFFERENT shard's tree: logically the snapshot is
    # a [shards, ...] stack sharded one-per-chip; requests shard likewise
    stacked = jax.tree.map(
        lambda a: sds((shards, *a.shape), a.dtype), snap_abs)
    spec_tree = jax.tree.map(lambda a: P(("data", "model")), snap_abs)

    def svc(snap_stk, lo, lolen, hi, hilen):
        body = lambda s, a, b, c, d: service(
            jax.tree.map(lambda x: x[0], s), a, b, c, d)
        return shard_map(
            body, mesh=mesh,
            in_specs=(spec_tree, P(("data", "model")), P(("data", "model")),
                      P(("data", "model")), P(("data", "model"))),
            out_specs=(P(("data", "model")), P(("data", "model")),
                       P(("data", "model"))),
            check_vma=False)(snap_stk, lo, lolen, hi, hilen)

    with mesh:
        lowered = jax.jit(svc).lower(stacked, keys, lens, keys, lens)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = hla.collective_bytes(compiled.as_text())

    rl = hla.roofline(cost, coll, model_flops_per_device=0.0)
    out = {
        "workload": f"honeycomb GET+SCAN, {n_items/1e6:.0f}M items "
                    f"range-sharded over {shards} chips, "
                    f"{batch_per_shard} requests/chip",
        "slots_per_shard": S,
        "peak_gb_per_chip": (mem.argument_size_in_bytes
                             + mem.output_size_in_bytes
                             + mem.temp_size_in_bytes
                             - mem.alias_size_in_bytes) / 2 ** 30,
        "compute_s": rl.compute_s, "memory_s": rl.memory_s,
        "collective_s": rl.collective_s,
        "collective_bytes": coll["total_bytes"],
        "reads_per_s_per_chip_bound": (
            batch_per_shard / max(rl.memory_s, rl.compute_s, 1e-12)),
        "delta_sync": delta_sync_analysis(cfg, snap_abs),
        "pipeline": pipeline_occupancy_model(cfg, snap_abs, batch_per_shard),
        "live_sharded_store": live_sharded_smoke(),
        "live_replicated_store": live_replicated_smoke(),
    }
    # the observability artifacts land NEXT TO the results (CI uploads
    # them): one registry metrics snapshot per live smoke, plus the
    # replicated smoke's sampled lifecycle traces as a Perfetto-loadable
    # Chrome trace-event file.  The bulky exports are popped out of the
    # main results JSON; the parsed/asserted surfaces stay inline.
    exp = Path("experiments")
    exp.mkdir(exist_ok=True)
    metrics = {k: out[k]["telemetry"]["snapshot"]
               for k in ("live_sharded_store", "live_replicated_store")}
    (exp / "store_dryrun_metrics.json").write_text(
        json.dumps(metrics, indent=1))
    trace = out["live_replicated_store"]["telemetry"].pop("chrome_trace")
    out["live_sharded_store"]["telemetry"].pop("chrome_trace")
    (exp / "store_dryrun_trace.json").write_text(json.dumps(trace))
    print(json.dumps(out, indent=1))
    (exp / "store_dryrun.json").write_text(json.dumps(out, indent=1))
    return out


if __name__ == "__main__":
    main()
