"""Epoch-pipelined execution engine (core/pipeline.py): double-buffered
snapshot staging/flip (incl. survival under GC churn), pipelined-vs-serial
scheduler equivalence (results AND sync byte counts), the serial mode's
op-for-op match with the legacy inline sequence, the image-row delta
scatter applied to a whole snapshot, and the shared power-of-two bucket
schedule."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (HoneycombConfig, HoneycombStore, OutOfOrderScheduler,
                        ShardedHoneycombStore, apply_snapshot_delta,
                        batched_get, bucket_pow2, uniform_int_boundaries)
from repro.core.keys import int_key, pack_keys

SMALL = HoneycombConfig(node_cap=16, log_cap=4, n_shortcuts=4)
B4 = uniform_int_boundaries(200, 4)


def submit_random_mixed(scheds, rng, n, key_space=200):
    """Submit an identical randomized put/update/delete/get/scan mix to
    every scheduler; returns nothing (rids align across schedulers)."""
    for _ in range(n):
        k = int(rng.integers(0, key_space))
        op = rng.random()
        for s in scheds:
            if op < 0.25:
                s.submit("put", int_key(k), value=b"v%03d" % k)
            elif op < 0.35:
                s.submit("update", int_key(k), value=b"u%03d" % k)
            elif op < 0.45:
                s.submit("delete", int_key(k))
            elif op < 0.8:
                s.submit("get", int_key(k))
            else:
                s.submit("scan", int_key(k),
                         int_key(min(k + 7, key_space - 1)),
                         expected_items=8)


# ---------------------------------------------------------------- flip path
def test_standby_invisible_until_flip():
    """begin_export stages the next epoch without touching the active
    snapshot; only flip() publishes it."""
    cfg = HoneycombConfig(node_cap=16, log_cap=4, n_shortcuts=4,
                          sync_policy="explicit")
    st = HoneycombStore(cfg, heap_capacity=256)
    for i in range(50):
        st.put(int_key(i), b"old")
    st.export_snapshot()
    assert st.epoch == 1
    st.update(int_key(3), b"new")
    assert st.begin_export()
    # device reads still answer from the active (pre-flip) epoch
    assert st.get_batch([int_key(3)]) == [b"old"]
    st.flip()
    assert st.epoch == 2
    assert st.get_batch([int_key(3)]) == [b"new"]
    # flip with nothing staged is a no-op
    snap = st.flip()
    assert st.epoch == 2 and snap is not None


def test_flip_under_gc_churn():
    """An old-epoch snapshot still answers at its read version after two
    staged flips plus collect_garbage() — the MVCC/GC pins survive the
    double-buffer path."""
    st = HoneycombStore(SMALL, heap_capacity=256)
    for i in range(50):
        st.put(int_key(i), b"old")
    old_snap = st.export_snapshot()
    for round_ in range(2):
        for i in range(50):
            st.update(int_key(i), b"new%d" % round_)
        assert st.begin_export()
        st.flip()
        st.tree.epochs.cpu_begin(0)
        st.collect_garbage()
    assert st.epoch == 3
    assert st.sync_stats.delta_syncs > 0
    lanes, lens = pack_keys([int_key(i) for i in range(50)], SMALL.key_words)
    res = batched_get(old_snap, jnp.asarray(lanes), jnp.asarray(lens), SMALL)
    assert bool(res.found.all())
    vals = np.asarray(res.vals)
    for i in range(50):
        assert vals[i].astype(">u4").tobytes()[:3] == b"old", i
    # and the flipped epoch answers fresh
    assert st.get_batch([int_key(7)]) == [b"new1"]


def test_first_stage_not_respun_by_reads_before_flip():
    """Regression: a read (lazy export) landing between the FIRST-ever
    begin_export and its flip must not re-stage a spurious sync — the
    clean-check honors a staged standby even when no active snapshot
    exists yet."""
    st = HoneycombStore(SMALL, heap_capacity=256)
    for i in range(40):
        st.put(int_key(i), b"v")
    assert st.begin_export()
    assert st.sync_stats.snapshots == 1
    # on_read policy: get_batch routes through export_snapshot, which must
    # only flip the staged standby, not meter a second sync
    assert st.get_batch([int_key(1)]) == [b"v"]
    assert st.sync_stats.snapshots == 1
    assert st.sync_stats.delta_syncs == 0
    assert st.epoch == 1


def test_restaged_standby_accumulates_deltas():
    """Two begin_export calls without an intervening flip accumulate into
    ONE standby; the eventual flip publishes both write bursts."""
    cfg = HoneycombConfig(node_cap=16, log_cap=4, n_shortcuts=4,
                          sync_policy="explicit")
    st = HoneycombStore(cfg, heap_capacity=256)
    for i in range(40):
        st.put(int_key(i), b"a")
    st.export_snapshot()
    st.update(int_key(1), b"b")
    assert st.begin_export()
    st.update(int_key(2), b"c")
    assert st.begin_export()
    assert st.get_batch([int_key(1), int_key(2)]) == [b"a", b"a"]
    st.flip()
    assert st.get_batch([int_key(1), int_key(2)]) == [b"b", b"c"]
    assert st.sync_stats.snapshots == 3     # one per begin_export


def test_router_flips_dirty_shards_independently():
    """begin_export stages ONLY dirty shards; per-shard epochs advance
    independently at flip."""
    sh = ShardedHoneycombStore(SMALL, heap_capacity=256, shards=4,
                               boundaries=B4)
    for i in range(0, 200, 2):
        sh.put(int_key(i), b"v")
    sh.export_snapshot()
    assert sh.per_shard_epochs == [1, 1, 1, 1]
    for i in range(100, 140, 2):            # shard 2 only
        sh.update(int_key(i), b"u")
    assert sh.begin_export() == [2]
    sh.flip()
    assert sh.per_shard_epochs == [1, 1, 2, 1]
    assert sh.pipeline_stats.flips == 5
    assert sh.get_batch([int_key(100), int_key(2)]) == [b"u", b"v"]


# ------------------------------------------- pipelined-vs-serial equivalence
def test_pipelined_equals_serial_randomized():
    """Randomized mixed workload: pipelined mode returns the same responses
    AND the same SyncStats (byte counts included) as serial mode."""
    a = ShardedHoneycombStore(SMALL, heap_capacity=256, shards=4,
                              boundaries=B4)
    b = ShardedHoneycombStore(SMALL, heap_capacity=256, shards=4,
                              boundaries=B4)
    sa = OutOfOrderScheduler(batch_size=8, routing=a.routing(),
                             pipeline="serial")
    sb = OutOfOrderScheduler(batch_size=8, routing=b.routing(),
                             pipeline="pipelined")
    rng = np.random.default_rng(17)
    for round_ in range(4):
        submit_random_mixed((sa, sb), rng, 70)
        out_a = sa.run(a)
        out_b = sb.run(b)
        assert out_a == out_b, round_
        assert a.sync_stats == b.sync_stats, round_
        assert sa.syncs == sb.syncs
    assert a.sync_stats.delta_syncs > 0     # the mix exercised delta syncs
    assert sa.dispatched_requests == sb.dispatched_requests
    # pipelined mode actually staged and flipped standby buffers
    assert b.pipeline_stats.staged_exports >= sb.syncs
    assert b.pipeline_stats.flips >= sb.syncs


def test_serial_run_matches_legacy_inline_sequence():
    """pipeline="serial" is op-for-op the pre-refactor run(): apply writes
    under deferred_sync, ONE facade export_snapshot(), dispatch
    ready_batches — same responses, same sync byte counts."""
    a = ShardedHoneycombStore(SMALL, heap_capacity=256, shards=4,
                              boundaries=B4)
    b = ShardedHoneycombStore(SMALL, heap_capacity=256, shards=4,
                              boundaries=B4)
    sched = OutOfOrderScheduler(batch_size=8, routing=a.routing(),
                                pipeline="serial")
    legacy = OutOfOrderScheduler(batch_size=8, routing=b.routing())
    rng = np.random.default_rng(5)
    submit_random_mixed((sched, legacy), rng, 90)
    out = sched.run(a)
    # the literal pre-refactor sequence, inlined:
    out_legacy = {}
    with b.deferred_sync():
        for r in legacy._writes:
            if r.kind == "put":
                b.put(r.key, r.value)
            elif r.kind == "update":
                b.update(r.key, r.value)
            else:
                b.delete(r.key)
            out_legacy[r.rid] = None
    legacy._writes.clear()
    if out_legacy:
        b.export_snapshot()
    for kind, batch in legacy.ready_batches(flush=True):
        if kind == "get":
            res = b.get_batch([r.key for r in batch])
        else:
            res = b.scan_batch([(r.key, r.hi) for r in batch])
        for r, v in zip(batch, res):
            out_legacy[r.rid] = v
    assert out == out_legacy
    assert a.sync_stats == b.sync_stats


def test_pipeline_stage_meters():
    """The stage meters accumulate: stall/stage timings, lane occupancy
    (bucket_pow2 padding), runs."""
    st = HoneycombStore(SMALL, heap_capacity=256)
    sched = OutOfOrderScheduler(batch_size=8, pipeline="pipelined")
    for i in range(20):
        sched.submit("put", int_key(i), value=b"v")
    for i in range(0, 20, 2):
        sched.submit("get", int_key(i))
    sched.run(st)
    s = sched.stats
    assert s.runs == 1
    assert s.admit_s > 0 and s.dispatch_s > 0
    assert s.dispatched_lanes == 10
    # 10 gets at batch_size=8 -> one full 8-batch + one 2-batch (pads to 2)
    assert s.padded_lanes == 8 + 2
    assert s.lane_occupancy == 1.0
    assert 0.0 <= s.stall_fraction <= 1.0
    assert st.pipeline_stats.staged_exports == sched.syncs == 1

    with pytest.raises(AssertionError):
        OutOfOrderScheduler(pipeline="warp")


# ------------------------------------------------------ image-row scatter
def test_packed_image_scatter_matches_oracle():
    """apply_snapshot_delta(backend="interpret") — ONE contiguous image-row
    scatter per dirty node, then the cache tier re-attached — is
    bit-identical to the jnp oracle on a materialized snapshot/delta
    pair."""
    from repro.launch.store_dryrun import abstract_delta, abstract_snapshot
    cfg = SMALL
    snap_abs, S = abstract_snapshot(cfg, n_items=64, shards=1)
    rng = np.random.default_rng(0)
    mat = lambda s: jnp.asarray(rng.integers(0, 100, s.shape).astype(s.dtype))
    snap = jax.tree.map(mat, snap_abs)
    delta = jax.tree.map(mat, abstract_delta(cfg, snap_abs, 3, 2))
    delta = delta._replace(
        rows=jnp.asarray(np.array([1, 4, S - 1], np.int32)),
        pt_lids=jnp.asarray(np.array([0, 2], np.int32)),
        pt_phys=jnp.asarray(np.array([5, 6], np.int32)))
    want = apply_snapshot_delta(snap, delta, cfg=cfg)
    got = apply_snapshot_delta(snap, delta, cfg=cfg, backend="interpret")
    assert got.cache_image is not None
    for f in want._fields:
        w, g = getattr(want, f), getattr(got, f)
        if f == "values":              # no value image in this snapshot
            assert w is None and g is None
        else:
            assert bool(jnp.array_equal(w, g)), f


# ------------------------------------------------------- bucket schedule
def test_bucket_schedule_pinned():
    """The shared power-of-two bucket schedule (one jit compile per bucket)
    is pinned, and every padded path (shard read batches + delta vectors;
    the scheduler consumes the shard's lane meters) uses the ONE helper in
    config — the former shard-local ``_bucket`` copy is gone."""
    assert [bucket_pow2(n) for n in range(11)] == \
        [1, 1, 2, 4, 4, 8, 8, 8, 8, 16, 16]
    assert bucket_pow2(256) == 256 and bucket_pow2(257) == 512
    from repro.core import config, shard
    assert shard.bucket_pow2 is config.bucket_pow2
    assert not hasattr(shard, "_bucket")
    # the scheduler's device-lane meters agree with the shard's padding
    st = HoneycombStore(SMALL, heap_capacity=256)
    for i in range(20):
        st.put(int_key(i), b"v")
    st.export_snapshot()
    sched = OutOfOrderScheduler(batch_size=8)
    for i in range(5):
        sched.submit("get", int_key(i))
    sched.run(st)
    assert sched.stats.dispatched_lanes == 5
    assert sched.stats.padded_lanes == bucket_pow2(5)
