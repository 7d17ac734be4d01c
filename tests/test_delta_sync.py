"""Incremental host->device delta snapshot sync (the PCIe-amortization
subsystem): equivalence with wholesale republish, threshold fallback,
O(writes) traffic scaling, sync policies, scheduler-batched sync, and the
Pallas scatter kernel."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import HoneycombConfig, HoneycombStore, OutOfOrderScheduler
from repro.core.keys import int_key

SMALL = HoneycombConfig(node_cap=16, log_cap=4, n_shortcuts=4)


def snapshots_equal(a, b) -> bool:
    def same(x, y):
        if x is None or y is None:          # e.g. no value image
            return x is None and y is None
        return bool(jnp.array_equal(x, y))
    return all(same(getattr(a, f), getattr(b, f)) for f in a._fields)


def apply_random_ops(store, oracle, rng, n):
    for _ in range(n):
        k = int_key(int(rng.integers(0, 200)))
        op = rng.random()
        if op < 0.55:
            v = bytes(rng.integers(65, 91, 8))
            store.put(k, v)
            oracle[k] = v
        elif op < 0.8:
            v = bytes(rng.integers(97, 123, 8))
            store.update(k, v)
            oracle[k] = v
        else:
            store.delete(k)
            oracle.pop(k, None)


def test_delta_equals_full_republish_after_random_ops():
    """The delta-synced resident snapshot is bit-identical to a wholesale
    republish after arbitrary put/update/delete mixes (including splits,
    underflow merges and GC wipes)."""
    store = HoneycombStore(SMALL, heap_capacity=256)
    oracle = {}
    rng = np.random.default_rng(7)
    store.export_snapshot()                      # first publish: full
    for round_ in range(8):
        apply_random_ops(store, oracle, rng, 40)
        if round_ % 3 == 2:                      # let GC wipe some rows too
            store.tree.epochs.cpu_begin(0)
            store.collect_garbage()
        snap = store.export_snapshot()
        full = store.export_snapshot(full=True)
        assert snapshots_equal(snap, full), f"round {round_}"
        # and the device path agrees with the host oracle
        keys = [int_key(i) for i in range(0, 200, 7)]
        assert store.get_batch(keys) == [oracle.get(k) for k in keys]
    assert store.sync_stats.delta_syncs > 0


def test_delta_traffic_scales_with_writes_not_store_size():
    """After a full export, W writes sync O(W) bytes, not O(S): the paper's
    log-block/PCIe-amortization claim, metered end to end."""
    store = HoneycombStore(HoneycombConfig(), heap_capacity=2048)
    for i in range(2000):
        store.put(int_key(i), b"v" * 8)
    store.export_snapshot()
    nodes = store.tree.heap.live_slots
    w = max(1, nodes // 10)

    deltas = []
    for mult in (1, 4):                          # growing write batches
        # stride the keys so each batch spreads over ~W*mult leaves
        for i in range(w * mult):
            store.update(int_key((i * 37) % 2000), b"u" * 8)
        b0 = store.sync_stats.bytes_synced
        store.export_snapshot()
        deltas.append(store.sync_stats.bytes_synced - b0)
        assert store.sync_stats.delta_fraction < 1.0
        b1 = store.sync_stats.bytes_synced
        store.export_snapshot(full=True)
        full_bytes = store.sync_stats.bytes_synced - b1
        assert deltas[-1] < 0.25 * full_bytes, (deltas[-1], full_bytes)
    assert deltas[1] > deltas[0]                 # more writes -> more bytes
    assert store.sync_stats.delta_syncs == 2


def test_threshold_falls_back_to_full_republish():
    """Dirty fraction above delta_full_threshold -> wholesale republish."""
    cfg = HoneycombConfig(node_cap=16, log_cap=4, n_shortcuts=4,
                          delta_full_threshold=0.02)
    store = HoneycombStore(cfg, heap_capacity=256)
    for i in range(150):
        store.put(int_key(i), b"v")
    store.export_snapshot()
    fulls = store.sync_stats.full_syncs
    for i in range(100):                          # touches >2% of rows
        store.update(int_key(i), b"u")
    store.export_snapshot()
    assert store.sync_stats.full_syncs == fulls + 1
    assert store.sync_stats.delta_syncs == 0
    # a single-row touch is under the threshold even at 2%
    store.update(int_key(0), b"w")
    store.export_snapshot()
    assert store.sync_stats.delta_syncs == 1


def test_heap_growth_forces_full_republish():
    """Array growth changes device shapes; the next sync must republish."""
    store = HoneycombStore(SMALL, heap_capacity=32)
    for i in range(20):
        store.put(int_key(i), b"v")
    store.export_snapshot()
    gen = store.tree.heap.generation
    for i in range(20, 400):                      # forces heap growth
        store.put(int_key(i), b"v")
    assert store.tree.heap.generation > gen
    fulls = store.sync_stats.full_syncs
    store.export_snapshot()
    assert store.sync_stats.full_syncs == fulls + 1
    # reads still correct after the republish
    assert store.get_batch([int_key(5), int_key(399)]) == [b"v", b"v"]


def test_sync_policy_every_k():
    cfg = HoneycombConfig(node_cap=16, log_cap=4, n_shortcuts=4,
                          sync_policy="every_k", sync_every_k=10)
    store = HoneycombStore(cfg, heap_capacity=256)
    for i in range(25):
        store.put(int_key(i), b"v")
    # 25 writes at K=10 -> 2 automatic syncs, remainder pending
    assert store.sync_stats.snapshots == 2
    store.export_snapshot()
    assert store.sync_stats.snapshots == 3


def test_sync_policy_explicit_reads_stale_snapshot():
    cfg = HoneycombConfig(node_cap=16, log_cap=4, n_shortcuts=4,
                          sync_policy="explicit")
    store = HoneycombStore(cfg, heap_capacity=256)
    for i in range(50):
        store.put(int_key(i), b"old")
    store.export_snapshot()
    store.update(int_key(0), b"new")
    # device read is stale-but-consistent until the explicit sync
    assert store.get_batch([int_key(0)]) == [b"old"]
    store.export_snapshot()
    assert store.get_batch([int_key(0)]) == [b"new"]


def test_scheduler_batches_writes_between_syncs():
    """scheduler.run(): many writes, ONE host->device sync, then reads —
    the paper's batched synchronization."""
    store = HoneycombStore(SMALL, heap_capacity=256)
    for i in range(100):
        store.put(int_key(i), b"v%d" % i)
    store.export_snapshot()
    snaps_before = store.sync_stats.snapshots
    sched = OutOfOrderScheduler(batch_size=8)
    write_rids = [sched.submit("update", int_key(i), value=b"w%d" % i)
                  for i in range(30)]
    write_rids.append(sched.submit("delete", int_key(99)))
    read_rids = {sched.submit("get", int_key(i)): i for i in range(0, 100, 9)}
    out = sched.run(store)
    assert sched.syncs == 1
    assert store.sync_stats.snapshots == snaps_before + 1
    assert all(out[r] is None for r in write_rids)
    for rid, i in read_rids.items():
        want = None if i == 99 else (b"w%d" % i if i < 30 else b"v%d" % i)
        assert out[rid] == want
    assert sched.applied_writes == 31


def test_scheduler_burst_defers_every_k_policy():
    """A scheduler write burst performs exactly ONE sync even when the
    store's own policy would sync every K writes mid-burst."""
    cfg = HoneycombConfig(node_cap=16, log_cap=4, n_shortcuts=4,
                          sync_policy="every_k", sync_every_k=4)
    store = HoneycombStore(cfg, heap_capacity=256)
    with store.deferred_sync():                  # quiet load phase
        for i in range(60):
            store.put(int_key(i), b"v")
    store.export_snapshot()
    snaps = store.sync_stats.snapshots
    sched = OutOfOrderScheduler()
    for i in range(30):                          # would trigger 7 every_k syncs
        sched.submit("update", int_key(i), value=b"w")
    rid = sched.submit("get", int_key(29))
    out = sched.run(store)
    assert store.sync_stats.snapshots == snaps + 1
    assert sched.syncs == 1
    assert out[rid] == b"w"


def test_pagetable_commands_accumulate_across_syncs():
    """Regression: multi-sync runs report cumulative PCIe command counts
    (they were overwritten per export)."""
    store = HoneycombStore(SMALL, heap_capacity=256)
    for i in range(100):
        store.put(int_key(i), b"v")
    store.export_snapshot()
    c1 = store.sync_stats.pagetable_commands
    r1 = store.sync_stats.read_version_updates
    assert c1 == store.tree.pt.sync_commands
    for i in range(100, 200):
        store.put(int_key(i), b"v")
    store.export_snapshot()
    assert store.sync_stats.pagetable_commands == store.tree.pt.sync_commands
    assert store.sync_stats.pagetable_commands > c1
    assert store.sync_stats.read_version_updates > r1


def test_old_snapshots_survive_delta_syncs():
    """Delta application is functional: snapshots held by in-flight batches
    keep answering at their read version (wait-free MVCC)."""
    from repro.core.keys import pack_keys
    from repro.core.read_path import batched_get
    cfg = SMALL
    store = HoneycombStore(cfg, heap_capacity=256)
    for i in range(50):
        store.put(int_key(i), b"old")
    old_snap = store.export_snapshot()
    for gen in range(3):                          # several delta syncs
        for i in range(50):
            store.update(int_key(i), b"new")
        store.export_snapshot()
    assert store.sync_stats.delta_syncs > 0
    lanes, lens = pack_keys([int_key(i) for i in range(50)], cfg.key_words)
    res = batched_get(old_snap, jnp.asarray(lanes), jnp.asarray(lens), cfg)
    vals = np.asarray(res.vals)
    assert bool(res.found.all())
    for i in range(50):
        assert vals[i].astype(">u4").tobytes()[:3] == b"old"


def test_delta_scatter_kernel_matches_ref():
    """Pallas interpret-mode scatter == jnp oracle (duplicate-row padding
    included)."""
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    dst = jnp.asarray(rng.integers(0, 2**31, (64, 12)).astype(np.uint32))
    rows = np.array([3, 17, 40, 40], np.int32)    # padded repeat
    upd = rng.integers(0, 2**31, (3, 12)).astype(np.uint32)
    upd = jnp.asarray(np.concatenate([upd, upd[-1:]]))
    want = ops.snapshot_delta_scatter(dst, jnp.asarray(rows), upd,
                                      backend="ref")
    got = ops.snapshot_delta_scatter(dst, jnp.asarray(rows), upd,
                                     backend="interpret")
    assert bool(jnp.array_equal(want, got))
