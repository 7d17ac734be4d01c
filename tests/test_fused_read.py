"""Fused device-resident read path (kernels/fused_read.py + the VMEM
cache tier): interpret-mode kernel parity, fused ≡ reference equivalence
through the whole service stack (results AND serving-version stamps),
cache-frontier edge cases, the stale-cache-after-remap regression, and
the dispatched-launch meter pins."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Get, HoneycombConfig, HoneycombService,
                        HoneycombStore, ReplicationConfig, Scan,
                        ShardedHoneycombStore, Update,
                        uniform_int_boundaries)
from repro.core.keys import int_key, pack_keys
from repro.core.shard import StoreShard
from repro.kernels import ops

SMALL = HoneycombConfig(node_cap=16, log_cap=4, n_shortcuts=4,
                        cache_slots=32, max_scan_leaves=2,
                        max_scan_items=16, max_height=6)


def _loaded_shard(cfg, n=120, heap_capacity=256):
    s = StoreShard(cfg, heap_capacity=heap_capacity)
    for i in range(n):
        s.put(int_key(i), b"v%06d" % i)
    for i in range(0, n, 7):
        s.update(int_key(i), b"u%06d" % i)
    for i in range(0, n, 13):
        s.delete(int_key(i))
    return s


def _packed(keys, cfg):
    lanes, lens = pack_keys(keys, cfg.key_words)
    return jnp.asarray(lanes), jnp.asarray(lens)


# ------------------------------------------------- interpret ≡ ref parity
@pytest.mark.parametrize("lb_fraction", [0.0, 0.25])
def test_fused_get_interpret_matches_ref(lb_fraction):
    cfg = SMALL
    snap = _loaded_shard(cfg).export_snapshot()
    keys = [int_key(i) for i in (1, 7, 13, 55, 119, 5000)]
    lanes, lens = _packed(keys, cfg)
    want, wm = ops.batched_get_fused(snap, lanes, lens, cfg=cfg,
                                     lb_fraction=lb_fraction, backend="ref")
    got, gm = ops.batched_get_fused(snap, lanes, lens, cfg=cfg,
                                    lb_fraction=lb_fraction,
                                    backend="interpret")
    for f in want._fields:
        np.testing.assert_array_equal(np.asarray(getattr(want, f)),
                                      np.asarray(getattr(got, f)),
                                      err_msg=f)
    np.testing.assert_array_equal(np.asarray(wm), np.asarray(gm))


@pytest.mark.parametrize("lb_fraction", [0.0, 0.25])
def test_fused_scan_interpret_matches_ref(lb_fraction):
    cfg = SMALL
    snap = _loaded_shard(cfg).export_snapshot()
    los = [int_key(i) for i in (0, 5, 40, 110)]
    his = [int_key(i) for i in (4, 9, 55, 400)]
    a = _packed(los, cfg) + _packed(his, cfg)
    want, wm = ops.batched_scan_fused(snap, *a, cfg=cfg,
                                      lb_fraction=lb_fraction, backend="ref")
    got, gm = ops.batched_scan_fused(snap, *a, cfg=cfg,
                                     lb_fraction=lb_fraction,
                                     backend="interpret")
    for f in want._fields:
        np.testing.assert_array_equal(np.asarray(getattr(want, f)),
                                      np.asarray(getattr(got, f)),
                                      err_msg=f)
    np.testing.assert_array_equal(np.asarray(wm), np.asarray(gm))


def _churned_snapshot(seed):
    """A snapshot with multi-level interior nodes, leaf logs, deletes and
    MVCC old versions still on the heap, plus the read versions seen
    along the way (older read versions make newer nodes and log entries
    invisible, so the kernel's version walk and log filter both run)."""
    rng = np.random.default_rng(seed)
    s = StoreShard(dataclasses.replace(SMALL, sync_policy="explicit"),
                   heap_capacity=512)
    rvs = []
    for rnd in range(4):
        for k in rng.integers(0, 300, 90):
            k = int(k)
            if rng.random() < 0.2:
                s.delete(int_key(k))
            else:
                s.put(int_key(k), b"r%d_%05d" % (rnd, k))
        s.export_snapshot()
        rvs.append(s.serving_version)
    return s._snapshot, rvs


@pytest.mark.parametrize("seed", [0, 1])
def test_fused_interpret_matches_ref_under_churn(seed):
    """Interpret ≡ ref on a churned tree, at the current and at older read
    versions, for a batch that is not a multiple of the 8-request block:
    GETs of present and absent keys, SCANs from empty to truncated."""
    cfg = SMALL
    snap, rvs = _churned_snapshot(seed)
    rng = np.random.default_rng(100 + seed)
    keys = [int_key(int(k)) for k in rng.integers(0, 320, 13)]
    los = rng.integers(0, 300, 11)
    spans = rng.choice([0, 3, 40, 400], 11)
    lo = _packed([int_key(int(a)) for a in los], cfg)
    hi = _packed([int_key(int(a + b)) for a, b in zip(los, spans)], cfg)
    for rv in (rvs[-1], rvs[1]):
        sn = snap._replace(read_version=jnp.int32(rv))
        for lb in (0.0, 0.5):
            want, wm = ops.batched_get_fused(sn, *_packed(keys, cfg),
                                             cfg=cfg, lb_fraction=lb,
                                             backend="ref")
            got, gm = ops.batched_get_fused(sn, *_packed(keys, cfg),
                                            cfg=cfg, lb_fraction=lb,
                                            backend="interpret")
            for f in want._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(want, f)),
                    np.asarray(getattr(got, f)), err_msg=f)
            np.testing.assert_array_equal(np.asarray(wm), np.asarray(gm))
            want, wm = ops.batched_scan_fused(sn, *lo, *hi, cfg=cfg,
                                              lb_fraction=lb, backend="ref")
            got, gm = ops.batched_scan_fused(sn, *lo, *hi, cfg=cfg,
                                             lb_fraction=lb,
                                             backend="interpret")
            for f in want._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(want, f)),
                    np.asarray(getattr(got, f)), err_msg=f)
            np.testing.assert_array_equal(np.asarray(wm), np.asarray(gm))
    assert bool(np.asarray(want.truncated).any())     # budgets exercised
    assert int(np.asarray(wm)[1]) > 0                 # heap pipe exercised


@pytest.mark.parametrize("tree", ["loaded", "churned"])
@pytest.mark.parametrize("op", ["get", "scan"])
def test_fused_tpu_interpreter_matches_ref(op, tree):
    """The TPU interpreter models DMAs, memory spaces and semaphores, fills
    scratch with garbage and raises on out-of-bounds reads — what a compiled
    kernel would read on the chip and the plain interpreter forgives.  Both
    pipes (lb 0.5), on a freshly loaded tree and on a churned tree at an
    older read version."""
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels import fused_read, ref
    cfg = SMALL
    if tree == "loaded":
        sn = _loaded_shard(cfg).export_snapshot()
    else:
        snap, rvs = _churned_snapshot(0)
        sn = snap._replace(read_version=jnp.int32(rvs[1]))
    args = (sn.image, sn.pagetable, sn.root_lid, sn.read_version,
            sn.cache_lids, sn.cache_image)
    rng = np.random.default_rng(100)
    los = rng.integers(0, 300, 11)
    lo = _packed([int_key(int(a)) for a in los], cfg)
    if op == "get":
        want, wm = ref.batched_get_fused_ref(sn, *lo, cfg=cfg,
                                             lb_fraction=0.5)
        got, gm = fused_read.batched_get_fused(
            *args, *lo, cfg=cfg, lb_fraction=0.5,
            interpret=pltpu.InterpretParams())
    else:
        spans = rng.choice([0, 3, 40, 400], 11)
        hi = _packed([int_key(int(a + b)) for a, b in zip(los, spans)], cfg)
        want, wm = ref.batched_scan_fused_ref(sn, *lo, *hi, cfg=cfg,
                                              lb_fraction=0.5)
        got, gm = fused_read.batched_scan_fused(
            *args, *lo, *hi, cfg=cfg, lb_fraction=0.5,
            interpret=pltpu.InterpretParams())
    for f in want._fields:
        np.testing.assert_array_equal(np.asarray(getattr(want, f)),
                                      np.asarray(getattr(got, f)), err_msg=f)
    np.testing.assert_array_equal(np.asarray(wm), np.asarray(gm))
    assert int(np.asarray(wm)[0]) > 0 and int(np.asarray(wm)[1]) > 0


# -------------------------------------- fused ≡ reference, full service
@pytest.mark.parametrize("shards", [1, 3])
@pytest.mark.parametrize("replicas", [1, 2])
@pytest.mark.parametrize("pipeline", ["serial", "pipelined"])
def test_fused_matches_reference_end_to_end(shards, replicas, pipeline):
    """Randomized op stream through the typed service on two identically
    loaded stores — fused vs reference backends must agree op-for-op on
    results AND serving-version/replica-visible stamps."""
    n_items = 200
    rng = np.random.default_rng(shards * 10 + replicas + len(pipeline))
    order = rng.permutation(n_items)

    def build(rb):
        st = ShardedHoneycombStore(
            dataclasses.replace(SMALL, read_backend=rb),
            heap_capacity=256, shards=shards,
            boundaries=uniform_int_boundaries(n_items, shards),
            replication=ReplicationConfig(replicas=replicas,
                                          policy="round_robin"))
        for i in order:
            st.put(int_key(int(i)), b"w%06d" % int(i))
        st.export_snapshot()
        return st

    opstream = []
    for k in rng.integers(0, n_items, 60):
        k = int(k)
        draw = rng.random()
        if draw < 0.15:
            opstream.append(Update(int_key(k), b"z%06d" % k))
        elif draw < 0.6:
            opstream.append(Get(int_key(k)))
        else:
            opstream.append(Scan(int_key(k),
                                 int_key(min(k + 5, n_items - 1)),
                                 expected_items=6))
    stamps = {}
    for rb in ("fused", "reference"):
        svc = HoneycombService(build(rb), batch_size=16, pipeline=pipeline)
        tickets = svc.submit_many(opstream)
        svc.drain()
        rs = [t.result() for t in tickets]
        stamps[rb] = [(r.status, r.value, r.items, r.serving_version)
                      for r in rs]
    assert stamps["fused"] == stamps["reference"]


@pytest.mark.parametrize("feed", ["log", "delta"])
def test_followers_serve_fused_from_shipped_cache(feed):
    """Followers inherit the cache tier through BOTH feeds (delta applies
    re-attach it; log replays rebuild it from the replayed image) and
    their fused reads match the cache-less reference fallback."""
    n_items = 160
    st = ShardedHoneycombStore(
        SMALL, heap_capacity=256, shards=1,
        boundaries=uniform_int_boundaries(n_items, 1),
        replication=ReplicationConfig(replicas=2, policy="round_robin",
                                      feed=feed))
    for i in range(n_items):
        st.put(int_key(i), b"v" * 12)
    st.export_snapshot()
    # flush the load epoch's leaf logs so the next epochs are replayable
    for _ in range(5):
        st.update(int_key(3), b"m" * 8)
    st.export_snapshot()
    # several small feed epochs (<= log_cap writes per leaf: the log feed
    # ships+replays them; the delta feed moves dirty image rows)
    for r in range(3):
        for i in (3, 50, 120):
            st.update(int_key(i), b"u%d" % r * 2)
        st.export_snapshot()
    grp = st.shards[0]
    if feed == "log":
        assert sum(f.sync_stats.log_replays for f in grp.followers) > 0
    for f in grp.followers:
        assert f.snapshot is not None
        assert f.snapshot.cache_lids is not None
        assert f.snapshot.cache_image is not None
        probe = [int_key(i) for i in range(0, n_items, 11)]
        v0 = grp.primary.cache.stats.vmem_hits
        got = grp.primary._device_get(f.snapshot, probe)
        assert grp.primary.cache.stats.vmem_hits > v0
        ref = grp.primary._device_get(
            f.snapshot._replace(cache_image=None), probe)
        assert got == ref


# --------------------------------------------- cache-frontier edge cases
def test_root_only_tree_serves_entirely_from_cache():
    """A tree short enough to fit whole inside the cached frontier (here:
    a single root leaf) resolves every descend level from VMEM — zero
    heap gathers."""
    s = StoreShard(SMALL, heap_capacity=64)
    for i in range(5):
        s.put(int_key(i), b"tiny")
    out = s.get_batch([int_key(i) for i in range(5)] + [int_key(99)])
    assert out == [b"tiny"] * 5 + [None]
    st = s.cache.stats
    assert st.vmem_hits > 0
    assert st.heap_gathers == 0


def test_cache_levels_beyond_tree_height():
    """cfg.cache_levels taller than the tree: the frontier walk stops at
    the leaves and fused reads still answer correctly."""
    cfg = dataclasses.replace(SMALL, cache_levels=5)
    s = _loaded_shard(cfg)
    host = {k: s.get(k) for k in (int_key(1), int_key(55), int_key(119))}
    got = s.get_batch(list(host))
    assert got == list(host.values())
    assert s.cache.stats.vmem_hits > 0


def test_partial_level_never_cached():
    """The frontier refuses a level that does not fit whole: cache
    membership stays decidable from the LID vector, and the fused path
    falls through to the heap for the uncached levels."""
    cfg = dataclasses.replace(SMALL, cache_slots=4, cache_ways=2)
    s = _loaded_shard(cfg, n=120)
    snap = s.export_snapshot()
    lids = np.asarray(snap.cache_lids)
    assert (lids != -1).sum() >= 1          # at least the root
    host = {k: s.get(k) for k in (int_key(2), int_key(77))}
    assert s.get_batch(list(host)) == list(host.values())
    assert s.cache.stats.heap_gathers > 0   # below-frontier levels


# ------------------------------------------ stale-cache-after-remap fix
def test_remap_invalidates_interior_cache():
    """Section 5 rule: a page-table command for a LID invalidates that
    LID's cache entry — a remapped LID can never serve a stale cached
    physical address from the metadata table."""
    s = StoreShard(SMALL, heap_capacity=256)
    assert s.tree.pt.on_remap is not None   # wired at construction
    for i in range(40):
        s.put(int_key(i), b"v" * 8)
    lid = s.tree.root_lid
    phys = s.tree.pt.lookup(lid)
    s.cache.lookup(lid, phys)               # warm the metadata entry
    inv0 = s.cache.stats.invalidations
    s.tree.pt.remap(lid, phys)              # the remap command itself
    assert s.cache.stats.invalidations == inv0 + 1
    row = s.cache._set_of(lid)
    assert lid not in s.cache.tag[row]      # entry dropped, not stale
    # free_lid is a page-table command too
    s.cache.lookup(lid, s.tree.pt.lookup(lid))
    inv1 = s.cache.stats.invalidations
    s.tree.pt.free_lid(lid)
    assert s.cache.stats.invalidations == inv1 + 1
    s.tree.pt.remap(lid, phys)              # restore for sanity


def test_reads_stay_correct_across_structural_churn():
    """End-to-end stale-cache regression: splits/merges remap LIDs
    between exports; fused reads after each export must match the host
    tree (the cache frontier re-attaches per staging, the metadata table
    invalidates per remap)."""
    s = StoreShard(SMALL, heap_capacity=512)
    live = {}
    rng = np.random.default_rng(3)
    for round_ in range(4):
        for i in rng.integers(0, 400, 60):
            k = int_key(int(i))
            v = b"r%d_%06d" % (round_, int(i))
            s.put(k, v)
            live[k] = v
        probe = list(live)[:: max(len(live) // 20, 1)]
        got = s.get_batch(probe)
        assert got == [live[k] for k in probe]


# ------------------------------------------------ dispatch-launch meter
def test_read_dispatch_counts():
    cfg = SMALL
    assert ops.read_dispatch_count("get", "fused", cfg) == 1
    assert ops.read_dispatch_count("scan", "fused", cfg) == 1
    ref_scan = cfg.max_height + 2 * cfg.max_scan_leaves
    assert ops.read_dispatch_count("scan", "reference", cfg) == ref_scan
    assert ops.read_dispatch_count("get", "reference", cfg) == ref_scan + 1


def test_shard_meters_fused_dispatches():
    """Acceptance: the fused path issues <= 2 device dispatches per read
    batch, measured by the launch meter at the shard dispatch site."""
    ops.reset_read_dispatches()
    s = _loaded_shard(SMALL)
    s.get_batch([int_key(1), int_key(2)])
    s.scan_batch([(int_key(1), int_key(9))])
    st = ops.read_dispatch_stats()
    assert st["get_fused"]["per_batch"] <= 2
    assert st["scan_fused"]["per_batch"] <= 2
    # the reference path pays per-stage launches
    ops.reset_read_dispatches()
    r = StoreShard(dataclasses.replace(SMALL, read_backend="reference"),
                   heap_capacity=256)
    for i in range(40):
        r.put(int_key(i), b"v" * 8)
    r.get_batch([int_key(1)])
    st = ops.read_dispatch_stats()
    assert st["get_reference"]["per_batch"] > 2
    ops.reset_read_dispatches()


def test_legacy_layout_falls_back_to_reference():
    """cfg.layout="legacy" snapshots carry no cache tier: the shard must
    dispatch reads through the reference path (and still answer right)."""
    cfg = dataclasses.replace(SMALL, layout="legacy")
    ops.reset_read_dispatches()
    s = _loaded_shard(cfg)
    out = s.get_batch([int_key(1), int_key(118)])
    assert out == [s.get(int_key(1)), s.get(int_key(118))]
    st = ops.read_dispatch_stats()
    assert "get_reference" in st and "get_fused" not in st
    ops.reset_read_dispatches()
