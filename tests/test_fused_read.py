"""Fused device-resident read path (kernels/fused_read.py + the VMEM
cache tier): interpret-mode kernel parity, answers through the whole
service stack equal to the CPU baseline store (with the sync invariants
those runs must keep), cache-frontier edge cases, the
stale-cache-after-remap regression, and the loud failure of a snapshot
without its cache tier."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.baselines.cpu_store import CpuOrderedStore
from repro.core import (Delete, Get, HoneycombConfig, HoneycombService, Put,
                        ReplicationConfig, Scan, ShardedHoneycombStore,
                        Update, uniform_int_boundaries)
from repro.core.api import NOT_FOUND, OK
from repro.core import shard as shard_mod
from repro.core.keys import int_key, pack_keys
from repro.core.read_path import GetResult
from repro.core.shard import StoreShard
from repro.kernels import ops, ref

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
def _leaf_of(s, key):
    """Heap row of the leaf the host tree puts ``key`` in."""
    return s.tree._traverse(*s.tree._pack(key))[-1].phys


def _leaf_log(s, key):
    """(key, op) of each log entry of ``key``'s leaf, in slot order."""
    h, phys = s.tree.heap, _leaf_of(s, key)
    return [(s.tree._key_bytes(h.log_keys[phys, j],
                               int(h.log_keylen[phys, j])),
             int(h.log_op[phys, j])) for j in range(int(h.nlog[phys]))]


def _flush_log(s, key):
    """Rewrite ``key`` until its leaf's log has merged, so the writes that
    follow land in an empty log."""
    for _ in range(s.cfg.log_cap + 1):
        if not _leaf_log(s, key):
            return
        s.update(key, s.tree.get(key, latest=True))
    raise AssertionError("the leaf's log never merged")


def _even_shard():
    """Even ids 2..240 loaded in order: odd ids fall between stored keys,
    0 and 1 below every one, ids above 240 above every one."""
    s = StoreShard(dataclasses.replace(SMALL, sync_policy="explicit"),
                   heap_capacity=512)
    for i in range(1, 121):
        s.put(int_key(2 * i), b"v%06d" % i)
    return s


ABSENT = [int_key(i) for i in (0, 1, 41, 151, 241, 10 ** 6)]


def _relog_tree():
    """Leaf logs that rewrite one key several times: update, delete and
    re-insert of a stored key, update then delete of another, and insert,
    delete, re-insert of a key the sorted block lacks.  The read versions
    are the current one and one pinned before those log entries."""
    s = _even_shard()
    ka, kb, kc = int_key(40), int_key(120), int_key(201)
    for k in (ka, kb, kc):
        _flush_log(s, k)
    s.export_snapshot()
    pinned = s.serving_version
    s.update(ka, b"a1")
    s.delete(ka)
    s.put(ka, b"a2")
    s.update(kb, b"b1")
    s.delete(kb)
    s.put(kc, b"c1")
    s.delete(kc)
    s.put(kc, b"c2")
    s.export_snapshot()
    for k, n in ((ka, 3), (kb, 2), (kc, 3)):
        assert [e for e, _ in _leaf_log(s, k)].count(k) == n
    keys = [ka, kb, kc, int_key(2), int_key(240)] + ABSENT
    return s, keys, [s.serving_version, pinned]


def _walk_left_tree():
    """A leaf whose first two sorted items are deleted in its log: the
    SCAN(K, K) spine finds no visible key <= K there and walks into the
    left sibling for its floor, and the point resolve answers from the
    leaf alone.  Pinned before the deletes, both keys are found."""
    s = _even_shard()
    h = s.tree.heap
    phys = _leaf_of(s, int_key(120))
    first = [s.tree._key_bytes(h.skeys[phys, i], int(h.skeylen[phys, i]))
             for i in range(3)]
    assert int.from_bytes(first[0], "big") > 2       # a left sibling exists
    _flush_log(s, first[2])
    s.export_snapshot()
    pinned = s.serving_version
    s.delete(first[0])
    s.delete(first[1])
    s.export_snapshot()
    between = int_key(int.from_bytes(first[0], "big") + 1)
    return s, first + [between] + ABSENT, [s.serving_version, pinned]


def _long_value_tree():
    """1,000-byte values, read from the snapshot's value image: loaded,
    rewritten, deleted, and one cut to an inline value."""
    cfg = dataclasses.replace(SMALL, sync_policy="explicit",
                              overflow_words=256)
    rng = np.random.default_rng(16)
    s = StoreShard(cfg, heap_capacity=256)

    def kb():
        return rng.integers(0, 256, 1000, dtype=np.uint8).tobytes()

    for i in range(1, 61):
        s.put(int_key(2 * i), kb())
    for i in (3, 17, 40):
        s.update(int_key(2 * i), kb())
    s.delete(int_key(2 * 9))
    s.update(int_key(2 * 21), b"short")
    s.export_snapshot()
    keys = [int_key(2 * i) for i in (1, 3, 9, 17, 21, 40, 60)] + ABSENT
    return s, keys, [s.serving_version]


def _loaded_tree():
    s = _loaded_shard(SMALL)
    snap = s.export_snapshot()
    keys = [int_key(i) for i in (1, 7, 13, 55, 119, 5000)]
    return s, keys, [int(snap.read_version)]


GET_TREES = {"loaded": _loaded_tree, "relog": _relog_tree,
             "walk_left": _walk_left_tree, "long_values": _long_value_tree}


def _gathered(sn, res, meters, cfg):
    """A GET batch's one packed buffer with its value rows appended, as
    the shard copies it back (``_pack_read`` + ``gather_values``)."""
    return np.asarray(shard_mod._jit_gather_values(
        shard_mod._pack_read(res, meters), sn.values,
        result_type=GetResult, cfg=cfg))


@pytest.mark.parametrize("lb_fraction,tree", [
    pytest.param(lb, tree, id=str(lb) if tree == "loaded" else f"{lb}-{tree}")
    for tree in GET_TREES for lb in (0.0, 0.25)])
def test_fused_get_interpret_matches_ref(lb_fraction, tree):
    """The GET kernel's point resolve answers what SCAN(K, K) plus the
    equality pass answers, at the current and a pinned read version, for
    stored keys, keys absent below, between and above them, keys whose
    leaf log rewrites them, keys whose leaf's first items are deleted, and
    1 KB values gathered from the value image."""
    s, keys, rvs = GET_TREES[tree]()
    cfg = s.cfg
    snap = s._snapshot
    lanes, lens = _packed(keys, cfg)
    for rv in rvs:
        sn = snap._replace(read_version=jnp.int32(rv))
        want, wm = ops.batched_get_fused(sn, lanes, lens, cfg=cfg,
                                         lb_fraction=lb_fraction,
                                         backend="ref")
        got, gm = ops.batched_get_fused(sn, lanes, lens, cfg=cfg,
                                        lb_fraction=lb_fraction,
                                        backend="interpret")
        for f in want._fields:
            np.testing.assert_array_equal(np.asarray(getattr(want, f)),
                                          np.asarray(getattr(got, f)),
                                          err_msg=f)
        np.testing.assert_array_equal(np.asarray(wm), np.asarray(gm))
        if rv != rvs[0]:
            continue
        # at the current version, the answers are the host tree's
        found = np.asarray(got.found)
        current = found
        assert [bool(x) for x in found] == [s.get(k) is not None
                                            for k in keys]
        if sn.values is not None:
            buf = _gathered(sn, got, gm, cfg)
            assert np.array_equal(buf, _gathered(sn, want, wm, cfg))
            (f, v, n), rows = s._fetch_read(buf, GetResult, len(keys), True)
            assert [s._decode_value(v[i], int(n[i]), rows, (i, 0))
                    if f[i] else None for i in range(len(keys))] \
                == [s.get(k) for k in keys]
            assert s.pipeline_stats.device_values >= 4
    if tree == "walk_left":
        # the SCAN spine found its floor in the left sibling
        sn = snap._replace(read_version=jnp.int32(rvs[0]))
        res, _ = ref.batched_scan_fused_ref(sn, lanes, lens, lanes, lens,
                                            cfg=cfg)
        assert np.asarray(res.count)[:3].tolist() == [1, 1, 1]
        assert current[:4].tolist() == [False, False, True, False]


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


@pytest.mark.parametrize("op,tree", [
    ("get", "loaded"), ("scan", "loaded"), ("get", "churned"),
    ("scan", "churned"), ("get", "relog"), ("get", "walk_left"),
    ("get", "long_values")])
def test_fused_tpu_interpreter_matches_ref(op, tree):
    """The TPU interpreter models DMAs, memory spaces and semaphores, fills
    scratch with garbage and raises on out-of-bounds reads — what a compiled
    kernel would read on the chip and the plain interpreter forgives.  Both
    pipes (lb 0.5), on a freshly loaded tree and on a churned tree at an
    older read version; GET also on the point-resolve trees of
    ``GET_TREES``, at every read version each gives."""
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels import fused_read
    cfg = SMALL
    rng = np.random.default_rng(100)
    los = rng.integers(0, 300, 11)
    lo = _packed([int_key(int(a)) for a in los], cfg)
    if tree == "loaded":
        sns = [_loaded_shard(cfg).export_snapshot()]
    elif tree == "churned":
        snap, rvs = _churned_snapshot(0)
        sns = [snap._replace(read_version=jnp.int32(rvs[1]))]
    else:
        s, keys, rvs = GET_TREES[tree]()
        cfg = s.cfg
        lo = _packed(keys, cfg)
        sns = [s._snapshot._replace(read_version=jnp.int32(rv))
               for rv in rvs]
    for sn in sns:
        args = (sn.image, sn.pagetable, sn.root_lid, sn.read_version,
                sn.cache_lids, sn.cache_image)
        if op == "get":
            want, wm = ref.batched_get_fused_ref(sn, *lo, cfg=cfg,
                                                 lb_fraction=0.5)
            got, gm = fused_read.batched_get_fused(
                *args, *lo, cfg=cfg, lb_fraction=0.5,
                interpret=pltpu.InterpretParams())
        else:
            spans = rng.choice([0, 3, 40, 400], 11)
            hi = _packed([int_key(int(a + b)) for a, b in zip(los, spans)],
                         cfg)
            want, wm = ref.batched_scan_fused_ref(sn, *lo, *hi, cfg=cfg,
                                                  lb_fraction=0.5)
            got, gm = fused_read.batched_scan_fused(
                *args, *lo, *hi, cfg=cfg, lb_fraction=0.5,
                interpret=pltpu.InterpretParams())
        for f in want._fields:
            np.testing.assert_array_equal(np.asarray(getattr(want, f)),
                                          np.asarray(getattr(got, f)),
                                          err_msg=f)
        np.testing.assert_array_equal(np.asarray(wm), np.asarray(gm))
        assert int(np.asarray(wm)[0]) > 0 and int(np.asarray(wm)[1]) > 0


# ------------------------------------ fused answers ≡ CPU baseline, service
def _mixed_ops(rng, n, key_space, value):
    """A seeded put/update/delete/get/scan mix over ``key_space`` ids."""
    ops = []
    for _ in range(n):
        k = int(rng.integers(0, key_space))
        draw = rng.random()
        if draw < 0.2:
            ops.append(Put(int_key(k), value(k, "p")))
        elif draw < 0.3:
            ops.append(Update(int_key(k), value(k, "u")))
        elif draw < 0.4:
            ops.append(Delete(int_key(k)))
        elif draw < 0.75:
            ops.append(Get(int_key(k)))
        else:
            ops.append(Scan(int_key(k), int_key(min(k + 5, key_space - 1)),
                            expected_items=6))
    return ops


@pytest.mark.parametrize("values", ["inline", "long"])
@pytest.mark.parametrize("shards", [1, 3])
@pytest.mark.parametrize("replicas", [1, 2])
@pytest.mark.parametrize("pipeline", ["serial", "pipelined"])
def test_fused_matches_reference_end_to_end(shards, replicas, pipeline,
                                            values):
    """Randomized epochs through ``HoneycombService`` across shards x
    replicas x pipeline modes, with values inline or longer than the 16
    inline bytes: every response's status, value and items equal the CPU
    baseline store's (each epoch's writes in submission order, then its
    reads).  The syncs keep their invariants: one image DMA per dirty node
    (a full publish is one whole-image DMA), the delta path taken, and
    follower feed bytes moved when there are followers."""
    n_items = 200
    rng = np.random.default_rng(shards * 10 + replicas + len(pipeline)
                                + len(values))

    def value(k, tag):
        v = b"%s%06d" % (tag.encode(), k)
        return v * 6 if values == "long" else v

    st = ShardedHoneycombStore(
        SMALL, heap_capacity=256, shards=shards,
        boundaries=uniform_int_boundaries(n_items, shards),
        replication=ReplicationConfig(replicas=replicas,
                                      policy="round_robin"))
    ref = CpuOrderedStore(node_cap=16)
    for i in rng.permutation(n_items):
        st.put(int_key(int(i)), value(int(i), "w"))
        ref.put(int_key(int(i)), value(int(i), "w"))
    st.export_snapshot()
    svc = HoneycombService(st, batch_size=16, pipeline=pipeline)
    for round_ in range(3):
        ops = _mixed_ops(rng, 60, n_items, value)
        tickets = svc.submit_many(ops)
        svc.drain()
        for op in ops:
            if isinstance(op, Delete):
                ref.delete(op.key)
            elif op.IS_WRITE:
                ref.put(op.key, op.value)
        for op, t in zip(ops, tickets):
            r = t.result()
            if isinstance(op, Get):
                v = ref.get(op.key)
                want = (OK if v is not None else NOT_FOUND, v, None)
            elif isinstance(op, Scan):
                want = (OK, None, ref.scan(op.lo, op.hi))
            else:
                want = (OK, None, None)
            assert (r.status, r.value, r.items) == want, (round_, op)
    s = st.sync_stats
    assert s.delta_syncs > 0                      # delta path exercised
    assert s.image_dma_count == s.full_syncs + s.delta_rows
    if replicas > 1:
        assert st.replication_bytes > 0


@pytest.mark.parametrize("feed", ["log", "delta"])
def test_followers_serve_fused_from_shipped_cache(feed):
    """Followers inherit the cache tier through BOTH feeds (delta applies
    re-attach it; log replays rebuild it from the replayed image) and
    their fused reads answer what the primary's host tree does."""
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
        assert got == [grp.primary.get(k) for k in probe]


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


# ------------------------------------------- a snapshot without its tier
@pytest.mark.parametrize("op", ["get", "scan"])
def test_snapshot_without_cache_tier_fails_loudly(op):
    """Every staging attaches the cache tier; a snapshot that lacks it is a
    bug, and the read dispatch raises rather than serve it some other
    way."""
    s = _loaded_shard(SMALL)
    snap = s.export_snapshot()._replace(cache_image=None)
    keys = [int_key(i) for i in range(1, 9)]
    with pytest.raises(RuntimeError, match="without its cache tier"):
        if op == "get":
            s._device_get(snap, keys)
        else:
            s._device_scan(snap, [(keys[0], keys[3])], None)
