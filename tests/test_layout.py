"""Packed node-image layout (core/schema.py): golden word offsets pinning
the image format, pack/unpack/device-view roundtrips, the schema-derived
field lists (no re-enumeration anywhere), the one-image-DMA-per-dirty-node
accounting invariant, randomized packed==legacy op-for-op equivalence
(results AND sync byte counts) across shards x replicas x pipeline modes,
and image-scatter / in-image key-search kernel parity."""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (FIELD_NAMES, NARROWED_FIELDS, NODE_SCHEMA,
                        DEFAULT_CONFIG, HoneycombConfig, HoneycombStore,
                        NodeImageLayout, OutOfOrderScheduler,
                        ReplicationConfig, ShardedHoneycombStore,
                        uniform_int_boundaries)
from repro.core.keys import int_key

SMALL = HoneycombConfig(node_cap=16, log_cap=4, n_shortcuts=4)


def small(layout):
    return dataclasses.replace(SMALL, layout=layout)


# ------------------------------------------------------------ golden layout
# The packed image format is a wire contract: every field's (word_offset,
# width) inside the default config's image row, in NODE_SCHEMA order.
GOLDEN_DEFAULT_OFFSETS = {
    "ntype": (0, 1), "nitems": (1, 1), "version": (2, 1), "oldptr": (3, 1),
    "left_child": (4, 1), "lsib": (5, 1), "rsib": (6, 1),
    "skeys": (7, 512), "skeylen": (519, 64), "svals": (583, 256),
    "svallen": (839, 64), "n_shortcuts": (903, 1), "sc_keys": (904, 64),
    "sc_keylen": (968, 8), "sc_pos": (976, 8), "nlog": (984, 1),
    "log_keys": (985, 128), "log_keylen": (1113, 16), "log_vals": (1129, 64),
    "log_vallen": (1193, 16), "log_op": (1209, 16), "log_backptr": (1225, 16),
    "log_hint": (1241, 16), "log_vdelta": (1257, 16),
}


def test_golden_offsets_pinned():
    """The default-config image layout is pinned word for word — 1273 field
    words (5092 B, the reproduction's analogue of the paper's 8 KB node),
    in a device row padded to 1280 words (the TPU's 128-lane tile)."""
    layout = NodeImageLayout.for_config(DEFAULT_CONFIG)
    assert layout.offsets() == GOLDEN_DEFAULT_OFFSETS
    assert layout.field_words == 1273
    assert layout.image_words == 1280
    assert layout.node_image_bytes == 5092
    # fields tile the row's head exactly: in schema order, no gaps
    assert list(layout.offsets()) == list(FIELD_NAMES)
    off = 0
    for name in FIELD_NAMES:
        o, w = layout.offsets()[name]
        assert o == off, name
        off += w
    assert off == layout.field_words
    # the test geometry used across the suite
    assert NodeImageLayout.for_config(SMALL).field_words == 345
    assert NodeImageLayout.for_config(SMALL).image_words == 384


def test_field_lists_derive_from_schema():
    """Heap allocation, snapshot publishing and the device-narrowing table
    all share the ONE schema — no hand-kept field list survives."""
    from repro.core.heap import NodeHeap
    from repro.core.read_path import NODE_FIELDS
    from repro.core.shard import _I32_FIELDS
    assert NodeHeap.ARRAY_FIELDS == FIELD_NAMES
    assert NODE_FIELDS == FIELD_NAMES
    assert _I32_FIELDS is NARROWED_FIELDS
    assert NARROWED_FIELDS == {"version", "log_op", "log_hint", "log_vdelta"}
    assert all(f.device in ("uint32", "int32") for f in NODE_SCHEMA)


def test_pack_unpack_view_roundtrip():
    """pack() -> unpack() is the identity (in device dtypes) on a live
    heap, and the device view() decodes every field identically —
    including NULL = -1 surviving the u32 transit of signed fields."""
    st = HoneycombStore(small("packed"), heap_capacity=256)
    rng = np.random.default_rng(3)
    for i in range(120):
        st.put(int_key(i), bytes(rng.integers(65, 91, 8)))
    for i in range(0, 120, 3):
        st.delete(int_key(i))
    h = st.tree.heap
    layout = NodeImageLayout.for_config(st.cfg)
    img = layout.pack(h)
    fields = layout.unpack(img)
    dimg = jnp.asarray(img)
    for spec in NODE_SCHEMA:
        want = getattr(h, spec.name).astype(spec.device)
        assert np.array_equal(fields[spec.name], want), spec.name
        assert np.array_equal(np.asarray(layout.view(dimg, spec.name)),
                              want), spec.name
    assert (h.rsib == -1).any()                  # NULLs actually exercised
    assert np.array_equal(fields["rsib"] == -1, h.rsib == -1)
    # row subsets pack the same bytes as the full image
    rows = np.array([0, 5, 9], np.int32)
    assert np.array_equal(layout.pack(h, rows), img[rows])


# --------------------------------------------------- the DMA-count invariant
def test_delta_sync_is_one_image_dma_per_dirty_node():
    """THE acceptance invariant: on the packed layout a delta sync issues
    exactly ONE contiguous image-row DMA per dirty node (a full publish is
    one whole-image DMA), metered end to end by SyncStats."""
    st = HoneycombStore(small("packed"), heap_capacity=256)
    layout = NodeImageLayout.for_config(st.cfg)
    for i in range(100):
        st.put(int_key(i), b"v")
    st.export_snapshot()                          # first publish: full
    assert st.sync_stats.full_syncs == 1
    assert st.sync_stats.image_dma_count == 1     # ONE whole-image DMA
    assert st.sync_stats.image_bytes == \
        st.tree.heap.capacity * layout.node_image_bytes
    for rnd in range(3):
        d0, b0 = st.sync_stats.image_dma_count, st.sync_stats.image_bytes
        for i in range(rnd * 7, rnd * 7 + 5):
            st.update(int_key(i), b"u%d" % rnd)
        st.export_snapshot()
        dmas = st.sync_stats.image_dma_count - d0
        dirty = (st.sync_stats.image_bytes - b0) // layout.node_image_bytes
        assert st.sync_stats.delta_syncs == rnd + 1
        assert dmas == dirty > 0, (dmas, dirty)   # one DMA per dirty node
    # legacy on the same traffic: one DMA per FIELD per node
    lg = HoneycombStore(small("legacy"), heap_capacity=256)
    for i in range(100):
        lg.put(int_key(i), b"v")
    lg.export_snapshot()
    assert lg.sync_stats.image_dma_count == len(FIELD_NAMES)
    assert lg.sync_stats.image_bytes == st.sync_stats.image_bytes - \
        (st.sync_stats.image_dma_count - 1) * layout.node_image_bytes


# ------------------------------------------------- packed == legacy, op for op
@pytest.mark.parametrize("shards", [1, 3])
@pytest.mark.parametrize("replicas", [1, 2])
@pytest.mark.parametrize("pipeline", ["serial", "pipelined"])
def test_packed_equals_legacy_randomized(shards, replicas, pipeline):
    """Randomized mixed workloads: the packed layout returns the same
    responses AND the same sync accounting as the legacy per-field layout
    across shards x replicas x pipeline modes.  Every SyncStats counter
    matches except image_dma_count — the counter the refactor collapses
    (one per dirty node instead of one per field per node)."""
    bnd = uniform_int_boundaries(200, shards) if shards > 1 else None
    repl = ReplicationConfig(replicas=replicas,
                             policy="round_robin" if replicas > 1
                             else "primary_only")
    stores, scheds = [], []
    for layout in ("packed", "legacy"):
        s = ShardedHoneycombStore(small(layout), heap_capacity=256,
                                  shards=shards, boundaries=bnd,
                                  replication=repl)
        stores.append(s)
        scheds.append(OutOfOrderScheduler(batch_size=8, routing=s.routing(),
                                          pipeline=pipeline))
    pk, lg = stores
    rng = np.random.default_rng(42)
    from test_pipeline_engine import submit_random_mixed
    for round_ in range(3):
        submit_random_mixed(scheds, rng, 60)
        out_p = scheds[0].run(pk)
        out_l = scheds[1].run(lg)
        assert out_p == out_l, round_
        sp = dataclasses.asdict(pk.sync_stats)
        sl = dataclasses.asdict(lg.sync_stats)
        # the DMA count is the one deliberate difference
        assert sp.pop("image_dma_count") < sl.pop("image_dma_count")
        assert sp == sl, round_
        assert pk.replication_bytes == lg.replication_bytes, round_
    assert pk.sync_stats.delta_syncs > 0          # delta path exercised
    assert pk.sync_stats.image_bytes == lg.sync_stats.image_bytes > 0
    if replicas > 1:
        assert pk.replication_bytes > 0


def test_direct_store_packed_equals_legacy():
    """No scheduler in the way: direct put/get/scan/delete + export on both
    layouts, same results, same bytes_synced."""
    pk = HoneycombStore(small("packed"), heap_capacity=256)
    lg = HoneycombStore(small("legacy"), heap_capacity=256)
    oracle = {}
    rng = np.random.default_rng(11)
    for round_ in range(4):
        for _ in range(50):
            k = int_key(int(rng.integers(0, 150)))
            r = rng.random()
            if r < 0.6:
                v = bytes(rng.integers(65, 91, 8))
                pk.put(k, v), lg.put(k, v)
                oracle[k] = v
            else:
                pk.delete(k), lg.delete(k)
                oracle.pop(k, None)
        keys = [int_key(i) for i in range(0, 150, 7)]
        assert pk.get_batch(keys) == lg.get_batch(keys) \
            == [oracle.get(k) for k in keys]
        ranges = [(int_key(a), int_key(a + 9)) for a in range(0, 140, 23)]
        assert pk.scan_batch(ranges) == lg.scan_batch(ranges)
        pk.export_snapshot()
        lg.export_snapshot()
        assert pk.sync_stats.bytes_synced == lg.sync_stats.bytes_synced


# ------------------------------------------------------------ kernel parity
def test_image_scatter_kernel_matches_ref():
    """snapshot_image_scatter interpret-mode == jnp oracle, duplicate
    (bucket-padded) rows included."""
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    image = jnp.asarray(rng.integers(0, 2**32, (64, 345), np.int64)
                        .astype(np.uint32))
    rows = jnp.asarray(np.array([2, 31, 63, 63], np.int32))  # padded repeat
    upd = rng.integers(0, 2**32, (3, 345), np.int64).astype(np.uint32)
    upd = jnp.asarray(np.concatenate([upd, upd[-1:]]))
    want = ops.snapshot_image_scatter(image, rows, upd, backend="ref")
    got = ops.snapshot_image_scatter(image, rows, upd, backend="interpret")
    assert bool(jnp.array_equal(want, got))


def test_key_search_image_kernel_matches_ref():
    """In-image floor search (candidate block sliced from packed image rows
    at static layout offsets) interpret-mode == jnp oracle."""
    from repro.core.keys import pack_keys
    from repro.kernels import ops
    cfg = SMALL
    layout = NodeImageLayout.for_config(cfg)
    rng = np.random.default_rng(7)
    B, kw = 8, cfg.key_words
    img = rng.integers(0, 2**32, (B, layout.image_words), np.int64) \
        .astype(np.uint32)
    sk, _ = layout.offsets()["skeys"]
    kl, _ = layout.offsets()["skeylen"]
    ct, _ = layout.offsets()["nitems"]
    # plant sorted candidate keys + sane lengths/counts in each image row
    for b in range(B):
        keys = sorted(rng.integers(65, 91, 6, dtype=np.uint8).tobytes()
                      for _ in range(cfg.node_cap))
        lanes, lens = pack_keys(keys, kw)
        img[b, sk:sk + cfg.node_cap * kw] = lanes.reshape(-1)
        img[b, kl:kl + cfg.node_cap] = lens.astype(np.uint32)
        img[b, ct] = rng.integers(1, cfg.node_cap + 1)
    q, qlen = pack_keys([rng.integers(65, 91, 6, dtype=np.uint8).tobytes()
                         for _ in range(B)], kw)
    kw_args = dict(keys_off=sk, lens_off=kl, count_off=ct,
                   n_keys=cfg.node_cap, key_words=kw)
    want = ops.key_search_image(jnp.asarray(q), jnp.asarray(qlen),
                                jnp.asarray(img), backend="ref", **kw_args)
    got = ops.key_search_image(jnp.asarray(q), jnp.asarray(qlen),
                               jnp.asarray(img), backend="interpret",
                               **kw_args)
    assert bool(jnp.array_equal(want, got))
    assert int(jnp.max(want)) >= 0               # some floors actually found
