"""Packed node-image layout (core/schema.py): golden word offsets pinning
the image format, pack/unpack/device-view roundtrips, the schema-derived
field lists (no re-enumeration anywhere), the one-image-DMA-per-dirty-node
accounting invariant, the direct store against the CPU baseline, and
in-image key-search kernel parity."""
import numpy as np

import jax.numpy as jnp

from repro.baselines.cpu_store import CpuOrderedStore
from repro.core import (FIELD_NAMES, NARROWED_FIELDS, NODE_SCHEMA,
                        DEFAULT_CONFIG, HoneycombConfig, HoneycombStore,
                        NodeImageLayout)
from repro.core.keys import int_key

SMALL = HoneycombConfig(node_cap=16, log_cap=4, n_shortcuts=4)


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
    """Heap allocation, the snapshot's field view and the device-narrowing
    table all share the ONE schema — no hand-kept field list survives."""
    from repro.core.heap import NodeHeap
    from repro.core.read_path import SnapshotFields
    assert NodeHeap.ARRAY_FIELDS == FIELD_NAMES
    assert SnapshotFields.__slots__[:len(FIELD_NAMES)] == FIELD_NAMES
    assert NARROWED_FIELDS == {"version", "log_op", "log_hint", "log_vdelta"}
    assert all(f.device in ("uint32", "int32") for f in NODE_SCHEMA)


def test_pack_unpack_view_roundtrip():
    """pack() -> unpack() is the identity (in device dtypes) on a live
    heap, and the device view() decodes every field identically —
    including NULL = -1 surviving the u32 transit of signed fields."""
    st = HoneycombStore(SMALL, heap_capacity=256)
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
    st = HoneycombStore(SMALL, heap_capacity=256)
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


# ----------------------------------------------- direct store vs baseline
def test_direct_store_matches_cpu_baseline():
    """No scheduler in the way: direct put/get/scan/delete + export answer
    what the CPU baseline store answers, and every sync issues one image
    DMA per dirty node."""
    st = HoneycombStore(SMALL, heap_capacity=256)
    ref = CpuOrderedStore(node_cap=16)
    rng = np.random.default_rng(11)
    for round_ in range(4):
        for _ in range(50):
            k = int_key(int(rng.integers(0, 150)))
            if rng.random() < 0.6:
                v = bytes(rng.integers(65, 91, 8))
                st.put(k, v), ref.put(k, v)
            else:
                st.delete(k), ref.delete(k)
        keys = [int_key(i) for i in range(0, 150, 7)]
        assert st.get_batch(keys) == [ref.get(k) for k in keys], round_
        ranges = [(int_key(a), int_key(a + 9)) for a in range(0, 140, 23)]
        assert st.scan_batch(ranges) == \
            [ref.scan(lo, hi) for lo, hi in ranges], round_
        st.export_snapshot()
    s = st.sync_stats
    assert s.delta_syncs > 0
    assert s.image_dma_count == s.full_syncs + s.delta_rows


# ------------------------------------------------------------ kernel parity
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
