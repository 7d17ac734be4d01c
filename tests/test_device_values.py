"""Out-of-node values served from the device value image.

A value longer than the inline width (``val_words`` lanes, 16 B) lives in
an ``overflow_words`` slot of the host overflow heap; the snapshot carries
those slots as its value image (``TreeSnapshot.values``), each sync uploads
only the slots allocated since the last one, and every read batch gathers
its long values on the device (``gather_values``) into its one packed
copy.  Checked here: answers through ``HoneycombService`` against the CPU
baseline on both read backends, the counters, one copy per batch, a
follower's own value image, slot reuse after the epoch window, the slot
size limit, that a read never falls back to the live host heap, and that
a store of inline values never builds a value image."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.baselines.cpu_store import CpuOrderedStore
from repro.core import (Delete, Get, HoneycombConfig, HoneycombService,
                        HoneycombStore, Put, ReplicationConfig, Scan,
                        ShardedHoneycombStore, Update)
from repro.core import shard as shard_mod
from repro.core.keys import int_key

CFG = HoneycombConfig(node_cap=16, log_cap=4, n_shortcuts=4,
                      max_scan_items=16, overflow_words=256)
KEYS = 120


def _value(rng, long_share: float = 0.8) -> bytes:
    """Seeded random bytes: mostly YCSB's 1,000-byte record, sometimes a
    value at or just past the 16-byte inline width, or a full slot."""
    n = (int(rng.choice([1000, 17, 1024])) if rng.random() < long_share
         else int(rng.choice([0, 5, 16])))
    return rng.integers(0, 256, n, dtype=np.uint8).tobytes()


def _loaded(backend: str = "fused", **kw):
    rng = np.random.default_rng(15)
    st = HoneycombStore(dataclasses.replace(CFG, read_backend=backend, **kw),
                        heap_capacity=256)
    ref = CpuOrderedStore(node_cap=16)
    for i in range(0, KEYS, 2):
        v = _value(rng)
        st.put(int_key(i), v)
        ref.put(int_key(i), v)
    return st, ref, rng


def _random_ops(rng, n: int) -> list:
    ops = []
    for _ in range(n):
        k = int_key(int(rng.integers(0, KEYS)))
        r = rng.random()
        if r < 0.35:
            ops.append(Get(k))
        elif r < 0.55:
            lo = int(rng.integers(0, KEYS))
            ops.append(Scan(int_key(lo), int_key(lo + int(rng.integers(0, 6)))))
        elif r < 0.75:
            ops.append(Put(k, _value(rng)))
        elif r < 0.9:
            ops.append(Update(k, _value(rng)))
        else:
            ops.append(Delete(k))
    return ops


def _long(v) -> int:
    return int(v is not None and len(v) > CFG.max_inline_val_bytes)


@pytest.mark.parametrize("backend", ["fused", "reference"])
def test_service_answers_match_cpu_baseline(backend):
    """Mixed epochs through ``HoneycombService``: every answer equals the
    CPU baseline's (each epoch's writes in submission order, then its
    reads); every long value came from the device image
    (``device_values`` counting them), one copy per read batch."""
    st, ref, rng = _loaded(backend)
    st.export_snapshot()
    svc = HoneycombService(st, batch_size=8)
    ps = st.pipeline_stats
    served_long = 0
    for _ in range(6):
        ops = _random_ops(rng, 48)
        tickets = svc.submit_many(ops)
        svc.drain()
        for op in ops:
            if op.IS_WRITE:
                if isinstance(op, Delete):
                    ref.delete(op.key)
                else:
                    ref.put(op.key, op.value)
        for op, t in zip(ops, tickets):
            got = t.result().unwrap()
            if isinstance(op, Get):
                want = ref.get(op.key)
                served_long += _long(want)
            elif isinstance(op, Scan):
                want = ref.scan(op.lo, op.hi)
                served_long += sum(_long(v) for _, v in want)
            else:
                continue
            assert got == want, op
    assert ps.host_scans == 0
    assert served_long > 100
    assert ps.device_values == served_long
    assert ps.read_batches > 0 and ps.read_copies == ps.read_batches
    assert st._snapshot.values is not None


def test_sync_uploads_only_new_value_slots(monkeypatch):
    """After the first publish a sync moves the slots allocated since the
    last sync and nothing else; a sync that wrote no long value launches
    no value program and moves no value byte."""
    st, _, rng = _loaded()
    st.export_snapshot()
    stats = st.sync_stats
    whole = stats.value_slots_synced
    assert whole == len(st.tree.overflow.lens)     # the first publish
    assert stats.value_bytes_synced == whole * 1024
    scatters = []
    real = shard_mod._jit_scatter_values
    monkeypatch.setattr(shard_mod, "_jit_scatter_values",
                        lambda *a: scatters.append(a) or real(*a))
    for i in range(3):
        st.update(int_key(2 * i), b"L" * 1000)
    st.update(int_key(8), b"short")
    st.export_snapshot()
    assert stats.value_slots_synced == whole + 3
    assert len(scatters) == 1
    bytes0 = stats.value_bytes_synced
    st.update(int_key(10), b"short again")
    st.export_snapshot()
    assert stats.value_bytes_synced == bytes0 and len(scatters) == 1
    assert st.get_batch([int_key(0), int_key(8), int_key(10)]) == [
        b"L" * 1000, b"short", b"short again"]


def test_follower_serves_values_from_its_own_image():
    """An epoch that stores long values is not replayable from the log
    feed: followers take the image delta with the epoch's value slots and
    answer from their own value image."""
    st = ShardedHoneycombStore(
        CFG, heap_capacity=256, shards=1,
        replication=ReplicationConfig(replicas=2, policy="round_robin"))
    g = st.shards[0]
    for i in range(40):
        st.put(int_key(i), b"s%03d" % i)
    st.export_snapshot()
    f = g.followers[0]
    assert f.snapshot.values is None
    fallbacks = g.feed_stats.log_fallback_epochs
    rng = np.random.default_rng(3)
    want = {}
    for rnd in range(2):                 # first value publish, then a delta
        for i in range(rnd, 40, 3):
            want[i] = rng.integers(0, 256, 1000, dtype=np.uint8).tobytes()
            st.update(int_key(i), want[i])
        st.export_snapshot()
    assert g.feed_stats.log_fallback_epochs == fallbacks + 2
    assert f.snapshot.values is not None
    assert f.snapshot.values is not g.primary._snapshot.values
    keys = sorted(want)
    ps = g.primary.pipeline_stats
    before = ps.device_values
    got = g.get_batch([int_key(i) for i in keys], replica=1)
    assert g.last_dispatch[0] == 1                 # the follower served it
    assert got == [want[i] for i in keys]
    assert ps.device_values == before + len(keys)
    scan = g.scan_batch([(int_key(0), int_key(5))], replica=1)[0]
    assert scan == [(int_key(i), want.get(i, b"s%03d" % i))
                    for i in range(6)]


def _slot_of(st, data: bytes) -> int:
    ovf = st.tree.overflow
    for s in np.flatnonzero(ovf.lens == len(data)):
        if ovf.read(int(s)) == data:
            return int(s)
    raise KeyError("value not in the overflow heap")


def test_overwritten_slot_reused_only_after_epoch_window():
    """Under the explicit policy the resident snapshot pins an epoch.  A
    log merge hands the overwritten value's slot (and the slots of the
    values the log shadowed) to GC; it is not reclaimed while that
    snapshot serves, reads of it return the old bytes, and once it is
    reclaimed and reused the older snapshot's value image still holds
    them."""
    st, _, _ = _loaded(sync_policy="explicit")
    old = b"o" * 1000
    st.put(int_key(3), old)
    s1 = st.export_snapshot()
    slot = _slot_of(st, old)
    for r in range(CFG.log_cap + 1):       # fills the log, then merges
        st.update(int_key(3), b"%d" % r * 1000)
    new = b"%d" % CFG.log_cap * 1000
    st.put(int_key(501), b"later write")
    st.collect_garbage()
    assert _slot_of(st, old) == slot               # s1 pins the window
    assert st.get_batch([int_key(3)]) == [old]     # resident snapshot s1
    st.export_snapshot()
    st.export_snapshot(force=True)                 # s1's pin rolls off
    st.collect_garbage()
    assert st.tree.overflow.lens[slot] == 0        # reclaimed
    fresh = [bytes([65 + i]) * 1000 for i in range(8)]
    for i, v in enumerate(fresh):
        st.put(int_key(601 + 2 * i), v)
    assert slot in {_slot_of(st, v) for v in fresh}   # and reused
    s3 = st.export_snapshot()
    assert st._device_get(s1, [int_key(3)]) == [old]
    assert st._device_get(s3, [int_key(3)] + [
        int_key(601 + 2 * i) for i in range(8)]) == [new] + fresh


@pytest.mark.parametrize("op", ["get", "scan"])
def test_snapshot_without_value_image_fails_loudly(op):
    """A long value read from a snapshot that lacks its value image raises:
    the live host heap may hold a newer value in that slot, so it is never
    read on a device path."""
    st, _, _ = _loaded()
    snap = st.export_snapshot()._replace(values=None)
    keys = [int_key(i) for i in range(0, 40, 2)]
    with pytest.raises(RuntimeError, match="without a value image"):
        if op == "get":
            st._device_get(snap, keys)
        else:
            st._device_scan(snap, [(keys[0], keys[5])],
                           st._fallback_read_version())


def test_value_longer_than_a_slot_is_refused():
    st = HoneycombStore(CFG)
    with pytest.raises(ValueError, match="1025 B.*1024 B"):
        st.put(int_key(1), b"v" * 1025)
    st.put(int_key(1), b"v" * 1024)                # a full slot fits
    small = HoneycombStore(HoneycombConfig())      # 128-word slots
    with pytest.raises(ValueError, match="1000 B.*512 B"):
        small.put(int_key(1), b"v" * 1000)
    assert st.get(int_key(1)) == b"v" * 1024


def test_inline_store_builds_no_value_image(monkeypatch):
    """A store of inline values publishes, syncs and reads with no value
    image and launches no ``gather_values``; a store with one long value
    launches it once per read batch."""
    launched = []
    real = shard_mod._jit_gather_values
    monkeypatch.setattr(shard_mod, "_jit_gather_values",
                        lambda *a, **k: launched.append(1) or real(*a, **k))
    st = HoneycombStore(CFG, heap_capacity=256)
    for i in range(60):
        st.put(int_key(i), b"%016d" % i)
    snap = st.export_snapshot()
    assert snap.values is None
    st.update(int_key(7), b"short")
    assert st.get_batch([int_key(7), int_key(8)]) == [b"short", b"%016d" % 8]
    assert st.scan_batch([(int_key(1), int_key(3))])[0][0][0] == int_key(1)
    assert st._snapshot.values is None and launched == []
    assert st.sync_stats.value_slots_synced == 0
    st.update(int_key(9), b"L" * 17)
    assert st.get_batch([int_key(9)]) == [b"L" * 17]
    st.scan_batch([(int_key(8), int_key(10))])
    assert len(launched) == 2
    assert st.pipeline_stats.device_values == 2
