"""End-to-end behaviour tests for the whole system: Honeycomb store under a
realistic mixed workload with concurrent-style readers, and the serving +
training integrations built on top of it."""
import numpy as np
import pytest

from repro.core import HoneycombConfig, HoneycombStore
from repro.core.keys import int_key


def test_mixed_workload_end_to_end():
    """YCSB-like mix driven through the full stack: host writes, batched
    accelerator reads, GC, snapshot refresh — everything stays coherent."""
    cfg = HoneycombConfig(node_cap=16, log_cap=4, n_shortcuts=4)
    store = HoneycombStore(cfg, heap_capacity=256)
    oracle: dict[bytes, bytes] = {}
    rng = np.random.default_rng(0)

    for round_ in range(6):
        # write phase (host)
        for _ in range(200):
            k = int_key(int(rng.integers(0, 300)))
            op = rng.random()
            if op < 0.6:
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
        # read phase (accelerator): point + range
        keys = [int_key(int(k)) for k in rng.integers(0, 300, 64)]
        got = store.get_batch(keys)
        assert got == [oracle.get(k) for k in keys]
        ranges = []
        for _ in range(16):
            a = int(rng.integers(0, 290))
            ranges.append((int_key(a), int_key(a + 9)))
        for (lo, hi), items in zip(ranges, store.scan_batch(ranges)):
            assert items == store.tree.scan(lo, hi)
        # GC between rounds (epochs closed)
        store.tree.epochs.cpu_begin(0)
        store.collect_garbage()

    store.tree.check_invariants()
    s = store.stats
    assert s.merges > 0 and s.splits > 0 and s.fast_path > 0


def test_snapshot_isolation_under_churn():
    """Readers pinned to old snapshots keep linearizable results while the
    host churns — the paper's core guarantee, system level."""
    cfg = HoneycombConfig(node_cap=16, log_cap=4, n_shortcuts=4)
    store = HoneycombStore(cfg, heap_capacity=256)
    for i in range(150):
        store.put(int_key(i), b"gen0-%d" % i)
    snap = store.export_snapshot()
    frozen = store.scan_batch([(int_key(0), int_key(149))])[0]

    import jax.numpy as jnp
    from repro.core.keys import pack_keys
    from repro.core.read_path import batched_scan
    for gen in range(1, 4):
        for i in range(150):
            store.update(int_key(i), b"gen%d-%d" % (gen, i))
    lo, ln = pack_keys([int_key(0)], cfg.key_words)
    hi, hn = pack_keys([int_key(149)], cfg.key_words)
    res = batched_scan(snap, jnp.asarray(lo), jnp.asarray(ln),
                       jnp.asarray(hi), jnp.asarray(hn), cfg)
    assert int(res.count[0]) >= 1
    first_val = np.asarray(res.vals)[0, 0].astype(">u4").tobytes()[:6]
    assert first_val == b"gen0-0"
    # live store sees the latest generation
    assert store.get_batch([int_key(0)])[0] == b"gen3-0"
    assert frozen[0][1] == b"gen0-0"


def test_honeycomb_vs_cpu_baseline_agree():
    """The accelerated store and the software baseline are observationally
    equivalent (same results; different cost profiles)."""
    from repro.baselines.cpu_store import CpuOrderedStore
    hc = HoneycombStore(HoneycombConfig(node_cap=16, log_cap=4,
                                        n_shortcuts=4))
    cp = CpuOrderedStore(node_cap=16)
    rng = np.random.default_rng(1)
    for _ in range(800):
        k = int_key(int(rng.integers(0, 200)))
        if rng.random() < 0.7:
            v = bytes(rng.integers(65, 91, 8))
            hc.put(k, v)
            cp.put(k, v)
        else:
            hc.delete(k)
            cp.delete(k)
    keys = [int_key(i) for i in range(200)]
    assert hc.get_batch(keys) == cp.get_batch(keys)
    ranges = [(int_key(a), int_key(a + 5)) for a in range(0, 190, 17)]
    assert hc.scan_batch(ranges) == cp.scan_batch(ranges)


def test_cpu_baseline_leaf_minimums_track_writes():
    """The baseline's leaf-minimum index stays in step through splits,
    new minimums and leaves emptied by deletes: every GET and SCAN matches
    a sorted-list oracle."""
    import bisect
    from repro.baselines.cpu_store import CpuOrderedStore
    cp = CpuOrderedStore(node_cap=4)
    oracle = {}
    rng = np.random.default_rng(5)
    for i in rng.permutation(120):
        cp.put(int_key(int(i)), b"v%d" % i)
        oracle[int_key(int(i))] = b"v%d" % i
    for i in list(range(0, 40)) + list(range(70, 75)):   # empty leaves
        cp.delete(int_key(i))
        oracle.pop(int_key(i))
    cp.put(int_key(0), b"low")                          # new global min
    oracle[int_key(0)] = b"low"
    assert cp._mins == [lf.keys[0] for lf in cp.leaves]
    for i in range(125):
        assert cp.get(int_key(i)) == oracle.get(int_key(i))
    keys = sorted(oracle)
    for lo, hi in [(0, 5), (39, 45), (68, 80), (100, 200)]:
        a, b = int_key(lo), int_key(hi)
        start = max(bisect.bisect_right(keys, a) - 1, 0)
        want = [(k, oracle[k]) for k in keys[start:] if k <= b]
        assert cp.scan(a, b) == want


def test_variable_length_keys_end_to_end():
    """The paper's headline feature: variable-size keys and values, inline,
    with lexicographic order — through writes, merges, splits and the
    batched device read path."""
    cfg = HoneycombConfig(node_cap=16, log_cap=4, n_shortcuts=4,
                          key_words=6, val_words=3)
    store = HoneycombStore(cfg, heap_capacity=256)
    rng = np.random.default_rng(4)
    oracle = {}
    keys = []
    for _ in range(400):
        klen = int(rng.integers(1, cfg.max_key_bytes + 1))
        k = rng.integers(97, 123, klen, dtype=np.uint8).tobytes()
        vlen = int(rng.integers(0, 40))        # some overflow the inline cap
        v = rng.integers(65, 91, vlen, dtype=np.uint8).tobytes()
        keys.append(k)
        if rng.random() < 0.85:
            store.put(k, v)
            oracle[k] = v
        else:
            store.delete(k)
            oracle.pop(k, None)
    store.tree.check_invariants()
    # device GETs (mix of present/absent/prefix-sibling keys)
    probes = keys[:64] + [k[:-1] for k in keys[:16] if len(k) > 1] \
        + [(k + b"z")[: cfg.max_key_bytes] for k in keys[:16]]
    got = store.get_batch(probes)
    assert got == [oracle.get(k) for k in probes]
    # device SCANs honor byte-lexicographic order incl. prefix relations
    ks = sorted(oracle)
    if len(ks) > 8:
        ranges = [(ks[1], ks[6]), (ks[0][:1], ks[-1])]
        for (lo, hi), items in zip(ranges, store.scan_batch(ranges)):
            assert items == store.tree.scan(lo, hi)
            assert [k for k, _ in items] == sorted(k for k, _ in items)
