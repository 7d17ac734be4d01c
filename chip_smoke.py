"""Drive the store's main path once on one TPU chip and check every answer.

    python chip_smoke.py [--items 1000000] [--seed 0]

Builds a ``ShardedHoneycombStore`` (default ``HoneycombConfig``, one shard,
two replicas fed by the log feed), loads ``--items`` 8-byte integer keys
with 16-byte values made from ``--seed`` (the same keys go into the CPU
baseline ``CpuOrderedStore``, the reference), publishes the snapshot, and
then runs epochs through ``HoneycombService`` with ``submit``/``drain``:

  1. 4 GET batches of 256 zipfian(0.99) keys, an eighth of them absent, and
     4 SCAN batches of 256 ranges of 1-16 items, all on the fused
     GET/SCAN kernels;
  2. one write epoch of 1,024 PUT/UPDATE/DELETE ops — the primary syncs
     through the image-row scatter, the follower through log replay or its
     image-delta fallback — then every written key read back from both
     replica lanes;
  3. the same again as one read-and-write epoch on the pipelined service;
  4. small update epochs on a fixed key set until the follower has applied
     one from the log feed (``log_replay_scatter``; at most 16 rounds).

Every answer is compared with the reference.  The counts the run checks
print one per line; the last line is the JSON result.  The script exits
non-zero, and prints no result, when JAX finds no TPU, when any answer
differs, or when any count check fails.  The compile cache is placed by
``repro.compile_cache``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

GET_BATCHES = SCAN_BATCHES = 4
BATCH = 256
WRITES = 1024
LOG_KEYS, LOG_ROUNDS = 8, 16


def zipf_ranks(rng, n: int, size: int, theta: float = 0.99):
    """Bounded zipfian ranks over [0, n) (YCSB's request distribution)."""
    import numpy as np
    cdf = np.cumsum(1.0 / np.arange(1, n + 1) ** theta)
    return np.searchsorted(cdf, rng.random(size) * cdf[-1])


class Smoke:
    """One store + its reference, and the phases that drive them."""

    def __init__(self, items: int, seed: int):
        import numpy as np
        from repro.baselines.cpu_store import CpuOrderedStore
        from repro.core import (HoneycombConfig, ReplicationConfig,
                                ShardedHoneycombStore)
        self.np = np
        self.n = items
        self.rng = np.random.default_rng(seed)
        # even ids are loaded, odd ids are absent until a PUT adds them
        self.order = self.rng.permutation(items)
        self.store = ShardedHoneycombStore(
            HoneycombConfig(), shards=1,
            replication=ReplicationConfig(replicas=2, policy="round_robin",
                                          feed="log"))
        self.ref = CpuOrderedStore()
        # key pairs (2k, 2k+1) some write already touched: later writes
        # pick fresh pairs, so no UPDATE ever targets a deleted key
        self.touched: set[int] = set()
        self.mismatches = 0
        self.lanes_served: set[int] = set()

    @staticmethod
    def key(i) -> bytes:
        from repro.core.keys import int_key
        return int_key(int(i))

    def value(self) -> bytes:
        return self.rng.integers(0, 256, 16, dtype=self.np.uint8).tobytes()

    # ------------------------------------------------------------ phases
    def load(self) -> dict:
        vals = self.rng.integers(0, 256, (self.n, 16), dtype=self.np.uint8)
        t0 = time.perf_counter()
        for i in self.order:
            self.store.put(self.key(2 * i), vals[i].tobytes())
        self.store.export_snapshot()
        t1 = time.perf_counter()
        for i in self.order:
            self.ref.put(self.key(2 * i), vals[i].tobytes())
        return {"load_s": t1 - t0, "ref_load_s": time.perf_counter() - t1}

    def check(self, what, got, want):
        if got != want:
            self.mismatches += 1
            if self.mismatches <= 5:
                print(f"MISMATCH {what}: got {got!r} want {want!r}",
                      file=sys.stderr)

    def read_ops(self, lo_id: int = 0, hi_id: int | None = None):
        """GET and SCAN batches over key ids in [lo_id, hi_id)."""
        from repro.core import Get, Scan
        hi_id = 2 * self.n if hi_id is None else hi_id
        span = (hi_id - lo_id) // 2
        ops = []
        for _ in range(GET_BATCHES):
            ids = lo_id + 2 * zipf_ranks(self.rng, span, BATCH)
            absent = self.rng.random(BATCH) < 0.125
            ops.append([Get(self.key(i + a)) for i, a in zip(ids, absent)])
        for _ in range(SCAN_BATCHES):      # ranges stay inside the span
            starts = lo_id + 2 * self.rng.integers(0, span - 16, BATCH)
            lens = self.rng.integers(1, 17, BATCH)
            ops.append([Scan(self.key(s), self.key(s + 2 * (k - 1)),
                             expected_items=int(k))
                        for s, k in zip(starts, lens)])
        return ops

    def fresh_pairs(self, count: int, lo_id: int, hi_id: int):
        """``count`` untouched key pairs k with ids 2k, 2k+1 in
        [lo_id, hi_id), marked touched."""
        out: list[int] = []
        while len(out) < count:
            for k in self.rng.integers(lo_id // 2, hi_id // 2, count):
                if int(k) not in self.touched and len(out) < count:
                    self.touched.add(int(k))
                    out.append(int(k))
        return out

    def write_ops(self, count: int, lo_id: int = 0,
                  hi_id: int | None = None):
        """``count`` writes on distinct fresh key pairs in [lo_id, hi_id):
        40% PUTs of absent keys, 40% UPDATEs and 20% DELETEs of loaded
        keys."""
        from repro.core import Delete, Put, Update
        hi_id = 2 * self.n if hi_id is None else hi_id
        ops = []
        for j, k in enumerate(self.fresh_pairs(count, lo_id, hi_id)):
            draw = j % 5
            if draw < 2:
                ops.append(Put(self.key(2 * k + 1), self.value()))
            elif draw < 4:
                ops.append(Update(self.key(2 * k), self.value()))
            else:
                ops.append(Delete(self.key(2 * k)))
        return ops

    def apply_ref(self, op):
        if op.KIND == "delete":
            self.ref.delete(op.key)
        else:
            self.ref.put(op.key, op.value)

    def run_epoch(self, svc, ops):
        """Submit ``ops`` in order, drain ONE epoch, compare every read
        with the reference (reads in an epoch never touch a key written in
        the same epoch, so either order of apply answers the same)."""
        tickets = svc.submit_many(ops)
        svc.drain()
        for op in ops:
            if op.IS_WRITE:
                self.apply_ref(op)
        for op, t in zip(ops, tickets):
            r = t.result()
            if op.KIND == "get":
                self.check(f"GET {op.key.hex()}", r.value,
                           self.ref.get(op.key))
                self.lanes_served.add(r.replica)
            elif op.KIND == "scan":
                self.check(f"SCAN {op.lo.hex()}..{op.hi.hex()}",
                           [tuple(x) for x in r.items],
                           self.ref.scan(op.lo, op.hi))
                self.lanes_served.add(r.replica)
            else:
                self.check(f"{op.KIND} status", r.status, "ok")

    def read_back(self, svc, ops):
        """Read every written key twice in a row: the round-robin policy
        pins consecutive reads to the two replica lanes."""
        from repro.core import Get
        keys = list(dict.fromkeys(op.key for op in ops if op.IS_WRITE))
        self.run_epoch(svc, [Get(k) for k in keys for _ in range(2)])

    def log_feed_rounds(self, svc):
        """Update one fixed key set per epoch until the follower applies
        an epoch from the log feed.  An epoch falls back to the image delta
        when a write missed the leaf fast path (a full leaf log merges);
        each leaf merges at most once in 16 appends, so with 8 keys one of
        the first 9 rounds replays."""
        from repro.core import Update
        follower = self.store.shards[0].followers[0]
        ids = [2 * k for k in self.fresh_pairs(LOG_KEYS, 0, 2 * self.n)]
        for rnd in range(LOG_ROUNDS):
            ops = [Update(self.key(i), self.value()) for i in ids]
            self.run_epoch(svc, ops)
            self.read_back(svc, ops)
            if follower.sync_stats.log_replays:
                return rnd + 1
        return LOG_ROUNDS


def run(items: int, seed: int) -> dict:
    """The whole smoke on whatever backend JAX runs; returns the counts."""
    import jax
    from repro.core import HoneycombService

    sm = Smoke(items, seed)
    counts = sm.load()
    store = sm.store
    group = store.shards[0]
    snap = group.primary._snapshot
    counts["keys"] = items
    counts["tree_height"] = group.tree.height
    counts["image_bytes"] = int(snap.image.nbytes)
    stats = jax.devices()[0].memory_stats() or {}
    counts["bytes_in_use"] = stats.get("bytes_in_use", "not reported")

    # device read batches per op: each read_ops batch is all GETs or all
    # SCANs, and every device read batch runs the fused GET or SCAN kernel
    fused = {"get": 0, "scan": 0}
    t0 = time.perf_counter()
    serial = HoneycombService(store, batch_size=BATCH)
    for batch in sm.read_ops():
        before = store.pipeline_stats.read_batches
        sm.run_epoch(serial, batch)
        fused[batch[0].KIND] += store.pipeline_stats.read_batches - before
    writes = sm.write_ops(WRITES)
    sm.run_epoch(serial, writes)
    sm.read_back(serial, writes)
    # pipelined: reads on the lower half of the key space, writes on the
    # upper half, in one epoch
    piped = HoneycombService(store, batch_size=BATCH, pipeline="pipelined")
    reads = sm.read_ops(0, items)
    writes = sm.write_ops(WRITES, items, 2 * items)
    sm.run_epoch(piped, [op for b in reads[::2] for op in b] + writes)
    sm.read_back(piped, writes)
    counts["log_rounds"] = sm.log_feed_rounds(serial)
    counts["serve_s"] = time.perf_counter() - t0

    counts["fused_get_batches"] = fused["get"]
    counts["fused_scan_batches"] = fused["scan"]
    cache = store.cache_stats
    counts["vmem_hits"] = cache.vmem_hits
    counts["heap_gathers"] = cache.heap_gathers
    counts["primary_delta_syncs"] = group.primary.sync_stats.delta_syncs
    fst = group.followers[0].sync_stats
    counts["follower_log_replays"] = fst.log_replays
    counts["follower_delta_syncs"] = fst.delta_syncs
    counts["host_scan_fallbacks"] = store.pipeline_stats.host_scans
    counts["lanes_served"] = sorted(sm.lanes_served)
    counts["mismatches"] = sm.mismatches
    return counts


def failed_checks(c: dict) -> list[str]:
    checks = {
        "tree height >= 4": c["tree_height"] >= 4,
        "fused GET and SCAN batches ran": (c["fused_get_batches"] > 0
                                           and c["fused_scan_batches"] > 0),
        "vmem_hits > 0": c["vmem_hits"] > 0,
        "heap_gathers > 0": c["heap_gathers"] > 0,
        "primary delta_syncs > 0": c["primary_delta_syncs"] > 0,
        "follower log_replays > 0": c["follower_log_replays"] > 0,
        "both replica lanes served": c["lanes_served"] == [0, 1],
        "no mismatches": c["mismatches"] == 0,
    }
    return [name for name, ok in checks.items() if not ok]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--items", type=int, default=1_000_000)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.compile_cache import enable_compile_cache
    cache_dir = Path(enable_compile_cache())
    entries = len(list(cache_dir.iterdir())) if cache_dir.is_dir() else 0
    devices = jax.devices()
    print(f"devices: {devices}", flush=True)
    if devices[0].platform != "tpu":
        print("chip_smoke: no TPU found; this smoke runs on the chip only",
              file=sys.stderr)
        return 2
    print(f"compile_cache: {cache_dir} ({entries} entries at start)")
    counts = run(args.items, args.seed)
    for name, value in counts.items():
        print(f"{name}: {value}", flush=True)
    bad = failed_checks(counts)
    if bad:
        print(f"chip_smoke: FAILED: {', '.join(bad)}", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
