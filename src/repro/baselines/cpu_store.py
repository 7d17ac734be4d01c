"""Software-only ordered key-value store — the eRPC-Masstree stand-in.

The paper's baseline (Section 6) is Masstree behind eRPC: a cache-crafted
in-memory trie/B+tree executed entirely on CPU cores.  For the benchmark
comparison we provide a well-implemented software store with the same
interface as HoneycombStore: a classic sorted-node B+tree (no shortcuts, no
log blocks, no MVCC, no accelerator path — every operation is a host
operation touching whole nodes).

The benchmarks meter *bytes touched* and operations/second so the
Honeycomb-vs-CPU comparison reproduces the paper's shape: Honeycomb wins on
read/scan throughput per (modeled) byte of interconnect, the CPU baseline
wins on pure write paths.
"""
from __future__ import annotations

import bisect
import dataclasses


@dataclasses.dataclass
class CpuStoreStats:
    gets: int = 0
    puts: int = 0
    deletes: int = 0
    scans: int = 0
    bytes_touched: int = 0
    node_visits: int = 0

    def collect(self):
        """Registry samples (core/telemetry.py collect protocol):
        ``cpu_store_*`` counters for the host-baseline op mix."""
        from repro.core.telemetry import samples_from
        return samples_from(self, "cpu_store", "baseline")


class _Leaf:
    __slots__ = ("keys", "vals", "next")

    def __init__(self):
        self.keys: list[bytes] = []
        self.vals: list[bytes] = []
        self.next: _Leaf | None = None


class CpuOrderedStore:
    """B+tree with in-leaf sorted arrays; interior levels as sorted lists of
    (separator, child).  Node capacity mirrors honeycomb's node_cap."""

    def __init__(self, node_cap: int = 64):
        self.node_cap = node_cap
        root = _Leaf()
        self.levels: list[list[bytes]] = []   # separators per interior level
        self.children: list[list] = []        # children per interior level
        self.leaves: list[_Leaf] = [root]
        # each leaf's minimum key (b"" for an empty leaf), kept in step
        # with ``leaves`` by every write
        self._mins: list[bytes] = [b""]
        self.stats = CpuStoreStats()

    # simple two-level structure: a sorted list of leaf minimums
    # (fanout-free "interior"), which is what Masstree's upper trie
    # amortizes to for random keys; adequate as a throughput baseline.
    def _locate(self, key: bytes) -> int:
        self.stats.node_visits += 1
        return max(bisect.bisect_right(self._mins, key) - 1, 0)

    def _find_leaf(self, key: bytes) -> _Leaf:
        return self.leaves[self._locate(key)]

    def put(self, key: bytes, val: bytes):
        self.stats.puts += 1
        pos = self._locate(key)
        lf = self.leaves[pos]
        i = bisect.bisect_left(lf.keys, key)
        self.stats.bytes_touched += sum(map(len, lf.keys)) \
            + sum(map(len, lf.vals))
        if i < len(lf.keys) and lf.keys[i] == key:
            lf.vals[i] = val
        else:
            lf.keys.insert(i, key)
            lf.vals.insert(i, val)
            self._mins[pos] = lf.keys[0]
            if len(lf.keys) > self.node_cap:
                self._split(pos)

    update = put

    def _split(self, pos: int):
        lf = self.leaves[pos]
        mid = len(lf.keys) // 2
        right = _Leaf()
        right.keys, right.vals = lf.keys[mid:], lf.vals[mid:]
        lf.keys, lf.vals = lf.keys[:mid], lf.vals[:mid]
        right.next, lf.next = lf.next, right
        self.leaves.insert(pos + 1, right)
        self._mins.insert(pos + 1, right.keys[0])

    def delete(self, key: bytes):
        self.stats.deletes += 1
        pos = self._locate(key)
        lf = self.leaves[pos]
        i = bisect.bisect_left(lf.keys, key)
        self.stats.bytes_touched += sum(map(len, lf.keys))
        if i < len(lf.keys) and lf.keys[i] == key:
            del lf.keys[i], lf.vals[i]
            self._mins[pos] = lf.keys[0] if lf.keys else b""
            if not lf.keys and len(self.leaves) > 1:
                if pos > 0:
                    self.leaves[pos - 1].next = lf.next
                del self.leaves[pos], self._mins[pos]

    def get(self, key: bytes) -> bytes | None:
        self.stats.gets += 1
        lf = self._find_leaf(key)
        self.stats.bytes_touched += sum(map(len, lf.keys))
        i = bisect.bisect_left(lf.keys, key)
        if i < len(lf.keys) and lf.keys[i] == key:
            self.stats.bytes_touched += len(lf.vals[i])
            return lf.vals[i]
        return None

    def scan(self, lo: bytes, hi: bytes,
             max_items: int | None = None) -> list[tuple[bytes, bytes]]:
        """Floor-start scan with Honeycomb-compatible semantics."""
        self.stats.scans += 1
        out: list[tuple[bytes, bytes]] = []
        pos = self._locate(lo)
        lf = self.leaves[pos]
        # floor: the largest key <= lo (may sit in an earlier leaf)
        floor = None
        for j in range(pos, -1, -1):
            cand = [k for k in self.leaves[j].keys if k <= lo]
            self.stats.bytes_touched += sum(
                map(len, self.leaves[j].keys))
            if cand:
                floor = cand[-1]
                v = self.leaves[j].vals[self.leaves[j].keys.index(floor)]
                out.append((floor, v))
                break
        node: _Leaf | None = lf
        while node is not None:
            self.stats.node_visits += 1
            self.stats.bytes_touched += sum(map(len, node.keys)) \
                + sum(map(len, node.vals))
            for k, v in zip(node.keys, node.vals):
                if k <= lo:
                    continue
                if k > hi:
                    return out
                out.append((k, v))
                if max_items and len(out) >= max_items:
                    return out
            node = node.next
        return out

    # batch facades for benchmark parity with HoneycombStore
    def get_batch(self, keys):
        return [self.get(k) for k in keys]

    def scan_batch(self, ranges):
        return [self.scan(lo, hi) for lo, hi in ranges]
