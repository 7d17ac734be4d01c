"""Fig. 17: log-block size sweep.  Bigger log blocks help inserts (fewer
merges => fewer page-table syncs) and hurt scans (more unsorted bytes per
leaf read) — the paper picks 512 B; here the analogue knob is log_cap.

Also reports the delta-vs-full sync-traffic curve: after a resident snapshot
exists, a batch of W writes delta-syncs O(W) bytes where a wholesale
republish moves the entire store — the log block plus batched page-table
commands are exactly what make the delta small (the paper's PCIe
amortization argument, now measurable end to end).

The node-image DMA accounting rides the same curve: every dirty node
crosses as ONE contiguous image-row DMA of ``node_image_bytes``
(core/schema.py; the paper's whole-node transfer)."""
from __future__ import annotations

import time

import numpy as np

from repro.core import HoneycombConfig, HoneycombStore, NodeImageLayout
from repro.core.keys import int_key
from .common import emit, uniform_sampler

WRITE_BATCHES = (16, 64, 256)


def sync_traffic_curve(st: HoneycombStore, n_items: int) -> dict:
    """Delta vs full host->device bytes for growing write batches, plus the
    append-only log-entry wire-format estimate (key+value+op per write) —
    the paper's log-block byte accounting.  The wire bytes lower-bound what
    a log-structured delta encoding would move; dirty-node deltas transfer
    whole node images and sit between that bound and a full republish.
    Each batch also reports its node-image DMA meters: invocations, dirty
    nodes, and bytes per dirty node (== node_image_bytes by construction,
    one DMA each)."""
    layout = NodeImageLayout.for_config(st.cfg)
    st.export_snapshot()                      # make the snapshot resident
    curve = {}
    rng = np.random.default_rng(23)
    for w in WRITE_BATCHES:
        w0 = st.sync_stats.log_wire_bytes
        for k in rng.integers(0, n_items, w):
            st.update(int_key(int(k)), b"u" * 16)
        wire_bytes = st.sync_stats.log_wire_bytes - w0
        b0 = st.sync_stats.bytes_synced
        d0 = st.sync_stats.image_dma_count
        i0 = st.sync_stats.image_bytes
        st.export_snapshot()
        delta_bytes = st.sync_stats.bytes_synced - b0
        image_dmas = st.sync_stats.image_dma_count - d0
        node_bytes = st.sync_stats.image_bytes - i0
        dirty = node_bytes // layout.node_image_bytes
        delta_fraction = st.sync_stats.delta_fraction
        b1 = st.sync_stats.bytes_synced
        st.export_snapshot(full=True)
        full_bytes = st.sync_stats.bytes_synced - b1
        curve[w] = {"delta_bytes": delta_bytes, "full_bytes": full_bytes,
                    "wire_bytes": wire_bytes,
                    "ratio": delta_bytes / full_bytes,
                    "wire_ratio": wire_bytes / full_bytes,
                    "wire_vs_delta": wire_bytes / max(delta_bytes, 1),
                    "delta_fraction": delta_fraction,
                    "image_dmas": image_dmas, "dirty_nodes": dirty,
                    "bytes_per_dirty_node": node_bytes / max(dirty, 1),
                    "dmas_per_dirty_node": image_dmas / max(dirty, 1)}
    return curve


def run(n_items: int = 2048, n_ops: int = 1024) -> dict:
    results = {}
    for log_cap in (2, 8, 16, 32):
        st = HoneycombStore(HoneycombConfig(log_cap=log_cap))
        rng = np.random.default_rng(0)
        for i in rng.permutation(n_items):
            st.put(int_key(int(i)), b"v" * 16)
        # insert throughput
        ks = rng.integers(n_items, 2 * n_items, n_ops)
        t0 = time.perf_counter()
        for k in ks:
            st.put(int_key(int(k)), b"v" * 16)
        ins = n_ops / (time.perf_counter() - t0)
        syncs = st.tree.pt.sync_commands
        # 1-item scan throughput
        st.export_snapshot()
        sampler = uniform_sampler(n_items, 17)
        t0 = time.perf_counter()
        for i in range(0, n_ops, 256):
            ks2 = [int_key(int(k)) for k in sampler(min(256, n_ops - i))]
            st.scan_batch([(k, k) for k in ks2])
        sc = n_ops / (time.perf_counter() - t0)
        curve = sync_traffic_curve(st, n_items)
        results[log_cap] = {"insert_ops_s": ins, "scan_ops_s": sc,
                            "pt_syncs": syncs, "sync_traffic": curve}
        tag = f"logcap_{log_cap}"
        emit(tag, 1e6 / ins,
             f"insert={ins:.0f}/s scan={sc:.0f}/s syncs={syncs}")
        for w, c in curve.items():
            emit(f"{tag}_sync_w{w}", c["delta_bytes"],
                 f"delta={c['delta_bytes']}B full={c['full_bytes']}B "
                 f"wire={c['wire_bytes']}B ratio={c['ratio']:.4f} "
                 f"dmas={c['image_dmas']} dirty={c['dirty_nodes']} "
                 f"B/node={c['bytes_per_dirty_node']:.0f}")
    return results


if __name__ == "__main__":
    run()
