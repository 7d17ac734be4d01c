"""Fig. 16: interior-node cache + load balancer — modeled AND measured.

The measured half replays a Zipfian hot-key workload through the REAL
``StoreShard`` read path: the fused megakernels resolve the first
``cfg.cache_levels`` descend levels from the VMEM-pinned cache tier
(``vmem_hits``) and fall through to the heap image below the frontier
(``heap_gathers``), while ``cfg.lb_fraction`` deterministically routes a
slice of cache-hit lanes down the heap pipe anyway — the paper's
dual-pipe load balancer, with the byte split read straight off the device
meters.

The modeled half keeps the original host metadata-table sweep (hit rates
by cache size, two-pipe completion-time model, NoLB ablation) — the
Fig. 16 curve shape the measured meters are compared against."""
from __future__ import annotations

import time

import numpy as np

from repro.core import HoneycombConfig, HoneycombStore
from repro.core.keys import int_key
from repro.core.schema import NodeImageLayout
from .common import emit, uniform_sampler, zipf_sampler

FAST_BPS = 4.0e9     # modeled on-board DRAM pipe
SLOW_BPS = 1.3e9     # modeled PCIe pipe (13 GB/s / 10 for scale)


def _measured_point(cfg: HoneycombConfig, n_items: int, n_ops: int,
                    batch: int = 256) -> dict:
    """One Zipfian GET workload through the live store at ``cfg``,
    returning throughput plus the device cache/pipe meters."""
    st = HoneycombStore(cfg)
    rng = np.random.default_rng(0)
    for i in rng.permutation(n_items):
        st.put(int_key(int(i)), b"v" * 16)
    st.export_snapshot()
    sampler = zipf_sampler(n_items, seed=19)
    keys = [int_key(int(k)) for k in sampler(n_ops)]
    st.get_batch(keys[:batch])            # warm the jit bucket
    s0 = st.cache.stats
    v0, h0, r0 = s0.vmem_hits, s0.heap_gathers, s0.lb_routed
    t0 = time.perf_counter()
    for i in range(0, n_ops, batch):
        st.get_batch(keys[i:i + batch])
    dt = time.perf_counter() - t0
    s = st.cache.stats
    node_b = NodeImageLayout.for_config(cfg).node_image_bytes
    vmem, heap = s.vmem_hits - v0, s.heap_gathers - h0
    total = vmem + heap
    return {
        "ops_per_s": n_ops / dt,
        "vmem_hits": vmem, "heap_gathers": heap,
        "lb_routed": s.lb_routed - r0,
        "device_hit_rate": vmem / total if total else 0.0,
        # dual-pipe byte split: each resolved level moves one node image
        "vmem_bytes": vmem * node_b, "heap_bytes": heap * node_b,
    }


def run(n_items: int = 8192, n_ops: int = 4096,
        lb_fractions: tuple[float, ...] = (0.0, 0.25, 0.5)) -> dict:
    results = {}
    # ---- measured: the real device read path --------------------------
    for frac in lb_fractions:
        r = _measured_point(HoneycombConfig(lb_fraction=frac), n_items,
                            n_ops)
        name = "measured_fused" + (f"_lb{frac:g}" if frac else "")
        results[name] = r
        emit(name, 1e6 / r["ops_per_s"],
             f"hit={r['device_hit_rate']:.2f} "
             f"vmem_B={r['vmem_bytes']} heap_B={r['heap_bytes']} "
             f"lb_routed={r['lb_routed']}")
    # ---- modeled: host metadata-table sweep (the Fig. 16 shape) ------
    for cache_slots, lb in ((8, True), (64, True), (256, True),
                            (256, False)):
        cfg = HoneycombConfig(cache_slots=cache_slots, load_balance=lb)
        st = HoneycombStore(cfg)
        rng = np.random.default_rng(0)
        for i in rng.permutation(n_items):
            st.put(int_key(int(i)), b"v" * 16)
        st.export_snapshot()
        cache = st.cache
        sampler = uniform_sampler(n_items, 19)
        tree = st.tree
        nbytes = cfg.header_bytes + cfg.shortcut_bytes + cfg.segment_bytes
        for k in sampler(n_ops):
            klanes, klen = tree._pack(int_key(int(k)))
            lid = tree.root_lid
            for _ in range(tree.height - 1):
                phys = tree.pt.lookup(lid)
                cache.route(lid, phys, nbytes)
                lid, _ = tree._interior_child(phys, klanes, klen)
        stats = cache.stats
        # two-pipe completion-time model: both pipes drain concurrently
        t_fast = stats.fast_bytes / FAST_BPS
        t_slow = stats.slow_bytes / SLOW_BPS
        t = max(t_fast, t_slow)
        mtput = n_ops / t if t else float("inf")
        name = f"cache{cache_slots}_{'lb' if lb else 'nolb'}"
        results[name] = {"hit_rate": stats.hit_rate,
                         "fast_bytes": stats.fast_bytes,
                         "slow_bytes": stats.slow_bytes,
                         "modeled_ops_s": mtput}
        emit(name, 1e6 * t / n_ops,
             f"hit={stats.hit_rate:.2f} modeled_ops_s={mtput:.2e}")
    return results


if __name__ == "__main__":
    run()
