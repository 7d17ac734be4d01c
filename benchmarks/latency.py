"""Fig. 12: latency-throughput.  Load is swept via device batch size; we
report median per-op latency at each offered batch (read-only 3-item
scans, the figure's workload), each batch one fused traversal dispatch
with the interior cache tier pinned in VMEM (kernels/fused_read.py).

``pipeline`` adds a second sweep: the same offered batches driven through
the scheduler's epoch pipeline with a 10% update mix (so every epoch has a
sync), serial vs pipelined — the per-op latency delta plus the
sync-stall-time meter show what the double-buffered flip buys at each
load point (see core/pipeline.py)."""
from __future__ import annotations

import time

import numpy as np

from .common import build_stores, emit, run_scheduled, uniform_sampler
from repro.core.keys import int_key

BATCHES = (8, 32, 128, 512)


def run(n_items: int = 4096, reps: int = 8,
        pipeline: tuple[str, ...] = ()) -> dict:
    results = {}
    hc, _ = build_stores(n_items, baseline=False)
    sampler = uniform_sampler(n_items, seed=9)
    for batch in BATCHES:
        lats = []
        for _ in range(reps):
            ks = sampler(batch)
            ranges = [(int_key(int(k)),
                       int_key(min(int(k) + 3, n_items - 1)))
                      for k in ks]
            t0 = time.perf_counter()
            hc.scan_batch(ranges)
            lats.append((time.perf_counter() - t0) / batch)
        med = float(np.median(lats)) * 1e6
        tput = batch / (np.median(lats) * batch)
        results[batch] = {"median_us_per_op": med, "ops_per_s": tput}
        emit(f"latency_b{batch}", med, f"ops_s={tput:.0f}")
    for mode in pipeline:
        for batch in BATCHES:
            hp, _ = build_stores(n_items, baseline=False)
            r = run_scheduled(hp, uniform_sampler(n_items, seed=9),
                              n_ops=batch * max(reps // 2, 1),
                              n_items=n_items, read_frac=0.9, scan_items=3,
                              batch=batch, pipeline=mode)
            us = 1e6 / r["ops_per_s"]
            results[f"b{batch}/{mode}"] = r
            emit(f"latency_b{batch}_{mode}", us,
                 f"ops_s={r['ops_per_s']:.0f} "
                 f"stall_s={r['sync_stall_s']:.3f} "
                 f"stall_frac={r['stall_fraction']:.2f} "
                 f"syncs={r['syncs']}")
    return results


if __name__ == "__main__":
    run(pipeline=("serial", "pipelined"))
