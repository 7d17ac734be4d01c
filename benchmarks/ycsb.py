"""Fig. 10: YCSB A-F, uniform and zipfian, Honeycomb vs CPU baseline.

Workloads (Table 2): A 50/50 update, B 95/5, C read-only, D 95/5 insert,
E scan-heavy (1..100-item scans, here capped for CPU scale), F
read-modify-write.  Reported: ops/s and ops/s/W (TDP model from the paper).

Shards are a sweep axis: the same workloads drive the live range-sharded
``ShardedHoneycombStore`` (the paper's Section 7 scale-out shape), with
per-shard sync bytes/op and router load imbalance metered alongside the
single-device numbers.

Pipeline is a second axis (``--pipeline serial,pipelined``): the same
workloads drive the typed service front end (``HoneycombService`` with
first-class ``Put``/``Get``/``Scan`` op messages — core/api.py, routing
self-wired from the store) through its epoch pipeline in each mode,
reporting pipelined-vs-serial throughput and the sync-stall-time meter
(serial blocks on the sync barrier every epoch; pipelined overlaps the
standby scatters with read dispatch — see core/pipeline.py).

Replicas are a third axis (``--replicas 1,2,4``): the read-heavy workloads
(B, C — uniform and the zipfian skew where read spreading wins, per F2)
drive the replicated store (core/replica.py) with round-robin read
spreading, reporting the read-throughput-vs-replicas curve plus the
sync-bytes-amplification curve (follower feed bytes per op on top of the
primary's sync traffic).

Feed is a fourth axis (``--feed log,delta`` x ``--relay-depth 0,2``): the
write-heavy workload A drives the replicated store under both follower
feeds, reporting the per-follower feed-bytes-per-epoch amplification
curve — the log-shipping artifact: the log feed ships the epoch's encoded
op wire stream (~tens of bytes per write) where the delta feed ships
whole dirty image rows (~5 KB each), so per-follower feed bytes collapse
by >=10x; epochs whose tree shape changed fall back to the image delta
and are excluded from the ratio but reported alongside it.  Relay depth
reshapes WHO pays: the total feed bytes are topology-invariant while the
primary's own egress drops to its O(fanout) direct edges.
"""
from __future__ import annotations

import dataclasses

from repro.core.keys import int_key

from .common import (TDP_BASELINE_W, TDP_HONEYCOMB_W, build_stores, emit,
                     run_mixed, run_scheduled, uniform_sampler, zipf_sampler)

WORKLOADS = {
    "A": dict(read_frac=0.5, scan_items=0),
    "B": dict(read_frac=0.95, scan_items=0),
    "C": dict(read_frac=1.0, scan_items=0),
    "D": dict(read_frac=0.95, scan_items=0),
    "E": dict(read_frac=0.95, scan_items=8),
    "F": dict(read_frac=0.666, scan_items=0),
}


def run(n_items: int = 4096, n_ops: int = 2048,
        shards: tuple[int, ...] = (1,),
        pipeline: tuple[str, ...] = (),
        replicas: tuple[int, ...] = (),
        feed: tuple[str, ...] = (),
        relay_depth: tuple[int, ...] = ()) -> dict:
    results = {}
    # feed axis: write-heavy A over log vs delta follower feeds and relay
    # depths — per-follower feed bytes per epoch is the amplification
    # artifact (acceptance: pure log feed <= 10% of the delta feed's,
    # fallback epochs excluded from the ratio and reported)
    per_follower = {}
    for nr in replicas if (feed or relay_depth) else ():
        if nr < 2:
            continue
        for fd in feed or ("log",):
            for depth in relay_depth or (0,):
                hf, _ = build_stores(n_items, shards=1, replicas=nr,
                                     replica_policy="round_robin", feed=fd,
                                     relay_depth=depth, baseline=False,
                                     force_router=True)
                fs0 = dataclasses.asdict(hf.shards[0].feed_stats)
                r = run_mixed(hf, uniform_sampler(n_items, seed=3),
                              n_ops=n_ops, n_items=n_items, batch=64,
                              **WORKLOADS["A"])
                d = {k: v - fs0[k] for k, v in
                     dataclasses.asdict(hf.shards[0].feed_stats).items()}
                nf = nr - 1
                if fd == "log":       # pure log deliveries only
                    per_fe = d["log_bytes"] / max(d["log_feed_epochs"] * nf,
                                                  1)
                else:                 # delta deliveries minus catch-ups
                    per_fe = ((d["feed_bytes"] - d["catchup_bytes"])
                              / max(d["delta_feed_epochs"] * nf, 1))
                per_follower[(nr, depth, fd)] = per_fe
                key = f"A/feed/{fd}/replicas{nr}/depth{depth}"
                results[key] = {
                    "honeycomb_ops_s": r["ops_per_s"], "replicas": nr,
                    "feed": fd, "relay_depth": depth,
                    "per_follower_feed_B_per_epoch": per_fe,
                    "feed_delta": d, "sync": r["sync"]}
                emit(f"ycsb_A_feed_{fd}_r{nr}_d{depth}",
                     1e6 / r["ops_per_s"],
                     f"perF_B/epoch={per_fe:.0f} "
                     f"feed_B={d['feed_bytes']} "
                     f"egress_B={d['primary_egress_bytes']} "
                     f"relay_B={d['relay_hop_bytes']} "
                     f"fallbacks={d['log_fallback_epochs']}")
    for (nr, depth, fd), log_b in sorted(per_follower.items()):
        if fd != "log" or (nr, depth, "delta") not in per_follower:
            continue
        ratio = log_b / max(per_follower[(nr, depth, "delta")], 1e-9)
        results[f"A/feed_ratio/replicas{nr}/depth{depth}"] = {
            "log_over_delta": ratio, "replicas": nr, "relay_depth": depth}
        emit(f"ycsb_A_feed_ratio_r{nr}_d{depth}", 0.0,
             f"log/delta={ratio:.4f} (target<=0.10, fallbacks excluded)")
    # replication axis: read-heavy workloads over growing replica sets —
    # read throughput should scale with serving lanes while writes (and
    # their delta feed) stay on the primary; the amplification meter is
    # the cost side of that curve
    warmed = not replicas
    for nr in replicas:
        # force_router: the replicas=1 baseline point runs the SAME routed
        # facade as the replicated points, so the curve compares like
        # against like
        hr, _ = build_stores(n_items, shards=1, replicas=nr,
                             replica_policy="round_robin", baseline=False,
                             force_router=True)
        if not warmed:
            # pre-compile the read-path and delta-scatter jit buckets once
            # (shapes are identical across replica counts) so compile time
            # is not charged to the sweep's first point
            for b in (1, 2, 4, 8, 16, 32, 64):
                hr.get_batch([int_key(0)] * b)
            for w in (4, 16, 48):
                for i in range(w):
                    hr.update(int_key(i), b"x" * 16)
                hr.export_snapshot()
            warmed = True
        for wl, dist in (("C", "zipfian"), ("B", "zipfian"),
                         ("B", "uniform")):
            mk = zipf_sampler if dist == "zipfian" else uniform_sampler
            lanes0 = [list(ops) for ops in hr.per_shard_replica_ops]
            # smaller read bursts than the default so even tiny runs
            # dispatch several batches — one policy pick per batch is what
            # spreads the load over replica lanes
            r = run_mixed(hr, mk(n_items, seed=3), n_ops=n_ops,
                          n_items=n_items, batch=64, **WORKLOADS[wl])
            sync = r["sync"]
            amp = sync["replication_bytes"] / max(r["ops"], 1)
            # THIS workload's per-lane spread (the store is reused, so the
            # lifetime counters must be diffed per run)
            lanes = [b - a for a, b in
                     zip(lanes0[0], hr.per_shard_replica_ops[0])]
            results[f"{wl}/{dist}/replicas{nr}"] = {
                "honeycomb_ops_s": r["ops_per_s"], "replicas": nr,
                "replica_ops": lanes, "sync": sync}
            emit(f"ycsb_{wl}_{dist}_r{nr}", 1e6 / r["ops_per_s"],
                 f"reads/s={r['ops_per_s']:.0f} replicas={nr} "
                 f"repl_B/op={amp:.0f} sync_B/op={sync['bytes_per_op']:.0f} "
                 f"lanes={lanes}")
    for ns in shards if isinstance(shards, (tuple, list)) else (shards,):
        hc, cp = build_stores(n_items, shards=ns)
        tag = "" if ns == 1 else f"/s{ns}"
        for dist in ("uniform", "zipfian"):
            for wl, spec in WORKLOADS.items():
                mk = uniform_sampler if dist == "uniform" else zipf_sampler
                r_h = run_mixed(hc, mk(n_items, seed=3), n_ops=n_ops,
                                n_items=n_items, **spec)
                r_c = run_mixed(cp, mk(n_items, seed=3), n_ops=n_ops,
                                n_items=n_items, is_honeycomb=False, **spec)
                h, c = r_h["ops_per_s"], r_c["ops_per_s"]
                eff_h = h / TDP_HONEYCOMB_W
                eff_c = c / TDP_BASELINE_W
                sync = r_h["sync"]
                results[f"{wl}/{dist}{tag}"] = {
                    "honeycomb_ops_s": h, "baseline_ops_s": c,
                    "speedup": h / c, "eff_ratio": eff_h / eff_c,
                    "shards": ns, "sync": sync}
                extra = ""
                if "load_imbalance" in sync:
                    extra = f" imbal={sync['load_imbalance']:.2f}"
                emit(f"ycsb_{wl}_{dist}{tag.replace('/', '_')}", 1e6 / h,
                     f"speedup={h / c:.2f}x eff={eff_h / eff_c:.2f}x "
                     f"sync_B/op={sync['bytes_per_op']:.0f} "
                     f"wire_B={sync['log_wire_bytes']} "
                     f"deltas={sync['delta_syncs']}/{sync['snapshots']} "
                     f"pt_cmds={sync['pagetable_commands']}{extra}")
        # pipeline axis: scheduler-driven epochs, serial vs pipelined, on
        # a write-heavy and a scan-heavy point (A, E) where the sync
        # barrier matters most
        for mode in pipeline:
            for wl in ("A", "E"):
                hp, _ = build_stores(n_items, shards=ns, baseline=False)
                r = run_scheduled(hp, uniform_sampler(n_items, seed=3),
                                  n_ops=n_ops, n_items=n_items,
                                  pipeline=mode, **WORKLOADS[wl])
                results[f"{wl}/pipeline{tag}/{mode}"] = r
                emit(f"ycsb_{wl}{tag.replace('/', '_')}_{mode}",
                     1e6 / r["ops_per_s"],
                     f"stall_s={r['sync_stall_s']:.3f} "
                     f"stall_frac={r['stall_fraction']:.2f} "
                     f"syncs={r['syncs']} epochs={r['epochs']} "
                     f"occ={r['lane_occupancy']:.2f}")
    return results


if __name__ == "__main__":
    run(shards=(1, 4), pipeline=("serial", "pipelined"), replicas=(1, 2, 4),
        feed=("log", "delta"), relay_depth=(0, 2))
