"""Benchmark entry point — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (shared harness contract).
Absolute CPU-container numbers are not the paper's Mops/s; the reproduced
artifacts are the relative trends and the analytic byte model — see
benchmarks/common.py and EXPERIMENTS.md.

``--shards 1,4`` sweeps the shard axis for the sections that serve the live
range-sharded store (YCSB, cloud-storage).  ``--pipeline serial,pipelined``
sweeps the scheduler's epoch-pipeline modes for the sections that drive it
(YCSB, latency), reporting pipelined-vs-serial throughput and sync-stall
time.  ``--replicas 1,2,4`` sweeps per-shard replica counts for the
replicated read-spreading sections (YCSB), reporting the
read-throughput-vs-replicas and sync-bytes-amplification curves.
``--feed log,delta`` sweeps the follower feed (log-shipped wire-stream
replay vs dirty-image-row delta) and ``--relay-depth 0,2`` the relay-tree
depth the feed payload fans out through, for the replicated sections.
``--tiny`` shrinks every section's workload for CI smoke runs.  A summary
table of every section's sync meters (log entries, wire bytes, sync bytes,
replica amplification) prints after the sweep; ``--metrics`` adds a second
table sourced from the telemetry REGISTRY snapshots the scheduled sections
attach (core/telemetry.py — device-cache hit rate, image-DMA counts, sync
stall fraction, lane occupancy), raises the per-request trace sample
rate, and writes ``experiments/metrics_snapshot.json`` plus a
Perfetto-loadable ``experiments/bench_trace.json`` next to the results.

The scheduler-driven sections run through the typed service API
(``HoneycombService.submit``/``drain`` with first-class op messages —
core/api.py); ``service_api_smoke`` additionally round-trips every request
through the wire codec and asserts monotone serving-version stamps on a
replicated sharded store.
"""
from __future__ import annotations

import argparse
import inspect
import json
import time
from pathlib import Path

from repro.compile_cache import enable_compile_cache

from . import (bytes_model, cache_lb, cloud_storage, common, key_size,
               latency, log_block, mvcc_cost, roofline, scan_size,
               service_smoke, ycsb)

SECTIONS = [
    ("service_api_smoke", service_smoke.run),
    ("fig10_ycsb", ycsb.run),
    ("fig11_cloud_storage", cloud_storage.run),
    ("fig12_latency", latency.run),
    ("fig13_scan_size", scan_size.run),
    ("fig14_key_size", key_size.run),
    ("fig15_mvcc", mvcc_cost.run),
    ("fig16_cache_lb", cache_lb.run),
    ("fig17_log_block", log_block.run),
    ("sec3.1_bytes_model", bytes_model.run),
    ("roofline", roofline.run),
]


# --tiny workload overrides, applied to any section parameter they name
TINY = {"n_items": 512, "n_ops": 192, "reps": 2}


def print_sync_summary(results: dict) -> None:
    """One table of every benchmark run's sync meters: write log entries /
    append-only wire bytes (the paper's log-block accounting), dirty-row
    sync bytes, and the replication amplification bytes the follower delta
    feed added on top — surfaced here so the traffic story is one screen,
    not scattered across sections (log_block.py keeps the deep dive)."""
    rows = []
    for section, recs in results.items():
        if not isinstance(recs, dict):
            continue
        for key, rec in recs.items():
            sync = rec.get("sync") if isinstance(rec, dict) else None
            if isinstance(sync, dict) and "log_wire_bytes" in sync:
                rows.append((f"{section}/{key}",
                             sync.get("log_entries", 0),
                             sync["log_wire_bytes"],
                             sync.get("bytes_synced", 0),
                             sync.get("image_dma_count", 0),
                             sync.get("feed_bytes",
                                      sync.get("replication_bytes", 0)),
                             sync.get("relay_hop_bytes", 0),
                             sync.get("log_fallback_epochs", 0)))
    if not rows:
        return
    print("# --- sync traffic summary ---")
    print(f"# {'run':<44} {'log_ents':>8} {'wire_B':>10} "
          f"{'sync_B':>12} {'img_dmas':>8} {'feed_B':>12} "
          f"{'relay_B':>12} {'fallbacks':>9}")
    for name, ents, wire, synced, dmas, feed, relay, fb in rows:
        print(f"# {name:<44} {ents:>8} {wire:>10} {synced:>12} "
              f"{dmas:>8} {feed:>12} {relay:>12} {fb:>9}")


def _mval(metrics: dict, name: str, **labels) -> float:
    """Sum the scalar registry samples named ``name`` (optionally filtered
    by label equality) out of a flat ``name{k=v,...}`` snapshot."""
    tot = 0.0
    for k, v in metrics.items():
        base, _, rest = k.partition("{")
        if base != name or isinstance(v, dict):
            continue
        if labels:
            ls = dict(p.split("=", 1)
                      for p in rest.rstrip("}").split(",") if "=" in p)
            if any(ls.get(a) != str(b) for a, b in labels.items()):
                continue
        tot += v
    return tot


def print_metrics_summary(results: dict) -> None:
    """One table per --metrics run sourced from the REGISTRY snapshots the
    scheduled sections attach (core/telemetry.py; not hand-picked stats
    fields): device-cache hit rate, image-DMA count, and the scheduler's
    sync stall fraction and lane occupancy."""
    rows = []
    for section, recs in results.items():
        if not isinstance(recs, dict):
            continue
        for key, rec in recs.items():
            m = rec.get("metrics") if isinstance(rec, dict) else None
            if not m:
                continue
            rows.append((f"{section}/{key}",
                         _mval(m, "cache_device_hit_rate"),
                         int(_mval(m, "sync_image_dma_count",
                                   src="primary")),
                         _mval(m, "pipeline_stall_fraction",
                               src="scheduler"),
                         _mval(m, "pipeline_lane_occupancy",
                               src="scheduler")))
    if not rows:
        return
    print("# --- registry metrics summary ---")
    print(f"# {'run':<44} {'dev_hit':>7} {'img_dmas':>8} {'stall_fr':>8} "
          f"{'lane_occ':>8}")
    for name, hit, dmas, stall, occ in rows:
        print(f"# {name:<44} {hit:>7.3f} {dmas:>8} {stall:>8.3f} "
              f"{occ:>8.3f}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("only", nargs="?", default=None,
                    help="run only sections whose name contains one of "
                         "these comma-separated substrings")
    ap.add_argument("--shards", default="1",
                    help="comma-separated shard counts for the sharded "
                         "sections (e.g. 1,4)")
    ap.add_argument("--pipeline", default="",
                    help="comma-separated scheduler pipeline modes to sweep "
                         "(e.g. serial,pipelined); empty skips the axis")
    ap.add_argument("--replicas", default="",
                    help="comma-separated per-shard replica counts for the "
                         "read-spreading sections (e.g. 1,2,4); empty "
                         "skips the axis")
    ap.add_argument("--feed", default="",
                    help="comma-separated follower feeds to sweep for the "
                         "replicated sections (e.g. log,delta); empty "
                         "uses the default log feed")
    ap.add_argument("--relay-depth", default="",
                    help="comma-separated relay-tree depths to sweep for "
                         "the replicated sections (e.g. 0,2); empty uses "
                         "the flat primary-feeds-all topology")
    ap.add_argument("--metrics", action="store_true",
                    help="print a registry metrics summary table (hit "
                         "rates, DMA counts, stall fraction, lane "
                         "occupancy) after the sweep, raise the trace "
                         "sample rate, and write the last section's "
                         "metrics snapshot + a Perfetto trace next to "
                         "bench_results.json")
    ap.add_argument("--tiny", action="store_true",
                    help="shrink workloads to smoke-test sizes (CI)")
    ap.add_argument("--strict", action="store_true",
                    help="exit non-zero if any requested section errored "
                         "(CI gates on this; the default keeps sweeping)")
    args = ap.parse_args()
    print(f"# compile cache: {enable_compile_cache()}", flush=True)
    shards = tuple(int(s) for s in args.shards.split(","))
    pipeline = tuple(m for m in args.pipeline.split(",") if m)
    replicas = tuple(int(r) for r in args.replicas.split(",") if r)
    feed = tuple(f for f in args.feed.split(",") if f)
    relay_depth = tuple(int(d) for d in args.relay_depth.split(",") if d != "")
    only = tuple(t for t in (args.only or "").split(",") if t)
    if args.metrics:
        common.TRACE_SAMPLE_RATE = 1 / 16   # every 16th request traced
    results = {}
    for name, fn in SECTIONS:
        if only and not any(tok in name for tok in only):
            continue
        params = inspect.signature(fn).parameters
        kwargs = {}
        if "shards" in params:
            kwargs["shards"] = shards
        if "pipeline" in params:
            kwargs["pipeline"] = pipeline
        if "replicas" in params:
            kwargs["replicas"] = replicas
        if "feed" in params and feed:
            kwargs["feed"] = feed
        if "relay_depth" in params and relay_depth:
            kwargs["relay_depth"] = relay_depth
        if args.tiny:
            kwargs.update({k: v for k, v in TINY.items() if k in params})
        print(f"# --- {name} ---", flush=True)
        t0 = time.perf_counter()
        try:
            results[name] = fn(**kwargs)
        except Exception as e:  # noqa: BLE001 — keep the suite running
            print(f"{name},0.00,ERROR:{type(e).__name__}:{e}")
            results[name] = {"error": str(e)}
        print(f"# {name} took {time.perf_counter() - t0:.1f}s", flush=True)
    print_sync_summary(results)
    out = Path("experiments/bench_results.json")
    out.parent.mkdir(exist_ok=True)
    out.write_text(json.dumps(results, indent=1, default=str))
    print(f"# results -> {out}")
    if args.metrics:
        print_metrics_summary(results)
        tm = common.LAST_TELEMETRY
        if tm is not None:
            snap = out.parent / "metrics_snapshot.json"
            snap.write_text(json.dumps(tm.snapshot(), indent=1))
            trace = out.parent / "bench_trace.json"
            trace.write_text(json.dumps(tm.chrome_trace()))
            print(f"# metrics -> {snap}  trace -> {trace} "
                  f"({len(tm.traces())} sampled)")
    errored = [n for n, r in results.items()
               if isinstance(r, dict) and "error" in r]
    if args.strict and errored:
        raise SystemExit(f"sections errored: {', '.join(errored)}")


if __name__ == "__main__":
    main()
