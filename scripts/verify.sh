#!/usr/bin/env bash
# Tier-1 verification gate (fast, deterministic).
#
#   scripts/verify.sh           # fast gate: everything not marked slow
#   scripts/verify.sh --all     # full suite, including slow tests
#   scripts/verify.sh --analyze # honeylint static analysis + EpochSan:
#                               # the repo-specific AST lint pass
#                               # (raw-clock / aliased-publish /
#                               # magic-offset / stats-collect /
#                               # bare-except rules + the pinned
#                               # NODE_SCHEMA/wire-codec golden), the
#                               # kernel jaxpr checker over every Pallas
#                               # entry point (f64 / callbacks /
#                               # input_output_aliases on in-place
#                               # scatters / single-dispatch fusion /
#                               # VMEM block budget), both merged into
#                               # experiments/analysis_report.json, then
#                               # the epoch/replica test surface re-run
#                               # under HONEYCOMB_EPOCHSAN=1 (runtime
#                               # sanitizer at the staging/flip/dispatch/
#                               # GC seams); nonzero on any finding
#   scripts/verify.sh --smoke  # benchmark smoke only (tiny sizes): the
#                              # HoneycombService smoke (typed op messages,
#                              # submit_many + drain over a replicated
#                              # sharded store, wire-codec roundtrip),
#                              # serial-vs-pipelined YCSB+latency plus a
#                              # --replicas 1,2 read-spreading sweep and a
#                              # --feed log,delta x --relay-depth 0,2
#                              # follower-feed amplification sweep, the
#                              # log-block sweep on BOTH snapshot layouts
#                              # (packed one-DMA-per-dirty-node vs legacy
#                              # per-field), a --read-backend
#                              # fused,reference sweep of the device read
#                              # path (fused megakernels + VMEM cache tier
#                              # vs the jnp reference), and both
#                              # store_dryrun LIVE smokes (sharded +
#                              # replicated with the log-shipped feed
#                              # engaged and fused-vs-reference equality
#                              # + vmem_hits asserted) on the packed
#                              # layout, with telemetry asserts: the
#                              # Prometheus export parses, key meters are
#                              # nonzero, and a sampled replicated trace
#                              # carries the full submit->resolve span
#                              # chain; results land in
#                              # experiments/bench_results.json (+
#                              # metrics_snapshot.json, bench_trace.json)
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [[ "${1:-}" == "--all" ]]; then
    exec python -m pytest -x -q
fi
if [[ "${1:-}" == "--analyze" ]]; then
    # static half: lint rules + schema golden + kernel jaxpr checks;
    # exits nonzero on any unbaselined finding
    python -m repro.analysis --json experiments/analysis_report.json
    # runtime half: the epoch/snapshot protocol surface under EpochSan
    # (strict mode — the first violated seam invariant raises there)
    HONEYCOMB_EPOCHSAN=1 python -m pytest -x -q -m "not slow" \
        tests/test_analysis.py tests/test_pipeline_engine.py \
        tests/test_replica.py tests/test_delta_sync.py \
        tests/test_scheduler_cache.py tests/test_log_feed.py
    exit 0
fi
if [[ "${1:-}" == "--smoke" ]]; then
    python -m benchmarks.run \
        service_api,fig10_ycsb,fig12_latency,fig17_log_block \
        --tiny --pipeline serial,pipelined --replicas 1,2 \
        --feed log,delta --relay-depth 0,2 \
        --layout packed,legacy --read-backend fused,reference \
        --metrics --strict
    # live deployment-shape smokes on the packed layout: assert the
    # one-image-DMA-per-dirty-node invariant survives the full stack,
    # and that the replicated store actually shipped (and replayed) the
    # log feed rather than silently regressing to image-row deltas
    python - <<'EOF'
import json
from repro.launch.store_dryrun import live_replicated_smoke, live_sharded_smoke
sh = live_sharded_smoke(shards=2, n_items=256, batch=32)
assert sh["layout"] == "packed" and sh["image_dma_count"] > 0, sh
# fused read path: the cache tier actually served descend levels from
# VMEM, and the smoke's in-place fused-vs-reference equality held
assert sh["read_path"]["backend"] == "fused", sh
assert sh["read_path"]["vmem_hits"] > 0, sh
assert sh["read_path"]["fused_matches_reference"], sh
rp = live_replicated_smoke(shards=2, replicas=2, n_items=256, batch=32)
assert rp["layout"] == "packed" and rp["primary_image_dmas"] > 0, rp
feed = rp["feed"]
assert feed["log_feed_epochs"] > 0 and feed["log_replays"] > 0, feed
assert feed["log_bytes"] > 0 and feed["wire_bytes"] > 0, feed
# followers inherited the cache tier over the feeds and their fused
# reads matched the reference fallback
assert rp["read_path"]["vmem_hits"] > 0, rp
assert rp["read_path"]["followers_cache_resident"], rp
assert rp["read_path"]["fused_matches_reference"], rp
# telemetry (core/telemetry.py): the Prometheus export must PARSE and the
# key meters of every wired stats surface must be live on the smokes
from repro.core import parse_prometheus, prom_value
for label, smoke in (("sharded", sh), ("replicated", rp)):
    tele = smoke["telemetry"]
    pv = parse_prometheus(tele["prometheus"])
    for meter in ("hc_sync_bytes_synced", "hc_sync_image_dma_count",
                  "hc_tree_puts", "hc_cache_vmem_hits",
                  "hc_pipeline_flips", "hc_read_batches"):
        assert prom_value(pv, meter) > 0, (label, meter, tele["prometheus"])
    # the read-dispatch split: host time blocked on the device's answers
    assert prom_value(pv, "hc_pipeline_fetch_s", src="store") > 0, \
        (label, tele["prometheus"])
    assert tele["sampled_traces"] > 0, (label, tele)
assert prom_value(parse_prometheus(rp["telemetry"]["prometheus"]),
                  "hc_replication_log_feed_epochs") > 0, rp["telemetry"]
# one sampled replicated pipelined trace shows the full lifecycle chain
# with the (shard, replica, epoch, serving_version) stamps attached
tr = rp["telemetry"]["last_trace"]
spans = tr["spans"]
assert spans[0] == "submit" and spans[-1] == "resolve", tr
assert "dispatch" in spans or tr["kind"] in ("put", "update"), tr
assert {"shard", "replica", "epoch", "serving_version"} <= set(tr["tags"]), tr
print(json.dumps({"live_sharded": sh, "live_replicated": rp},
                 indent=1, default=str))
EOF
    # the smoke's --metrics artifacts exist and the trace file is
    # Chrome-trace-shaped (CI uploads both next to bench_results.json)
    python - <<'EOF'
import json
from pathlib import Path
snap = json.loads(Path("experiments/metrics_snapshot.json").read_text())
assert any(k.startswith("sync_") for k in snap), list(snap)[:5]
trace = json.loads(Path("experiments/bench_trace.json").read_text())
assert isinstance(trace.get("traceEvents"), list), trace.keys()
print(f"metrics snapshot keys: {len(snap)}; "
      f"trace events: {len(trace['traceEvents'])}")
EOF
    exit 0
fi
exec python -m pytest -x -q -m "not slow"
