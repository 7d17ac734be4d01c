"""Telemetry layer tests (core/telemetry.py).

Pins: histogram percentile accuracy against a sorted-array oracle (random
and adversarial distributions) and exact merge semantics; the one
injectable clock shared by shard/replica/scheduler; registry aggregation
equal to the old per-layer sums (the merge_stats move is a refactor, not
a behaviour change); the sampled trace lifecycle (span ordering,
epoch/serving-version tags matching the Response stamps on a replicated
pipelined store, ring-buffer bound, rate-0 => nothing allocated); the
Prometheus export round trip; the all-six-surfaces snapshot; the program
spans on the profiler's clock and the read-dispatch split they meter; and
the per-program compile counter.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CLOCK, Get, Histogram, HoneycombConfig,
                        HoneycombService, HoneycombStore, Put,
                        ReplicationConfig, Scan, ShardedHoneycombStore,
                        TelemetryConfig, Tracer, Update, merge_stats,
                        parse_prometheus, prom_value, uniform_int_boundaries)
from repro.core.telemetry import SPAN_PREFIX, Telemetry
from repro.core import replica as replica_mod
from repro.core import scheduler as scheduler_mod
from repro.core import shard as shard_mod
from repro.core.keys import int_key
from repro.core.shard import SyncStats

N_ITEMS = 96


def _traffic(svc, n_items, ops=48, seed=3):
    rng = np.random.default_rng(seed)
    tickets = svc.submit_many(
        op for _ in range(ops // 2)
        for op in (Update(int_key(int(rng.integers(0, n_items))), b"t" * 8),
                   Get(int_key(int(rng.integers(0, n_items))))))
    out = svc.drain()
    return tickets, out


@pytest.fixture(scope="module")
def replicated_service():
    """One replicated sharded pipelined store + a rate-1 traced service,
    drained once — the shared subject for the aggregation/trace tests."""
    st = ShardedHoneycombStore(
        HoneycombConfig(), heap_capacity=512, shards=2,
        boundaries=uniform_int_boundaries(N_ITEMS, 2),
        replication=ReplicationConfig(replicas=2, policy="round_robin"))
    rng = np.random.default_rng(7)
    for i in rng.permutation(N_ITEMS):
        st.put(int_key(int(i)), b"v" * 8)
    st.export_snapshot()
    svc = HoneycombService(
        st, batch_size=8, pipeline="pipelined",
        telemetry=TelemetryConfig(trace_sample_rate=1.0,
                                  trace_capacity=4096))
    tickets, out = _traffic(svc, N_ITEMS)
    epochs_after = list(st.per_shard_epochs)
    return st, svc, tickets, out, epochs_after


# ----------------------------------------------------------------- histogram
BUCKET_FACTOR = 10.0 ** (1.0 / 16)       # one default bucket's ratio


def _oracle(data, p):
    return float(np.percentile(np.asarray(data), p,
                               method="inverted_cdf"))


@pytest.mark.parametrize("dist", ["lognormal", "uniform", "heavy_tail",
                                  "two_point", "constant"])
def test_histogram_percentiles_vs_oracle(dist):
    rng = np.random.default_rng(11)
    data = {
        "lognormal": np.exp(rng.normal(-8.0, 1.5, 4000)),
        "uniform": rng.uniform(1e-5, 1e-2, 4000),
        "heavy_tail": np.concatenate([rng.uniform(1e-6, 1e-5, 3900),
                                      rng.uniform(0.1, 10.0, 100)]),
        "two_point": np.array([1e-4] * 900 + [1e-1] * 100),
        "constant": np.full(1000, 3.3e-3),
    }[dist]
    h = Histogram()
    for v in data:
        h.record(float(v))
    assert h.count == len(data)
    assert h.total == pytest.approx(float(np.sum(data)), rel=1e-9)
    assert h.vmin == float(np.min(data)) and h.vmax == float(np.max(data))
    for p in (50, 95, 99, 99.9):
        est, want = h.percentile(p), _oracle(data, p)
        # accuracy contract: within one bucket ratio of the rank oracle
        # (plus epsilon for the clamp at the observed extremes)
        assert want / (BUCKET_FACTOR * 1.01) <= est <= \
            want * BUCKET_FACTOR * 1.01, (dist, p, est, want)


def test_histogram_constant_is_exact():
    h = Histogram()
    for _ in range(100):
        h.record(2.5e-4)
    for p in (50, 99, 99.9):
        assert h.percentile(p) == pytest.approx(2.5e-4)


def test_histogram_under_overflow_and_weighted():
    h = Histogram(lo=1e-3, hi=1e0)
    h.record(1e-6, n=10)                 # underflow bucket
    h.record(50.0, n=2)                  # overflow bucket
    assert h.count == 12
    assert h.percentile(50) == pytest.approx(1e-6)   # clamped to vmin
    assert h.percentile(99.9) == pytest.approx(50.0)  # clamped to vmax
    hw, hs = Histogram(), Histogram()
    hw.record(1e-4, n=5)
    for _ in range(5):
        hs.record(1e-4)
    assert hw.counts == hs.counts and hw.count == hs.count
    assert hw.total == pytest.approx(hs.total)


def test_histogram_merge_equals_union():
    rng = np.random.default_rng(5)
    a, b = rng.uniform(1e-6, 1e-1, 500), np.exp(rng.normal(-6, 2, 500))
    ha, hb, hu = Histogram(), Histogram(), Histogram()
    for v in a:
        ha.record(float(v))
    for v in b:
        hb.record(float(v))
    for v in np.concatenate([a, b]):
        hu.record(float(v))
    ha.merge(hb)
    assert ha.counts == hu.counts
    assert ha.count == hu.count
    assert ha.total == pytest.approx(hu.total)
    assert ha.vmin == hu.vmin and ha.vmax == hu.vmax
    for p in (50, 95, 99, 99.9):
        assert ha.percentile(p) == hu.percentile(p)
    with pytest.raises(AssertionError):
        ha.merge(Histogram(lo=1e-6))     # geometry mismatch refuses


# --------------------------------------------------------------------- clock
def test_one_clock_everywhere():
    """The satellite's point: shard, replica and scheduler read THE same
    injectable clock object — freezing it freezes all three."""
    assert shard_mod._now is CLOCK
    assert replica_mod._now is CLOCK
    assert scheduler_mod._now is CLOCK
    with CLOCK.frozen(100.0):
        assert shard_mod._now() == 100.0
        assert scheduler_mod._now() == 100.0
        CLOCK.advance(2.5)
        assert replica_mod._now() == 102.5
    t0 = CLOCK()                          # unfrozen again: monotonic
    assert CLOCK() >= t0


def test_frozen_clock_zeroes_stage_timings():
    st = ShardedHoneycombStore(HoneycombConfig(), heap_capacity=512,
                               shards=1)
    for i in range(32):
        st.put(int_key(i), b"v" * 8)
    with CLOCK.frozen(50.0):
        svc = HoneycombService(st, batch_size=8)
        _traffic(svc, 32, ops=16)
        assert svc.stats.admit_s == 0.0
        assert svc.stats.export_s == 0.0
        assert svc.stats.sync_stall_s == 0.0
        assert svc.stats.dispatch_s == 0.0
        ps = st.pipeline_stats
        assert ps.dispatched_lanes > 0        # reads did dispatch
        assert ps.pack_s == 0.0
        assert ps.fetch_s == 0.0
        assert ps.decode_s == 0.0


# -------------------------------------------------- aggregation regression
def test_registry_aggregates_equal_per_layer_sums(replicated_service):
    st, svc, _, _, _ = replicated_service
    tm = svc.telemetry
    # sync (primaries): registry == router aggregate == hand sum
    assert tm.value("sync_log_entries", src="primary") == \
        st.sync_stats.log_entries == \
        sum(sh.sync_stats.log_entries for sh in st.shards)
    assert tm.value("sync_bytes_synced", src="primary") == \
        st.sync_stats.bytes_synced
    # replication amplification (followers)
    assert tm.value("sync_bytes_synced", src="followers") == \
        st.replication_stats.bytes_synced == \
        sum(f.sync_stats.bytes_synced
            for sh in st.shards for f in sh.followers)
    # tree, pipeline (store side), cache, feed
    assert tm.value("tree_puts") == st.stats.puts == \
        sum(sh.stats.puts for sh in st.shards)
    assert tm.value("pipeline_flips", src="store") == \
        st.pipeline_stats.flips
    assert tm.value("cache_vmem_hits") == st.cache_stats.vmem_hits == \
        sum(sh.cache_stats.vmem_hits for sh in st.shards)
    assert tm.value("replication_feed_bytes") == st.feed_stats.feed_bytes
    # scheduler meters come in through the same registry
    assert tm.value("scheduler_applied_writes") == \
        svc.scheduler.applied_writes
    # delta_fraction merges by MAX (SyncStats.merge), not sum
    assert st.sync_stats.delta_fraction == \
        max(sh.sync_stats.delta_fraction for sh in st.shards)


def test_merge_stats_matches_manual_field_sums():
    a = SyncStats(snapshots=2, bytes_synced=100, delta_fraction=0.25)
    b = SyncStats(snapshots=3, bytes_synced=50, delta_fraction=0.75)
    agg = merge_stats([a, b], SyncStats)
    assert agg.snapshots == 5 and agg.bytes_synced == 150
    assert agg.delta_fraction == 0.75     # max-merged, per SyncStats.merge


def test_six_surfaces_in_one_snapshot(replicated_service):
    _, svc, _, _, _ = replicated_service
    snap = svc.metrics_snapshot()
    prefixes = {k.split("{")[0].split("_")[0] for k in snap}
    for want in ("sync", "tree", "pipeline", "cache", "replication",
                 "read", "scheduler"):
        assert want in prefixes, (want, sorted(prefixes))
    # the kernel meter rode in as plain tuples with op/backend labels
    assert any(k.startswith("read_batches{") for k in snap), sorted(snap)[:8]


# ---------------------------------------------------------------- exporters
def test_prometheus_round_trip(replicated_service):
    _, svc, _, _, _ = replicated_service
    text = svc.prometheus()
    parsed = parse_prometheus(text)      # raises on any unparseable line
    assert prom_value(parsed, "hc_sync_log_entries", src="primary") == \
        svc.telemetry.value("sync_log_entries", src="primary")
    assert prom_value(parsed, "hc_tree_puts") == \
        svc.telemetry.value("tree_puts")
    # the read-dispatch split of the store side exports as a counter (the
    # text exposition prints six significant digits)
    fetch_s = prom_value(parsed, "hc_pipeline_fetch_s", src="store")
    assert fetch_s > 0
    assert fetch_s == pytest.approx(
        svc.telemetry.value("pipeline_fetch_s", src="store"), rel=1e-5)
    # histograms export as summaries with quantile + sum + count series
    assert prom_value(parsed, "hc_request_latency_seconds_count") > 0
    assert "hc_request_latency_seconds" in parsed
    with pytest.raises(ValueError):
        parse_prometheus("not a metric line at all {")


def test_chrome_trace_export(replicated_service):
    _, svc, _, _, _ = replicated_service
    ct = svc.chrome_trace()
    assert ct["traceEvents"], "no events exported"
    ev = ct["traceEvents"][0]
    for field in ("name", "ph", "ts", "dur", "pid", "tid", "args"):
        assert field in ev
    assert ev["ph"] == "X"
    assert all(e["dur"] >= 0.0 for e in ct["traceEvents"])


# ------------------------------------------------------------------ tracing
def test_trace_lifecycle_and_response_stamps(replicated_service):
    st, svc, tickets, out, epochs_after = replicated_service
    traces = {t.rid: t for t in svc.traces()}
    assert len(traces) == len(tickets)    # rate 1.0: every request traced
    for ticket in tickets:
        tr = traces[ticket.rid]
        resp = out[ticket.rid]
        names = tr.span_names()
        assert names[0] == "submit" and names[-1] == "resolve", names
        if ticket.op.IS_WRITE:
            assert "admit" in names, names
        else:
            assert "dispatch" in names, names
        assert "export_stage" in names and "flip" in names, names
        assert names.index("export_stage") < names.index("flip")
        # span times are ordered along the lifecycle
        starts = [s.t0 for s in tr.spans]
        assert starts == sorted(starts), names
        assert tr.t1 >= tr.t0
        # the finish stamps ARE the response stamps
        assert tr.tags["shard"] == resp.shard
        assert tr.tags["replica"] == resp.replica
        assert tr.tags["serving_version"] == resp.serving_version
        assert tr.tags["status"] == resp.status
        assert tr.tags["epoch"] == epochs_after[resp.shard]
        # the dispatch span carries the serving pins too
        if not ticket.op.IS_WRITE:
            disp = tr.spans[names.index("dispatch")]
            assert disp.tags["serving_version"] == resp.serving_version
            assert disp.tags["replica"] == resp.replica


def test_trace_ring_buffer_bound():
    st = ShardedHoneycombStore(HoneycombConfig(), heap_capacity=512,
                               shards=1)
    for i in range(32):
        st.put(int_key(i), b"v" * 8)
    svc = HoneycombService(
        st, batch_size=8,
        telemetry=TelemetryConfig(trace_sample_rate=1.0, trace_capacity=8))
    tickets, _ = _traffic(svc, 32, ops=40)
    tr = svc.traces()
    assert len(tr) == 8                   # bounded ring
    # the ring keeps the newest traces
    assert [t.rid for t in tr] == \
        sorted(t.rid for t in tickets)[-8:]
    assert svc.telemetry.tracer.sampled == len(tickets)


def test_sample_rate_zero_allocates_nothing():
    st = ShardedHoneycombStore(HoneycombConfig(), heap_capacity=512,
                               shards=1)
    for i in range(16):
        st.put(int_key(i), b"v" * 8)
    svc = HoneycombService(st, batch_size=8)      # default rate 0
    assert svc.telemetry is not None
    assert svc.telemetry.tracer is None           # no tracer object at all
    _traffic(svc, 16, ops=8)
    assert svc.traces() == []
    # the submit->resolve histogram only fills from traces => stays empty
    assert svc.scheduler._req_hist.count == 0


def test_tracer_deterministic_sampling():
    tr = Tracer(sample_rate=0.25, capacity=16)
    live = [tr.begin(rid, "get") is not None for rid in range(12)]
    assert live == [True, False, False, False] * 3
    assert tr.live_count == 3 and tr.sampled == 3
    assert not tr.is_live(1)              # unsampled rid allocated nothing
    tr.span(1, "dispatch", 0.0, 1.0)      # no-op, not an error
    assert tr.finish(1) is None


def test_disabled_telemetry_is_absent():
    st = ShardedHoneycombStore(HoneycombConfig(), heap_capacity=512,
                               shards=1)
    for i in range(16):
        st.put(int_key(i), b"v" * 8)
    svc = HoneycombService(st, batch_size=8,
                           telemetry=TelemetryConfig(enabled=False))
    assert svc.telemetry is None
    assert svc.scheduler.telemetry is None
    _, out = _traffic(svc, 16, ops=8)
    assert all(r.status in ("ok", "not_found") for r in out.values())
    assert svc.metrics_snapshot() == {}
    assert svc.prometheus() == ""
    assert svc.traces() == []
    assert svc.chrome_trace() == {"traceEvents": []}


def test_latency_histograms_fill_at_dispatch(replicated_service):
    """The read spans fill at dispatch: the store's pack/fetch/decode
    split is live and lies inside the scheduler's dispatch stage; the
    submit->resolve histogram covers every traced request."""
    st, svc, tickets, _, _ = replicated_service
    tm = svc.telemetry
    ps = st.pipeline_stats
    assert ps.pack_s > 0 and ps.fetch_s > 0 and ps.decode_s > 0
    assert ps.pack_s + ps.fetch_s + ps.decode_s <= svc.stats.dispatch_s
    assert tm.value("pipeline_decode_s", src="store") == \
        pytest.approx(ps.decode_s)
    req = tm.registry.histogram("request_latency_seconds",
                                layer="scheduler")
    assert req.count == len(tickets)


# every program span a drain with writes and SCANs opens (core/telemetry.py)
SPAN_NAMES = ("admit", "export", "sync.barrier", "sync.refresh",
              "sync.pack", "sync.put", "sync.launch", "dispatch",
              "read.pack", "read.launch", "read.fetch", "read.decode",
              "read.host_scan")


def test_program_spans_land_in_profiler_trace(tmp_path):
    """One serial drain with writes, short SCANs and a SCAN too wide for
    the device (served by the host tree), under the JAX profiler on the
    CPU: every ``hc.*`` span is in the trace's host plane."""
    from jax.profiler import ProfileData
    cfg = HoneycombConfig()
    st = HoneycombStore(cfg, heap_capacity=512)
    for i in range(64):
        st.put(int_key(i), b"v" * 8)
    svc = HoneycombService(st, batch_size=8)
    wide = cfg.max_scan_items + 8
    with jax.profiler.trace(str(tmp_path)):
        tickets = svc.submit_many(
            [Put(int_key(100 + i), b"w" * 8) for i in range(4)]
            + [Scan(int_key(i), int_key(i + 3), expected_items=4)
               for i in range(8)]
            + [Scan(int_key(0), int_key(wide), expected_items=wide)])
        svc.drain()
    assert all(t.done for t in tickets)
    assert len(tickets[-1].result().items) == wide + 1
    assert st.pipeline_stats.host_scans == 1
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    missing = {SPAN_PREFIX + n for n in SPAN_NAMES} - names
    assert not missing, sorted(missing)


def test_programs_built_counts_by_name():
    tm = Telemetry()

    def programs_built_probe(x):
        return x * 3 + 1

    before = tm.value("programs_built", fun_name="jit(programs_built_probe)")
    jax.jit(programs_built_probe)(jnp.ones(3)).block_until_ready()
    assert tm.value("programs_built",
                    fun_name="jit(programs_built_probe)") == before + 1
    assert "hc_programs_built" in parse_prometheus(tm.to_prometheus())
