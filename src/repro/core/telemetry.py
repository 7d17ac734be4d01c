"""Unified telemetry: ONE metrics registry, log-bucketed latency
histograms, and sampled per-request lifecycle tracing (design doc — this
docstring IS the reference).

Honeycomb's evaluation lives on per-component breakdowns — PCIe traffic
split, cache hit rate, sync stall, tail latency (paper Figs. 13-16) — and
the serving stack already meters every layer (``SyncStats``,
``CacheStats``, ``PipelineStats``, ``FeedStats``, ``TreeStats``).  This
module gives those scattered dataclasses
one front door:

  * ``MetricsRegistry`` — counters, gauges, and log-bucketed latency
    ``Histogram``s (p50/p95/p99/p999), plus *registered sources*: any
    object with a ``collect()`` method (or a zero-arg callable returning
    samples) re-reads live at every ``collect()``/export, so registry
    snapshots are always current without push-style instrumentation.
  * ``Tracer`` — sampled per-request ``Trace``s recording spans across the
    full ticket lifecycle (submit -> admit -> export_stage -> flip ->
    dispatch -> resolve), each tagged with (shard, replica, epoch,
    serving_version) so a linearizability or freshness-redirect anomaly is
    diagnosable from one trace.  Traces land in a bounded ring buffer
    (``deque(maxlen=trace_capacity)``); sampling is deterministic (every
    ``round(1/rate)``-th request), and rate 0 means NO tracer object at
    all — the scheduler's hot path then only pays ``is None`` branches.
  * Three exporters — Prometheus text exposition (``to_prometheus``),
    JSON snapshot (``snapshot``), and Chrome trace-event JSON
    (``chrome_trace_events`` — load the file in Perfetto / chrome://tracing).
  * ``Clock`` — THE injectable monotonic clock (module singleton
    ``CLOCK``).  core/shard.py, core/replica.py and core/scheduler.py all
    alias it as their ``_now``, so a test freezes ONE clock
    (``CLOCK.frozen()``) instead of monkeypatching three modules.
  * ``span`` — THE program span: a ``jax.profiler.TraceAnnotation``
    named ``hc.<name>`` (on the profiler's clock, so a device trace can
    pin each idle gap on the host step it falls in; an inactive
    annotation costs well under a microsecond) that, given a stats
    object and a field, also adds the block's ``CLOCK`` duration to that
    field — each stage is timed once, and frozen-clock tests still read
    zero.  Spans sit at stage and batch level, never one per request:

      span               where                               feeds
      ------------------ ----------------------------------- ---------------------
      hc.admit           scheduler stage_admit               admit_s (scheduler)
      hc.export          scheduler stage_export, whole stage export_s (scheduler)
      hc.sync.barrier    serial block_until_ready /          sync_stall_s
                         pipelined flip                      (scheduler)
      hc.sync.refresh    begin_export: cache.refresh         trace only
      hc.sync.pack       dirty-row sort, padding, pack       trace only
      hc.sync.put        jnp.asarray uploads of the sync     trace only
      hc.sync.launch     the sync program's launch           trace only
      hc.sync.values     value-image upload (new value       trace only
                         slots, or the whole image)
      hc.dispatch        scheduler stage_dispatch, whole     dispatch_s (scheduler)
      hc.read.pack       padding, pack_keys, lane uploads    pack_s (store)
      hc.read.launch     the read program's launch           trace only
      hc.read.gather     gather_values' launch (a store      trace only
                         holding out-of-node values)
      hc.read.fetch      the one packed copy of results and  fetch_s (store)
                         meters
      hc.read.decode     the decode loops                    decode_s (store)
      hc.read.host_scan  truncated SCANs served by the tree  trace only
  * ``merge_stats`` — THE per-layer aggregation helper (moved here from
    core/router.py, which keeps ``aggregate_stats`` as the historical
    alias): merge per-shard / per-replica stats objects via their
    ``merge()`` when they define one, else plain field sums.

Wiring: ``HoneycombService`` builds a ``Telemetry`` bundle from
``ServiceConfig.telemetry`` (a ``TelemetryConfig``, core/config.py),
calls ``wire_store(store)`` — which registers every stats surface the
facade exposes (works for ``StoreShard``/``HoneycombStore``,
``ShardedHoneycombStore`` and bare ``ReplicaGroup`` alike, because they
all share the meter property names) — and hands the bundle to the
``OutOfOrderScheduler``, which records the request latency histogram
and drives the tracer.  Every ``Telemetry`` also turns on the
process-wide compile listener (``watch_compiles``) behind
``programs_built``.  ``enabled=False`` skips ALL of it:
no registry, no histograms, no tracer, byte-identical scheduler behaviour
to the pre-telemetry code.  Program spans are not part of the bundle:
the stage meters they feed run either way.

Metric-name reference (the names benchmarks columns, verify.sh asserts
and Prometheus scrapes key on — keep in sync with the ``collect()``
implementations; Prometheus names carry the ``hc_`` prefix):

  name                            type       layer      meaning
  ------------------------------- ---------- ---------- -------------------
  sync_snapshots                  counter    shard      exports that refreshed the device image
  sync_full_syncs                 counter    shard      wholesale republishes
  sync_delta_syncs                counter    shard      incremental scatters
  sync_bytes_synced               counter    shard      host->device array traffic
  sync_pagetable_commands         counter    shard      PCIe page-table updates
  sync_read_version_updates       counter    shard      PCIe read-version writes
  sync_delta_rows                 counter    shard      dirty node rows scattered
  sync_delta_fraction             gauge      shard      dirty fraction at last sync (worst shard)
  sync_log_entries                counter    shard      writes accepted (one log entry each)
  sync_log_wire_bytes             counter    shard      append-only wire-format bytes
  sync_image_dma_count            counter    shard      node-image DMA invocations
  sync_image_bytes                counter    shard      node-image payload bytes
  sync_log_replays                counter    shard      follower stagings replayed from the op log
  sync_value_slots_synced         counter    shard      out-of-node value slots uploaded to the value image
  sync_value_bytes_synced         counter    shard      their bytes
    (labels src="primary" — the serving path's own sync traffic — and
     src="followers" — the replication amplification on top of it)
  tree_puts/updates/deletes       counter    btree      host write ops applied
  tree_fast_path                  counter    btree      log-append fast-path writes
  tree_merges/splits/node_merges  counter    btree      structural maintenance ops
  tree_restarts/grows             counter    btree      CAS retries / root growths
  pipeline_runs                   counter    pipeline   scheduler epochs (src="scheduler")
  pipeline_admit_s                counter    pipeline   host write-apply wall seconds
  pipeline_export_s               counter    pipeline   standby staging wall seconds
  pipeline_dispatch_s             counter    pipeline   read-dispatch wall seconds
  pipeline_sync_stall_s           counter    pipeline   sync barrier wall seconds (hc.sync.barrier)
  pipeline_pack_s                 counter    pipeline   read key packing + upload seconds (hc.read.pack)
  pipeline_fetch_s                counter    pipeline   read result device->host seconds (hc.read.fetch)
  pipeline_decode_s               counter    pipeline   read result decode seconds (hc.read.decode)
  pipeline_device_values          counter    pipeline   out-of-node values served from the device value image
  pipeline_staged_exports         counter    pipeline   begin_export standby stagings
  pipeline_flips                  counter    pipeline   epoch publishes
  pipeline_read_batches           counter    pipeline   device read batches dispatched
  pipeline_dispatched_lanes       counter    pipeline   real requests inside device batches
  pipeline_padded_lanes           counter    pipeline   bucket_pow2 lanes those occupied
  pipeline_lane_occupancy         gauge      pipeline   dispatched/padded (1.0 = no waste)
  pipeline_stall_fraction         gauge      pipeline   sync stall share of epoch wall time
    (labels src="store" — the shard-side staging meters — and
     src="scheduler" — the scheduler's epoch-stage meters)
  cache_hits/misses/invalidations counter    cache      metadata-table probes (Section 5)
  cache_fast_path_reads           counter    cache      served from the packed cache
  cache_slow_path_reads           counter    cache      routed to the heap
  cache_fast_bytes/slow_bytes     counter    cache      bytes per pipe
  cache_vmem_hits                 counter    cache      fused-kernel levels served from VMEM
  cache_heap_gathers              counter    cache      fused-kernel levels gathered from heap
  cache_lb_routed                 counter    cache      cache hits the balancer re-routed
  cache_hit_rate                  gauge      cache      hits / probes
  cache_device_hit_rate           gauge      cache      vmem_hits / (vmem_hits + heap_gathers)
  replication_feed_bytes          counter    replica    bytes over all feed edges
  replication_wire_bytes          counter    replica    exact op wire stream bytes shipped
  replication_log_bytes           counter    replica    edge bytes of log-replay deliveries
  replication_fallback_bytes      counter    replica    image-delta bytes on fallback epochs
  replication_primary_egress_bytes counter   replica    bytes on primary->child edges
  replication_relay_hop_bytes     counter    replica    bytes on relay->child edges
  replication_log_feed_epochs     counter    replica    stagings shipped as a log payload
  replication_log_fallback_epochs counter    replica    log stagings that shipped the delta
  replication_delta_feed_epochs   counter    replica    stagings shipped as deltas by choice
  replication_full_feed_epochs    counter    replica    full-publish stagings
  replication_full_catchups       counter    replica    out-of-sync followers refed a full copy
  replication_catchup_bytes       counter    replica    bytes those catch-ups moved
  scheduler_dispatched_batches    counter    scheduler  device batches composed
  scheduler_dispatched_requests   counter    scheduler  read requests inside them
  scheduler_applied_writes        counter    scheduler  writes admitted host-side
  scheduler_syncs                 counter    scheduler  per-shard syncs its epochs ran
  request_latency_seconds         histogram  scheduler  submit->resolve (traced requests)
  programs_built                  counter    jax        programs compiled or fetched from
    (label fun_name=<jitted program>)                   the persistent cache, process-wide
  traces_sampled/traces_retained  counter/gauge tracer  sampling meters

Histogram geometry: geometric buckets, ``buckets_per_decade`` per decade
over [``lo``, ``hi``) plus underflow/overflow buckets.  Percentiles
return the geometric midpoint of the rank's bucket clamped to the
observed [min, max] — worst-case relative error is one bucket ratio
(~15% at the default 16 buckets/decade), which is what the oracle test
(tests/test_telemetry.py) pins.  ``merge`` requires identical geometry
(elementwise add), so per-shard histograms aggregate exactly.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import re
import time
from collections import deque
from typing import Any, Callable, Iterable

from jax.profiler import TraceAnnotation

from .config import TelemetryConfig

__all__ = [
    "CLOCK", "Clock", "Counter", "Gauge", "Histogram", "MetricSample",
    "MetricsRegistry", "SPAN_PREFIX", "Span", "Telemetry", "Trace", "Tracer",
    "chrome_trace_events", "merge_stats", "parse_prometheus",
    "programs_built", "prom_value", "samples_from", "span", "watch_compiles",
]


# ------------------------------------------------------------------ clock
class Clock:
    """THE injectable monotonic clock.  Calls through to
    ``time.perf_counter`` until frozen; a frozen clock returns a
    deterministic value that only ``advance()`` moves — so tests freeze
    ONE object instead of monkeypatching ``_now`` in three modules."""

    __slots__ = ("_frozen_at",)

    def __init__(self):
        self._frozen_at: float | None = None

    def __call__(self) -> float:
        at = self._frozen_at
        return time.perf_counter() if at is None else at

    now = __call__

    def freeze(self, at: float = 0.0) -> None:
        self._frozen_at = at

    def advance(self, dt: float) -> None:
        assert self._frozen_at is not None, "advance() needs a frozen clock"
        self._frozen_at += dt

    def unfreeze(self) -> None:
        self._frozen_at = None

    @contextlib.contextmanager
    def frozen(self, at: float = 0.0):
        """``with CLOCK.frozen(10.0): ...`` — deterministic time inside."""
        prev = self._frozen_at
        self.freeze(at)
        try:
            yield self
        finally:
            self._frozen_at = prev


#: The process-wide clock every timing site (shard, replica, scheduler,
#: tracer) reads.  Freeze THIS to freeze them all.
CLOCK = Clock()


# ------------------------------------------------------------ program spans
SPAN_PREFIX = "hc."


class span:
    """``with span("read.pack", stats, "pack_s"):`` — one program span.

    Opens a ``jax.profiler.TraceAnnotation`` named ``hc.<name>`` (it
    lands in a profiler trace only while one is recording) and, when
    ``stats`` is given, adds the block's ``CLOCK`` duration to
    ``stats.<field>``.  ``t0``/``t1`` keep the two clock readings for
    callers that also hand them to the sampled ``Tracer``."""

    __slots__ = ("_ann", "_stats", "_field", "t0", "t1")

    def __init__(self, name: str, stats=None, field: str | None = None):
        self._ann = TraceAnnotation(SPAN_PREFIX + name)
        self._stats, self._field = stats, field

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self.t0 = CLOCK()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = CLOCK()
        if self._stats is not None:
            setattr(self._stats, self._field,
                    getattr(self._stats, self._field) + self.t1 - self.t0)
        self._ann.__exit__(*exc)


# ------------------------------------------------------ programs JAX built
#: JAX's event around each backend compile (a persistent-cache fetch
#: included), recorded with the jitted program's ``fun_name``
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_PROGRAMS_BUILT: dict[str, int] = {}
_watching_compiles = False


def _on_duration_event(event: str, _secs: float, **kw) -> None:
    if event == COMPILE_EVENT:
        name = str(kw.get("fun_name", "unknown"))
        _PROGRAMS_BUILT[name] = _PROGRAMS_BUILT.get(name, 0) + 1


def watch_compiles() -> None:
    """Register the process-wide compile listener (once; later calls are
    no-ops).  Programs built before the first call are not counted."""
    global _watching_compiles
    if not _watching_compiles:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(
            _on_duration_event)
        _watching_compiles = True


def programs_built() -> list[tuple]:
    """``programs_built{fun_name=...}`` counter samples, one per program
    name the listener has seen."""
    return [("programs_built", "counter", float(n),
             {"layer": "jax", "fun_name": name})
            for name, n in sorted(_PROGRAMS_BUILT.items())]


# ------------------------------------------------------- samples & merges
@dataclasses.dataclass
class MetricSample:
    """One collected observation.  ``value`` is a float for counters and
    gauges and the ``Histogram`` object itself for histograms (exporters
    render quantiles/sum/count from it)."""
    name: str
    kind: str                    # "counter" | "gauge" | "histogram"
    value: Any
    labels: dict = dataclasses.field(default_factory=dict)

    def key(self) -> str:
        """Stable flat key: ``name{k=v,...}`` (name alone when unlabeled)."""
        if not self.labels:
            return self.name
        inner = ",".join(f"{k}={self.labels[k]}" for k in sorted(self.labels))
        return f"{self.name}{{{inner}}}"


def samples_from(obj, prefix: str, layer: str,
                 gauges: Iterable[str] = (),
                 derived: Iterable[str] = ()) -> list[MetricSample]:
    """The shared ``collect()`` implementation for the stats dataclasses:
    every numeric field becomes ``{prefix}_{field}`` (counter unless named
    in ``gauges``), and each ``derived`` property name is sampled as a
    gauge.  All samples carry ``layer=<layer>``."""
    out = []
    gauges = set(gauges)
    for f in dataclasses.fields(obj):
        v = getattr(obj, f.name)
        if not isinstance(v, (int, float)):
            continue
        kind = "gauge" if f.name in gauges else "counter"
        out.append(MetricSample(f"{prefix}_{f.name}", kind, float(v),
                                {"layer": layer}))
    for name in derived:
        out.append(MetricSample(f"{prefix}_{name}", "gauge",
                                float(getattr(obj, name)), {"layer": layer}))
    return out


def merge_stats(parts, factory):
    """Merge per-shard / per-replica stat objects into one ``factory()``.

    THE aggregation helper for every layer (formerly
    ``router.aggregate_stats``, which remains as an alias): objects with a
    ``merge()`` method merge through it (``SyncStats`` maxes
    ``delta_fraction``, ``PipelineStats`` sums); plain dataclasses
    (``TreeStats``, ``CacheStats``, ``FeedStats``) field-sum.  The
    registry's ``collect()`` path reads the SAME aggregates, so Prometheus
    numbers and per-layer meter properties can never disagree
    (pinned by tests/test_telemetry.py)."""
    agg = factory()
    if hasattr(agg, "merge"):
        for p in parts:
            agg.merge(p)
    else:
        for p in parts:
            for f in dataclasses.fields(agg):
                setattr(agg, f.name,
                        getattr(agg, f.name) + getattr(p, f.name))
    return agg


# -------------------------------------------------------------- instruments
class Counter:
    """Monotone accumulator (registry-owned; layer meters stay dataclasses
    and come in through ``collect()`` sources instead)."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        self.value += n


class Gauge:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)


class Histogram:
    """Log-bucketed latency histogram: geometric buckets over
    [``lo``, ``hi``) at ``buckets_per_decade`` resolution, plus
    underflow/overflow buckets.  See the module docstring for the accuracy
    contract; ``record(v, n)`` records ``n`` observations of ``v`` in one
    call."""

    __slots__ = ("lo", "hi", "bpd", "counts", "count", "total",
                 "vmin", "vmax")

    def __init__(self, lo: float = 1e-7, hi: float = 1e3,
                 buckets_per_decade: int = 16):
        assert lo > 0 and hi > lo and buckets_per_decade >= 1
        self.lo, self.hi, self.bpd = lo, hi, buckets_per_decade
        n = int(math.ceil(math.log10(hi / lo) * buckets_per_decade))
        self.counts = [0] * (n + 2)      # [underflow] + n buckets + [overflow]
        self.count = 0
        self.total = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf

    def _index(self, v: float) -> int:
        if v < self.lo:
            return 0
        if v >= self.hi:
            return len(self.counts) - 1
        i = 1 + int(math.log10(v / self.lo) * self.bpd)
        return min(i, len(self.counts) - 2)

    def _bounds(self, i: int) -> tuple[float, float]:
        if i == 0:
            return 0.0, self.lo
        if i == len(self.counts) - 1:
            return self.hi, math.inf
        return (self.lo * 10.0 ** ((i - 1) / self.bpd),
                self.lo * 10.0 ** (i / self.bpd))

    def record(self, v: float, n: int = 1) -> None:
        if n <= 0:
            return
        self.counts[self._index(v)] += n
        self.count += n
        self.total += v * n
        self.vmin = min(self.vmin, v)
        self.vmax = max(self.vmax, v)

    def merge(self, other: "Histogram") -> None:
        assert (self.lo, self.hi, self.bpd) == \
            (other.lo, other.hi, other.bpd), "histogram geometry mismatch"
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.count += other.count
        self.total += other.total
        self.vmin = min(self.vmin, other.vmin)
        self.vmax = max(self.vmax, other.vmax)

    def percentile(self, p: float) -> float:
        """Value at percentile ``p`` (0-100): geometric midpoint of the
        rank's bucket, clamped to the observed [min, max]."""
        if not self.count:
            return 0.0
        rank = max(1, math.ceil(p / 100.0 * self.count))
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= rank:
                blo, bhi = self._bounds(i)
                if i == 0:
                    mid = self.vmin
                elif i == len(self.counts) - 1:
                    mid = self.vmax
                else:
                    mid = math.sqrt(blo * bhi)
                return min(max(mid, self.vmin), self.vmax)
        return self.vmax

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def quantiles(self) -> dict:
        return {"p50": self.percentile(50), "p95": self.percentile(95),
                "p99": self.percentile(99), "p999": self.percentile(99.9)}

    def to_dict(self) -> dict:
        out = {"count": self.count, "sum": self.total, "mean": self.mean,
               "min": self.vmin if self.count else 0.0,
               "max": self.vmax if self.count else 0.0}
        out.update(self.quantiles())
        return out


# ---------------------------------------------------------------- registry
class MetricsRegistry:
    """One registry per ``Telemetry`` bundle: owns its counters/gauges/
    histograms (get-or-create by (name, labels)) and any number of
    registered SOURCES — zero-arg callables returning either an object
    with ``collect()`` or an iterable of samples (``MetricSample`` or
    ``(name, kind, value[, labels])`` tuples, the plain form
    ``Telemetry.wire_scheduler`` uses).  Sources are re-invoked on every
    ``collect()``, so exports always reflect live meter state."""

    def __init__(self):
        self._own: dict[tuple, tuple[str, Any]] = {}
        self._sources: list[tuple[Callable[[], Any], dict]] = []

    # ------------------------------------------------------- instruments
    def _get(self, name: str, kind: str, make, labels: dict):
        key = (name, tuple(sorted(labels.items())))
        hit = self._own.get(key)
        if hit is None:
            hit = (kind, make())
            self._own[key] = hit
        assert hit[0] == kind, f"{name} already registered as {hit[0]}"
        return hit[1]

    def counter(self, name: str, **labels) -> Counter:
        return self._get(name, "counter", Counter, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get(name, "gauge", Gauge, labels)

    def histogram(self, name: str, lo: float = 1e-7, hi: float = 1e3,
                  buckets_per_decade: int = 16, **labels) -> Histogram:
        return self._get(name, "histogram",
                         lambda: Histogram(lo, hi, buckets_per_decade),
                         labels)

    # ----------------------------------------------------------- sources
    def register(self, source, **labels) -> None:
        """Register a live stats source.  ``source`` is a zero-arg
        callable (preferred — re-read every collect) or an object with a
        ``collect()`` method; extra ``labels`` are stamped onto every
        sample it yields (e.g. ``src="scheduler"`` to keep two
        ``pipeline_*`` surfaces apart)."""
        fn = source if callable(source) else (lambda: source)
        self._sources.append((fn, labels))

    @staticmethod
    def _as_sample(x, extra: dict) -> MetricSample:
        if isinstance(x, MetricSample):
            s = x
        else:
            name, kind, value = x[0], x[1], x[2]
            labels = dict(x[3]) if len(x) > 3 else {}
            s = MetricSample(name, kind, value, labels)
        if extra:
            s = MetricSample(s.name, s.kind, s.value, {**s.labels, **extra})
        return s

    def collect(self) -> list[MetricSample]:
        out = []
        for (name, litems), (kind, inst) in self._own.items():
            value = inst if kind == "histogram" else inst.value
            out.append(MetricSample(name, kind, value, dict(litems)))
        for fn, extra in self._sources:
            got = fn()
            if got is None:
                continue
            if hasattr(got, "collect"):
                got = got.collect()
            for x in got:
                out.append(self._as_sample(x, extra))
        return out

    # --------------------------------------------------------- exporters
    def snapshot(self) -> dict:
        """JSON-able flat snapshot: ``{key: value}`` with histograms
        rendered to their count/sum/quantile dicts."""
        out = {}
        for s in self.collect():
            out[s.key()] = (s.value.to_dict()
                            if isinstance(s.value, Histogram) else s.value)
        return out

    def to_prometheus(self, prefix: str = "hc") -> str:
        """Prometheus text exposition (histograms as summaries)."""
        lines = []
        typed: set[str] = set()
        for s in self.collect():
            name = _prom_name(f"{prefix}_{s.name}")
            if isinstance(s.value, Histogram):
                if name not in typed:
                    typed.add(name)
                    lines.append(f"# TYPE {name} summary")
                h = s.value
                for q, pct in (("0.5", 50), ("0.95", 95), ("0.99", 99),
                               ("0.999", 99.9)):
                    lines.append(f"{name}{_prom_labels(s.labels, quantile=q)}"
                                 f" {h.percentile(pct):g}")
                lines.append(f"{name}_sum{_prom_labels(s.labels)}"
                             f" {h.total:g}")
                lines.append(f"{name}_count{_prom_labels(s.labels)}"
                             f" {h.count:g}")
            else:
                if name not in typed:
                    typed.add(name)
                    lines.append(f"# TYPE {name} {s.kind}")
                lines.append(f"{name}{_prom_labels(s.labels)} {s.value:g}")
        return "\n".join(lines) + "\n"


def _prom_name(name: str) -> str:
    return re.sub(r"[^a-zA-Z0-9_:]", "_", name)


def _prom_labels(labels: dict, **extra) -> str:
    merged = {**labels, **extra}
    if not merged:
        return ""
    inner = ",".join(f'{_prom_name(k)}="{merged[k]}"'
                     for k in sorted(merged))
    return "{" + inner + "}"


_PROM_LINE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
                        r"(?:\{(.*)\})?\s+(\S+)$")
_PROM_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="([^"]*)"')


def parse_prometheus(text: str) -> dict:
    """Parse the text exposition back into ``{name: [(labels, value)]}``
    — the verify.sh/tests half of the Prometheus round trip.  Raises
    ``ValueError`` on any non-comment line that does not parse."""
    out: dict[str, list] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _PROM_LINE.match(line)
        if m is None:
            raise ValueError(f"unparseable Prometheus line: {line!r}")
        name, rawlabels, raw = m.groups()
        labels = dict(_PROM_LABEL.findall(rawlabels)) if rawlabels else {}
        out.setdefault(name, []).append((labels, float(raw)))
    return out


def prom_value(parsed: dict, name: str, **labels) -> float:
    """Sum of every ``name`` series whose labels include ``labels``."""
    return sum(v for ls, v in parsed.get(name, ())
               if all(ls.get(k) == str(w) for k, w in labels.items()))


# ----------------------------------------------------------------- tracing
@dataclasses.dataclass
class Span:
    """One lifecycle stage of a traced request (``t0 == t1`` marks an
    instant event, e.g. submit/resolve)."""
    name: str
    t0: float
    t1: float
    tags: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Trace:
    """One sampled request's full lifecycle.  ``tags`` accumulates the
    response stamps at finish: shard, replica, epoch, serving_version,
    status."""
    rid: int
    kind: str
    t0: float
    t1: float = 0.0
    spans: list = dataclasses.field(default_factory=list)
    tags: dict = dataclasses.field(default_factory=dict)

    def span_names(self) -> list[str]:
        return [s.name for s in self.spans]


class Tracer:
    """Deterministic sampled request tracing: every ``round(1/rate)``-th
    submitted request gets a live ``Trace``; finished traces land in a
    bounded ring (``deque(maxlen=capacity)``).  The scheduler only calls
    in through ``is_live``/``span``/``span_all``, all of which are no-ops
    (and allocation-free) for unsampled rids."""

    def __init__(self, sample_rate: float, capacity: int = 256,
                 clock: Clock | None = None):
        assert 0.0 < sample_rate <= 1.0, "tracer needs a rate in (0, 1]"
        assert capacity >= 1
        self.period = max(1, round(1.0 / sample_rate))
        self.clock = clock or CLOCK
        self.sampled = 0
        self._seen = 0
        self._live: dict[int, Trace] = {}
        self.traces: deque[Trace] = deque(maxlen=capacity)

    @property
    def live_count(self) -> int:
        return len(self._live)

    def live_rids(self) -> list[int]:
        return list(self._live)

    def is_live(self, rid: int) -> bool:
        return rid in self._live

    def begin(self, rid: int, kind: str, **tags) -> Trace | None:
        """Sampling decision + submit instant; returns the live trace or
        None (the unsampled fast path allocates nothing)."""
        self._seen += 1
        if (self._seen - 1) % self.period:
            return None
        now = self.clock()
        t = Trace(rid=rid, kind=kind, t0=now, tags=dict(tags))
        t.spans.append(Span("submit", now, now))
        self._live[rid] = t
        self.sampled += 1
        return t

    def span(self, rid: int, name: str, t0: float, t1: float,
             **tags) -> None:
        t = self._live.get(rid)
        if t is not None:
            t.spans.append(Span(name, t0, t1, dict(tags) if tags else {}))

    def span_all(self, name: str, t0: float, t1: float, **tags) -> None:
        """Attach one span to every live trace (the export/flip stages
        cover the whole epoch, not one request)."""
        for t in self._live.values():
            t.spans.append(Span(name, t0, t1, dict(tags) if tags else {}))

    def finish(self, rid: int, **tags) -> Trace | None:
        t = self._live.pop(rid, None)
        if t is None:
            return None
        now = self.clock()
        t.spans.append(Span("resolve", now, now))
        t.tags.update(tags)
        t.t1 = now
        self.traces.append(t)
        return t

    def collect(self) -> list[tuple]:
        return [("traces_sampled", "counter", self.sampled,
                 {"layer": "tracer"}),
                ("traces_retained", "gauge", len(self.traces),
                 {"layer": "tracer"}),
                ("traces_live", "gauge", len(self._live),
                 {"layer": "tracer"})]


def chrome_trace_events(traces: Iterable[Trace]) -> dict:
    """Chrome trace-event JSON (Perfetto / chrome://tracing loadable):
    one complete ("ph": "X") event per span, pid = shard, tid = rid,
    timestamps in microseconds, tags in ``args``."""
    evs = []
    for t in traces:
        for s in t.spans:
            evs.append({
                "name": s.name, "ph": "X", "cat": t.kind,
                "ts": s.t0 * 1e6, "dur": max((s.t1 - s.t0) * 1e6, 0.0),
                "pid": int(t.tags.get("shard", 0)), "tid": t.rid,
                "args": {**t.tags, **s.tags, "rid": t.rid, "kind": t.kind},
            })
    return {"traceEvents": evs, "displayTimeUnit": "ms"}


# ------------------------------------------------------------------ bundle
class Telemetry:
    """Registry + (optional) tracer behind one handle, with the wiring
    helpers the service layer uses.  Constructed by ``HoneycombService``
    from ``ServiceConfig.telemetry`` when enabled; standalone use is one
    line: ``tm = Telemetry(); tm.wire_store(store)``."""

    def __init__(self, cfg: TelemetryConfig | None = None,
                 clock: Clock | None = None):
        self.cfg = cfg or TelemetryConfig()
        self.clock = clock or CLOCK
        self.registry = MetricsRegistry()
        self.tracer = (Tracer(self.cfg.trace_sample_rate,
                              self.cfg.trace_capacity, self.clock)
                       if self.cfg.trace_sample_rate > 0 else None)
        if self.tracer is not None:
            self.registry.register(self.tracer.collect)
        watch_compiles()
        self.registry.register(programs_built)

    @property
    def enabled(self) -> bool:
        return self.cfg.enabled

    def histogram(self, name: str, **labels) -> Histogram:
        return self.registry.histogram(
            name, lo=self.cfg.latency_lo, hi=self.cfg.latency_hi,
            buckets_per_decade=self.cfg.buckets_per_decade, **labels)

    # ------------------------------------------------------------ wiring
    def wire_store(self, store) -> "Telemetry":
        """Register every stats surface the facade exposes.  Probes by
        meter property name, so it works across the whole facade family
        (``StoreShard``/``HoneycombStore``, ``ShardedHoneycombStore``,
        bare ``ReplicaGroup``) — absent surfaces are skipped."""
        reg = self.registry
        reg.register(lambda: store.sync_stats, src="primary")
        reg.register(lambda: store.stats)                     # TreeStats
        if hasattr(store, "pipeline_stats"):
            reg.register(lambda: store.pipeline_stats, src="store")
        if hasattr(store, "cache_stats"):
            reg.register(lambda: store.cache_stats)
        if hasattr(store, "feed_stats"):
            reg.register(lambda: store.feed_stats)
            reg.register(lambda: store.replication_stats, src="followers")
        # EpochSan meters, when the sanitizer is active (lazy import: the
        # registry must stay constructible without the analysis package)
        from ..analysis import epochsan as _epochsan
        san = _epochsan.get()
        if san is not None:
            reg.register(lambda: san.stats)
        return self

    def wire_scheduler(self, sched) -> "Telemetry":
        self.registry.register(lambda: sched.stats, src="scheduler")

        def _sched_meters():
            lab = {"layer": "scheduler"}
            return [
                ("scheduler_dispatched_batches", "counter",
                 sched.dispatched_batches, lab),
                ("scheduler_dispatched_requests", "counter",
                 sched.dispatched_requests, lab),
                ("scheduler_applied_writes", "counter",
                 sched.applied_writes, lab),
                ("scheduler_syncs", "counter", sched.syncs, lab),
            ]
        self.registry.register(_sched_meters)
        return self

    # --------------------------------------------------------- exporters
    def collect(self) -> list[MetricSample]:
        return self.registry.collect()

    def snapshot(self) -> dict:
        return self.registry.snapshot()

    def to_prometheus(self, prefix: str = "hc") -> str:
        return self.registry.to_prometheus(prefix)

    def traces(self) -> list[Trace]:
        return list(self.tracer.traces) if self.tracer is not None else []

    def chrome_trace(self) -> dict:
        return chrome_trace_events(self.traces())

    # ------------------------------------------------------------ lookup
    def value(self, name: str, **labels) -> float:
        """Sum of every matching counter/gauge sample — the benchmark
        table's accessor, so columns read the registry, not the layer
        dataclasses."""
        tot = 0.0
        for s in self.collect():
            if s.name == name and not isinstance(s.value, Histogram) and \
                    all(s.labels.get(k) == v for k, v in labels.items()):
                tot += s.value
        return tot

    def quantile(self, name: str, p: float, **labels) -> float:
        """Percentile ``p`` over every matching histogram (merged)."""
        merged = None
        for s in self.collect():
            if s.name == name and isinstance(s.value, Histogram) and \
                    all(s.labels.get(k) == v for k, v in labels.items()):
                if merged is None:
                    merged = Histogram(s.value.lo, s.value.hi, s.value.bpd)
                merged.merge(s.value)
        return merged.percentile(p) if merged is not None else 0.0

    def summary(self) -> dict:
        """Flat JSON-able registry view keyed ``name{labels}`` (scalars
        verbatim, histograms as quantile dicts) — what the benchmarks
        attach next to their results."""
        return self.registry.snapshot()
