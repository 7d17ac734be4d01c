"""Honeycomb core: the paper's contribution as a composable JAX module."""
from .config import (HoneycombConfig, DEFAULT_CONFIG, FeedTopology,
                     REPLICA_FEEDS, REPLICA_POLICIES, ReplicationConfig,
                     ServiceConfig, ShardingConfig, TelemetryConfig,
                     bucket_pow2)
from .telemetry import (CLOCK, Clock, Histogram, MetricSample,
                        MetricsRegistry, Span, Telemetry, Trace, Tracer,
                        chrome_trace_events, merge_stats, parse_prometheus,
                        prom_value)
from .api import (Delete, Get, HoneycombService, Put, Response, Routing,
                  Scan, Ticket, Update, WIRE_ENTRY_OVERHEAD, WireDecodeError,
                  decode_wire, decode_wire_stream, wire_entry_nbytes)
from .btree import HoneycombTree
from .pipeline import PIPELINE_MODES, PipelineStats
from .shard import StagedSync, StoreShard
from .store import HoneycombStore, SyncStats
from .replica import FeedStats, FollowerReplica, ReplicaGroup
from .router import (ShardedHoneycombStore, aggregate_stats,
                     uniform_int_boundaries)
from .read_path import (TreeSnapshot, SnapshotDelta, ScanResult, GetResult,
                        apply_snapshot_delta, batched_get, batched_scan,
                        descend, log_sort_positions, snapshot_fields)
from .schema import (FIELD_NAMES, NARROWED_FIELDS, NODE_SCHEMA, FieldSpec,
                     NodeImageLayout)
from .scheduler import OutOfOrderScheduler, Request
from .cache import InteriorCache

__all__ = [
    "HoneycombConfig", "DEFAULT_CONFIG", "ServiceConfig", "ShardingConfig",
    "ReplicationConfig", "REPLICA_POLICIES", "REPLICA_FEEDS",
    "FeedTopology", "HoneycombTree",
    "HoneycombStore", "StoreShard", "StagedSync", "ShardedHoneycombStore",
    "ReplicaGroup", "FollowerReplica", "FeedStats", "aggregate_stats",
    "uniform_int_boundaries", "bucket_pow2",
    "PIPELINE_MODES", "PipelineStats",
    "Get", "Scan", "Put", "Update", "Delete", "Response", "Ticket",
    "Routing", "HoneycombService", "decode_wire", "decode_wire_stream",
    "wire_entry_nbytes", "WIRE_ENTRY_OVERHEAD", "WireDecodeError",
    "TreeSnapshot", "SnapshotDelta", "ScanResult", "GetResult",
    "apply_snapshot_delta", "batched_get", "batched_scan",
    "descend", "log_sort_positions", "snapshot_fields",
    "FieldSpec", "NODE_SCHEMA", "FIELD_NAMES", "NARROWED_FIELDS",
    "NodeImageLayout", "OutOfOrderScheduler", "Request",
    "InteriorCache", "SyncStats",
    "TelemetryConfig", "Telemetry", "MetricsRegistry", "MetricSample",
    "Histogram", "Tracer", "Trace", "Span", "Clock", "CLOCK",
    "chrome_trace_events", "merge_stats", "parse_prometheus", "prom_value",
]
