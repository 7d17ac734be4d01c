"""Metric readers: one module per metric, named as the metric in
BENCHMARK.json (end-to-end and per-layer alike), each with
``read(ctx) -> float | None``.  A reader that finds nothing to read
returns None and the metric is left out.

``ctx`` is the dict ``bench/harness.py`` builds after the window: counts
of the window (``requests``, ``reads``, ``writes``, ``syncs``, ``ops``
per kind), the program's meters as differences over the window
(``sched``: the scheduler's PipelineStats, ``shard``: the store's
PipelineStats, ``sync``: SyncStats, ``cache``: CacheStats), the host
clock (``window_s``, ``front_s`` over ``front_requests``, ``setup_s``, ``latencies_s`` of every
request of the window), the store geometry (``store``,
``tree_height``), the chip's ``peaks`` and the reduced ``trace``
(bench/trace_reduce.py) when the run was traced."""


def device_seconds(ctx, modules) -> float | None:
    """Device seconds of the traced programs whose names are in
    ``modules`` (None when the run was not traced)."""
    tr = ctx.get("trace")
    if not tr:
        return None
    return sum(s for name, s in tr["modules"].items() if name in modules)
