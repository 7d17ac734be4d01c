"""Value gather (core/shard.py ``gather_values``): device microseconds of
the gather program per out-of-node value served from the device value
image (``PipelineStats.device_values``).  Nothing to read without a
trace, without the program, or without the counter."""

from bench.metrics import device_seconds

GATHER_MODULES = ("gather_values",)


def read(ctx):
    t = device_seconds(ctx, GATHER_MODULES)
    values = ctx["shard"].get("device_values")
    if not t or not values:
        return None
    return t / values * 1e6
