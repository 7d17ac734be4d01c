"""Read kernel (kernels/fused_read.py): device microseconds of the fused
GET/SCAN programs per request lane they served."""

from bench.metrics import device_seconds

READ_MODULES = ("batched_get_fused", "batched_scan_fused")


def read(ctx):
    t = device_seconds(ctx, READ_MODULES)
    lanes = ctx["shard"]["dispatched_lanes"]
    if not t or not lanes:
        return None
    return t / lanes * 1e6
