"""Sync kernel (kernels/delta_scatter.py under ``apply_snapshot_delta``):
device milliseconds of the sync programs per sync in the window."""

from bench.metrics import device_seconds

SYNC_MODULES = ("apply_snapshot_delta",)


def read(ctx):
    t = device_seconds(ctx, SYNC_MODULES)
    if not t or not ctx["syncs"]:
        return None
    return t / ctx["syncs"] * 1e3
