"""Sync kernel roofline: the bytes a sync needs — each dirty node row's
image payload read once and written once (2 x ``SyncStats.image_bytes``)
— over the sync programs' device time times the chip's peak HBM
bandwidth, in percent.  The copy of the whole image that the
non-donated sync program makes is not needed work and is not counted."""

from bench.metrics import device_seconds
from bench.metrics.sync_kernel_ms_per_epoch import SYNC_MODULES


def read(ctx):
    t = device_seconds(ctx, SYNC_MODULES)
    need = 2 * ctx["sync"]["image_bytes"]
    if not t or not need:
        return None
    return 100.0 * need / (t * ctx["peaks"]["hbm_bytes_per_s"])
