"""Read kernel roofline: the bytes the lookups need by the Section 3.1
model (``bench.bytes_model``: header, shortcut block and one segment per
level, plus the log at the leaf, for the tree's height) over the fused
programs' device time times the chip's peak HBM bandwidth, in percent.
The model counts what a lookup needs, not the 8-row windows today's
kernel moves."""

from bench.bytes_model import lookup_bytes
from bench.metrics import device_seconds
from bench.metrics.read_kernel_us_per_req import READ_MODULES


def read(ctx):
    t = device_seconds(ctx, READ_MODULES)
    lanes = ctx["shard"]["dispatched_lanes"]
    if not t or not lanes:
        return None
    need = lanes * lookup_bytes(ctx["store"], ctx["tree_height"])
    return 100.0 * need / (t * ctx["peaks"]["hbm_bytes_per_s"])
