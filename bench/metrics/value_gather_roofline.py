"""Value gather roofline: the bytes the gather needs — each served value's
slot row (``overflow_words`` u32 words) read once from the value image
and written once into the batch's answer — over the gather program's
device time times the chip's peak HBM bandwidth, in percent.  Rows the
gather writes as zeros for inline values are not needed work and are not
counted."""

from bench.metrics import device_seconds
from bench.metrics.value_gather_us_per_req import GATHER_MODULES


def gather_bytes(values: int, overflow_words: int) -> int:
    """Bytes ``values`` slot rows of ``overflow_words`` words need, read
    once and written once."""
    return 2 * values * 4 * overflow_words


def read(ctx):
    t = device_seconds(ctx, GATHER_MODULES)
    values = ctx["shard"].get("device_values")
    if not t or not values:
        return None
    need = gather_bytes(values, int(ctx["store"]["overflow_words"]))
    return 100.0 * need / (t * ctx["peaks"]["hbm_bytes_per_s"])
