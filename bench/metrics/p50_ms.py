"""Median latency of all requests of the window, in milliseconds, as the
cell's loop module times them (``open``: from a request's due time to the
end of the drain that answers it)."""

import numpy as np


def read(ctx):
    lat = ctx["latencies_s"]
    return float(np.percentile(lat, 50)) * 1e3 if len(lat) else None
