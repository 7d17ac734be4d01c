"""90th percentile latency of all requests of the window, in
milliseconds (as ``p50_ms``).  The open cell's tail: host stalls of about
0.1 s on the chip machine delay 0 to 5% of a window's requests, so the
99th percentile reads either the queue's tail or a stall, and the 90th
reads the queue's tail in every run."""

import numpy as np


def read(ctx):
    lat = ctx["latencies_s"]
    return float(np.percentile(lat, 90)) * 1e3 if len(lat) else None
