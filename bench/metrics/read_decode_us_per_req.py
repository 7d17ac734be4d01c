"""Read dispatch (core/shard.py, span ``hc.read.decode``): host
microseconds per request lane spent decoding a read batch's results (the
store's ``PipelineStats.decode_s``).  Nothing to read from a program that
does not meter it."""


def read(ctx):
    s = ctx["shard"]
    if "decode_s" not in s or not s["dispatched_lanes"]:
        return None
    return s["decode_s"] / s["dispatched_lanes"] * 1e6
