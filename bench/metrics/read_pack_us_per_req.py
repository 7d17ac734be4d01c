"""Read dispatch (core/shard.py, span ``hc.read.pack``): host
microseconds per request lane spent padding a read batch, packing its
keys and uploading the lanes (the store's ``PipelineStats.pack_s``).
Nothing to read from a program that does not meter it."""


def read(ctx):
    s = ctx["shard"]
    if "pack_s" not in s or not s["dispatched_lanes"]:
        return None
    return s["pack_s"] / s["dispatched_lanes"] * 1e6
