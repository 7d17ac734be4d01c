"""Read dispatch (core/shard.py, span ``hc.read.fetch``): share of the
window the host spent blocked on the device's answers, copying read
results and meters back (the store's ``PipelineStats.fetch_s``).  Nothing
to read from a program that does not meter it."""


def read(ctx):
    s = ctx["shard"]
    if "fetch_s" not in s or not ctx["reads"]:
        return None
    return s["fetch_s"] / ctx["window_s"]
