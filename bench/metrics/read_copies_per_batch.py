"""Read dispatch (core/shard.py, span ``hc.read.fetch``): device->host
copies per device read batch (the store's ``PipelineStats.read_copies``
over ``read_batches``).  Nothing to read from a program that does not
count them, or that dispatched no device read batch."""


def read(ctx):
    s = ctx["shard"]
    if not s.get("read_batches"):
        return None
    return s["read_copies"] / s["read_batches"]
