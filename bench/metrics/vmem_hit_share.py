"""Cache tier (core/cache.py): share of the fused kernels' node visits
served from the VMEM cache tier (``CacheStats.vmem_hits`` over hits plus
``heap_gathers``)."""


def read(ctx):
    c = ctx["cache"]
    total = c["vmem_hits"] + c["heap_gathers"]
    if not total:
        return None
    return c["vmem_hits"] / total
