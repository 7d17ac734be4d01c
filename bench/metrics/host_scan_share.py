"""Read dispatch: share of SCANs the device could not finish and the host
tree answered (``PipelineStats.host_scans`` of the store)."""


def read(ctx):
    if not ctx["ops"].get("scan"):
        return None
    return ctx["shard"]["host_scans"] / ctx["ops"]["scan"]
