"""Sync / export (core/shard.py): bytes the syncs moved host to device
(``SyncStats.bytes_synced``) per write served in the window."""


def read(ctx):
    if not ctx["writes"]:
        return None
    return ctx["sync"]["bytes_synced"] / ctx["writes"]
