"""Sync kernel (scheduler ``stage_export``, span ``hc.sync.barrier``):
host milliseconds per sync spent in the sync barrier, the serial
scheduler's wait for the sync program (the scheduler's ``sync_stall_s``).
A program that does not meter the read stages (no ``fetch_s``) adds the
whole export stage to ``sync_stall_s``: there is no barrier to read."""


def read(ctx):
    if "fetch_s" not in ctx["shard"] or not ctx["syncs"]:
        return None
    return ctx["sched"]["sync_stall_s"] / ctx["syncs"] * 1e3
