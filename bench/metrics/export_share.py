"""Sync / export (core/shard.py): share of the window the scheduler spent
in its export stage, the serial barrier's wait included.  The scheduler
adds the stage's wall time to both ``export_s`` and ``sync_stall_s``, so
``export_s`` alone is the whole stage."""


def read(ctx):
    if not ctx["syncs"]:
        return None
    return ctx["sched"]["export_s"] / ctx["window_s"]
