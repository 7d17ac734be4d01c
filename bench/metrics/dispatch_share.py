"""Read dispatch (scheduler stage_dispatch, core/shard.py pack and
decode): share of the window the scheduler spent dispatching read batches
(``PipelineStats.dispatch_s``), device wait included."""


def read(ctx):
    if not ctx["reads"]:
        return None
    return ctx["sched"]["dispatch_s"] / ctx["window_s"]
