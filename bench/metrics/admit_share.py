"""Host tree admit (scheduler stage_admit -> core/btree.py): share of the
window the scheduler spent applying writes (``PipelineStats.admit_s``)."""


def read(ctx):
    if not ctx["writes"]:
        return None
    return ctx["sched"]["admit_s"] / ctx["window_s"]
