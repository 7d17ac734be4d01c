"""Device: share of the traced window in which no operation ran on the
chip (1 - busy union / window)."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    return tr["idle_share"]
