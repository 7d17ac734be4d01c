"""Front end (core/api.py): host microseconds per request the benchmark's
client spent in ``submit`` and in reading the tickets, outside ``drain``,
over every request it served from the window's start to its end (an open
loop's lead-in included)."""


def read(ctx):
    if not ctx["front_requests"]:
        return None
    return ctx["front_s"] / ctx["front_requests"] * 1e6
