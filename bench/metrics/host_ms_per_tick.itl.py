"""Host milliseconds per front-end tick: the benchmark's clock around
each ``ServeFrontend.tick()`` that did work, total over ticks."""


def read(ctx):
    c = ctx.counters
    if not c.get("ticks"):
        return None
    return 1e3 * c["tick_s"] / c["ticks"]
