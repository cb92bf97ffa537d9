"""Mean over the window's ticks of the KV pool's blocks in use over its
usable blocks (the engine's own gauges), in percent.  A full pool
preempts a stream, which then waits to be prefilled again."""


def read(ctx):
    c = ctx.counters
    if not c.get("ticks"):
        return None
    return 100.0 * c["block_share_sum"] / c["ticks"]
