"""Streams preempted per second of the window (the engine's
``preemptions`` counter before and after).  A full KV pool frees a
stream's blocks when another stream grows; the victim waits in the
queue and is prefilled again, prompt and output, which costs served
tokens."""


def read(ctx):
    c = ctx.counters
    if "preemptions" not in c or not ctx.window_s:
        return None
    return c["preemptions"] / ctx.window_s
