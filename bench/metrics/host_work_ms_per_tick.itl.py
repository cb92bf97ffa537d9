"""Host milliseconds per front-end tick that did work, outside every wait
on the device, from the program's own spans in the traced window: each
``serve.tick`` less the time inside it in the program's ``serve.wait``
spans and in the runtime's waits (a decode dispatch that waits for
device memory a running step still holds).  Beside
``host_ms_per_tick.itl``, which times whole ticks, this is the time the
host spends on the tick's own work: the time that sets the pace once
the device step is short."""

from bench import program_trace as P


def read(ctx):
    if ctx.trace is None:
        return None
    return P.host_work_ms(P.program_spans(ctx.trace.window))
