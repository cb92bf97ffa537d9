"""Tenant loads into the adapter pool per second of the window
(``AdapterPool.loads`` before and after).  A load copies the tenant's
factors to the device and waits for them inside the front end's tick,
so every stream in flight waits with it."""


def read(ctx):
    c = ctx.counters
    if "adapter_loads" not in c or not ctx.window_s:
        return None
    return c["adapter_loads"] / ctx.window_s
