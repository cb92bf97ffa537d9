"""``quantized_matmul``'s share of its roofline in the decode program:
each decode step streams every nf4 projection once (memory bound at
these row counts), over the kernel's device time inside ``decode_fn``,
in percent.  Steps are counted from the kernel's calls (seven
projections a layer)."""

from bench import costs as C


def read(ctx):
    if ctx.trace is None:
        return None
    secs, calls = ctx.trace.kernel_time("quantized_matmul", module="decode_fn")
    if not calls or secs <= 0:
        return None
    m = ctx.config
    steps = calls / (7 * m["num_hidden_layers"])
    need = C.seconds(*C.quantized_matmul_step(m, m["serve"]["n_slots"]),
                     ctx.peaks)[0]
    return 100.0 * need * steps / secs
