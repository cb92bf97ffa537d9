"""``flash_attention``'s share of its roofline in the train step: each
forward call computes causal attention over one microbatch (compute
bound at 4k tokens), over the kernel's device time inside
``train_step``, in percent.  Recomputed forward calls count as calls:
each is the same work."""

from bench import costs as C


def read(ctx):
    if ctx.trace is None:
        return None
    secs, calls = ctx.trace.kernel_time("flash_attention", module="train_step")
    if not calls or secs <= 0:
        return None
    m, tr = ctx.config, ctx.traffic
    rows = tr["step_tokens"] // tr["seq_len"] // m["train"]["microbatches"]
    need = C.seconds(*C.flash_forward_call(m, rows, tr["seq_len"]),
                     ctx.peaks)[0]
    return 100.0 * need * calls / secs
