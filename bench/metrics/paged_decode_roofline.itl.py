"""``paged_decode``'s share of its roofline in the decode program: each
decode step reads the K and V of every live cached token once (memory
bound), over the kernel's device time inside ``decode_fn``, in percent.
Steps are counted from the kernel's calls (one a layer); the live
tokens per step are the traced window's mean."""

from bench import costs as C


def read(ctx):
    t = C.traced_decode(ctx)
    if t is None:
        return None
    secs, calls = ctx.trace.kernel_time("paged_decode", module="decode_fn")
    if not calls or secs <= 0:
        return None
    steps = calls / ctx.config["num_hidden_layers"]
    need = C.seconds(*C.paged_decode_step(ctx.config, t["live_tokens"],
                                          t["rows"]), ctx.peaks)[0]
    return 100.0 * need * steps / secs
