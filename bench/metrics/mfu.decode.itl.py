"""The decode program's share of the chip's roofline: what each traced
decode step needs (nf4 projections and output head over the active
rows, the live KV cache, each distinct active tenant's adapter rows;
the larger of operations over peak and bytes over bandwidth) over the
device time of the ``decode_fn`` program, in percent."""

from bench import costs as C


def read(ctx):
    t = C.traced_decode(ctx)
    if t is None:
        return None
    secs, runs = ctx.trace.module_time("decode_fn")
    if not runs or secs <= 0:
        return None
    need = C.seconds(*C.decode_step(
        ctx.config, t["rows"], t["live_tokens"], t["quanta"], t["lora"]),
        ctx.peaks)[0]
    return 100.0 * need * runs / secs
