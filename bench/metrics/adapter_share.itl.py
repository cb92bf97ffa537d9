"""The banked adapters' share of the decode program's device time: the
self time of the ops inside ``jit_decode_fn`` under the ``adapter``
name scope (each request's factor gather and delta, the QuanTA chains
and the ``banked_lora_delta`` kernel; the shared base matmul stays
outside it) over the program's device time, in percent."""

from bench import program_trace as P


def read(ctx):
    if ctx.trace is None:
        return None
    secs, ops = P.scope_time(ctx.trace, "decode_fn", "adapter",
                             P.hlo_op_names())
    step_s, _ = ctx.trace.module_time("decode_fn")
    if not ops or step_s <= 0:
        return None
    return 100.0 * secs / step_s
