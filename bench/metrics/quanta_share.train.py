"""The QuanTA chain's share of the train step's device time: the self
time of the ops inside ``jit_train_step`` under the ``quanta`` name
scope (forward, recomputed forward and backward of the T - S chain)
over the program's device time, in percent."""

from bench import program_trace as P


def read(ctx):
    if ctx.trace is None:
        return None
    secs, ops = P.scope_time(ctx.trace, "train_step", "quanta",
                             P.hlo_op_names())
    step_s, _ = ctx.trace.module_time("train_step")
    if not ops or step_s <= 0:
        return None
    return 100.0 * secs / step_s
