"""Model FLOP utilisation of training: the operations a trained token
needs (``costs.train_flops_per_token``: no recomputation) times the
window's trained tokens per second, over the chip's bf16 peak, in
percent."""

from bench import costs as C


def read(ctx):
    c = ctx.counters
    if not c.get("tokens_per_s"):
        return None
    need = C.train_flops_per_token(ctx.config, ctx.traffic["seq_len"])
    return 100.0 * need * c["tokens_per_s"] / ctx.peaks["bf16_flops_per_s"]
