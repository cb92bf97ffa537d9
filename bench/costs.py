"""Operations and bytes a step needs, from the configuration's shapes.

"Needs" is what the algorithm must do, not what the program happens to
do: active rows only, live tokens of the KV cache only, the nf4 weights
at their packed size, no recomputation.  Each function returns
``(flops, bytes)``; :func:`seconds` turns that into the least time the
chip could take (the larger of the two bounds) and names the bound.
"""

from __future__ import annotations

import math

from bench import weights as W


def seconds(flops: float, nbytes: float, peaks: dict):
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return max(t_c, t_m), ("compute" if t_c >= t_m else "memory")


def proj_params(m: dict) -> int:
    """Projection parameters of all layers."""
    return m["num_hidden_layers"] * sum(
        a * b for a, b in W.proj_shapes(m).values())


def nf4_bytes(m: dict, block: int = 64) -> float:
    """Packed nf4 projections: half a byte per weight plus an fp32 scale
    per block of ``block`` rows and column."""
    return m["num_hidden_layers"] * sum(
        a * b / 2 + math.ceil(a / block) * b * 4
        for a, b in W.proj_shapes(m).values())


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    return (m["num_hidden_layers"] * 2 * m["num_key_value_heads"]
            * m["head_dim"] * itemsize)


def quantized_matmul_step(m: dict, rows: int):
    """All nf4 projections of one decode step over ``rows`` rows."""
    p = proj_params(m)
    act = m["num_hidden_layers"] * sum(
        rows * (a + b) * 2 for a, b in W.proj_shapes(m).values())
    return 2.0 * rows * p, nf4_bytes(m) + act


def paged_decode_step(m: dict, live_tokens: float, rows: int):
    """Attention of one decode step over ``live_tokens`` cached tokens
    (summed over the active rows): read each token's K and V once."""
    hq = m["num_attention_heads"] * m["head_dim"]
    flops = 4.0 * live_tokens * hq * m["num_hidden_layers"]
    q_o = 2 * rows * hq * 2 * m["num_hidden_layers"]
    return flops, live_tokens * kv_bytes_per_token(m) + q_o


def head_bytes(m: dict) -> int:
    return W.padded_vocab(m["vocab_size"]) * m["hidden_size"] * 2


def quanta_chain_flops(dims_in, dims_out, pairs) -> float:
    """Operations of one chain application to one row."""
    cur = list(dims_in)
    total = 0.0
    for (m_, n_), (om, on, im, in_) in zip(
            pairs, W.quanta_shapes(dims_in, dims_out, pairs)):
        total += 2.0 * (math.prod(cur) // (im * in_)) * om * on * im * in_
        cur[m_], cur[n_] = om, on
    return total


def quanta_tenant_bytes(m: dict, q: dict) -> float:
    """fp32 bytes of one fold-free QuanTA tenant (T and S) over all layers."""
    n = sum(math.prod(s) for t in q["targets"].values()
            for s in W.quanta_shapes(t["dims_in"], t["dims_out"], q["pairs"]))
    return 2 * 4 * n * m["num_hidden_layers"]


def lora_tenant_bytes(m: dict, lo: dict) -> float:
    shapes = W.proj_shapes(m)
    n = sum((shapes[t][0] + shapes[t][1]) * lo["rank"] for t in lo["targets"])
    return 4 * n * m["num_hidden_layers"]


def decode_step(m: dict, rows: int, live_tokens: float, quanta_tenants: float,
                lora_tenants: float):
    """A whole decode step: nf4 projections, attention over the live
    cache, the output head, and each distinct active tenant's adapter
    rows read once (applied to its rows)."""
    f_q, b_q = quantized_matmul_step(m, rows)
    f_a, b_a = paged_decode_step(m, live_tokens, rows)
    f_h = 2.0 * rows * m["hidden_size"] * m["vocab_size"]
    ad = m.get("adapters", {})
    b_ad, f_ad = 0.0, 0.0
    if "quanta" in ad:
        q = ad["quanta"]
        b_ad += quanta_tenants * quanta_tenant_bytes(m, q)
    if "lora" in ad:
        b_ad += lora_tenants * lora_tenant_bytes(m, ad["lora"])
    return f_q + f_a + f_h + f_ad, b_q + b_a + head_bytes(m) + b_ad


def flash_forward_call(m: dict, rows: int, seq: int):
    """One causal flash-attention forward over ``rows`` sequences of
    ``seq`` tokens: QK and PV over the causal pairs; Q, K, V read and
    the output written once."""
    h, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    pairs = seq * (seq + 1) / 2
    flops = 4.0 * rows * h * hd * pairs
    nbytes = 2 * rows * seq * hd * (2 * h + 2 * kv)
    return flops, nbytes


def train_flops_per_token(m: dict, seq: int) -> float:
    """Required operations per trained token: forward and input
    gradients through the frozen projections and head, causal attention
    forward and backward, and the QuanTA chains (trainable T: forward,
    input and tensor gradients; frozen S: forward and input gradient).
    Recomputation is not counted."""
    proj = proj_params(m)
    head = m["hidden_size"] * m["vocab_size"]
    ctx = (seq + 1) / 2
    attn = (12.0 * ctx * m["num_attention_heads"] * m["head_dim"]
            * m["num_hidden_layers"])
    q = m["adapters"]["quanta"]
    chain = sum(quanta_chain_flops(t["dims_in"], t["dims_out"], q["pairs"])
                for t in q["targets"].values())
    return 4.0 * proj + 4.0 * head + attn + 5.0 * chain * m["num_hidden_layers"]


def traced_decode(ctx):
    """The traced window's decode steps, from the benchmark's own
    per-tick counters: mean live tokens, active rows and distinct active
    tenants per step (None when nothing was traced)."""
    c = ctx.counters
    n = c.get("traced_decode_steps", 0)
    if ctx.trace is None or not n:
        return None
    return {"steps": n,
            "live_tokens": c["traced_live_tokens"] / n,
            "rows": c["traced_live_slots"] / n,
            "quanta": c.get("traced_quanta_tenants", 0) / n,
            "lora": c.get("traced_lora_tenants", 0) / n}
