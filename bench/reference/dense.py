"""Plain fp32 reference of the dense decoder family the benchmark runs.

Straight ``jax.numpy`` under ``Precision.HIGHEST``: RMSNorm, rotary
embedding (rotate-half pairs), grouped-query causal attention, SwiGLU,
tied or untied output head; nf4 round trip of the frozen projections
from the public QLoRA codebook (blocks of 64 along ``d_in``, absmax
scale); fold-free QuanTA (``T x - S x``, each two-axis tensor applied in
turn) and LoRA (``alpha / r * (x A) B``) deltas on top of the base.

It imports nothing of the system under test and takes nothing it made:
every layer's weights are regenerated from the seed (``bench.weights``)
inside the layer loop, so only one layer is ever held in fp32.

``precision="fp8"`` is the control: every matrix product rounds both
operands to float8 e4m3 first (the step below the bf16 the
configurations state).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST

# QLoRA's NF4 codebook (Dettmers et al. 2023, Appendix E).
NF4 = np.array([
    -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
    -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
    0.07958029955625534, 0.16093020141124725, 0.24611230194568634,
    0.33791524171829224, 0.44070982933044434, 0.5626170039176941,
    0.7229568362236023, 1.0], np.float32)


def _round(x, precision):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def mm(a, b, precision="fp32"):
    return jnp.matmul(_round(a, precision), _round(b, precision),
                      precision=HIGHEST)


def nf4_roundtrip(w, block: int = 64):
    """Nearest-code nf4 of ``w (d_in, d_out)`` with absmax scales over
    blocks of ``block`` rows, back in fp32."""
    d_in, d_out = w.shape
    wb = w.astype(jnp.float32).reshape(d_in // block, block, d_out)
    scale = jnp.max(jnp.abs(wb), axis=1, keepdims=True)
    v = jnp.clip(wb / scale, -1.0, 1.0)
    mids = (NF4[:-1] + NF4[1:]) / 2.0
    code = jnp.zeros(v.shape, jnp.int32)
    for b in mids.tolist():
        code = code + (v >= b).astype(jnp.int32)
    return (jnp.asarray(NF4)[code] * scale).reshape(d_in, d_out)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """``x (T, H, hd)`` at integer positions ``pos (T,)``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def quanta_apply(x, tensors, dims_in, pairs, precision):
    """Apply the chain of two-axis tensors to rows ``x (T, d_in)``."""
    h = x.reshape((x.shape[0],) + tuple(dims_in))
    for t, (m_, n_) in zip(tensors, pairs):
        om, on, im, in_ = t.shape
        h = jnp.moveaxis(h, (1 + m_, 1 + n_), (-2, -1))
        lead = h.shape[:-2]
        y = mm(h.reshape(-1, im * in_), t.reshape(om * on, im * in_).T,
               precision)
        h = jnp.moveaxis(y.reshape(lead + (om, on)), (-2, -1),
                         (1 + m_, 1 + n_))
    return h.reshape(x.shape[0], -1)


def attention(q, k, v, precision, q_chunk: int = 1024):
    """Causal GQA attention, queries in chunks (each rematerialised
    under differentiation) so the fp32 scores of one chunk are live."""
    t, h, hd = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scale = 1.0 / math.sqrt(hd)
    c = min(q_chunk, t)
    pad = -t % c
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, c, h, hd)
    kpos = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        qc, i = args
        s = jnp.einsum("qhd,khd->hqk", _round(qc, precision),
                       _round(k, precision), precision=HIGHEST) * scale
        qpos = i * c + jnp.arange(c)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", _round(p, precision),
                          _round(v, precision), precision=HIGHEST)

    out = jax.lax.map(one, (qp, jnp.arange(qp.shape[0])))
    return out.reshape(-1, h, hd)[:t]


def layer_weights(m: dict, key, layer, nf4: bool) -> dict:
    shapes = W.proj_shapes(m)
    w = {}
    for name in W.PROJ:
        p = W.proj_weight(key, name, layer, shapes[name]).astype(jnp.float32)
        w[name] = nf4_roundtrip(p) if nf4 else p
    d = m["hidden_size"]
    w["ln1"] = W.norm_weight(key, "ln1", layer, d).astype(jnp.float32)
    w["ln2"] = W.norm_weight(key, "ln2", layer, d).astype(jnp.float32)
    return w


def layer_forward(m, w, ad, x, pos, precision):
    """One decoder layer on ``x (T, d)``; ``ad`` maps a target to
    ``delta(h) -> (T, d_out)`` (absent targets have none)."""
    hd, nh, nkv = m["head_dim"], m["num_attention_heads"], \
        m["num_key_value_heads"]
    eps = m["rms_norm_eps"]

    def proj(name, h):
        y = mm(h, w[name], precision)
        if name in ad:
            y = y + ad[name](h)
        return y

    h = rms_norm(x, w["ln1"], eps)
    t = x.shape[0]
    q = rope(proj("q_proj", h).reshape(t, nh, hd), pos, m["rope_theta"])
    k = rope(proj("k_proj", h).reshape(t, nkv, hd), pos, m["rope_theta"])
    v = proj("v_proj", h).reshape(t, nkv, hd)
    o = attention(q, k, v, precision).reshape(t, nh * hd)
    x = x + proj("o_proj", o)
    h = rms_norm(x, w["ln2"], eps)
    f = jax.nn.silu(proj("gate_proj", h)) * proj("up_proj", h)
    return x + proj("down_proj", f)


def adapter_fns(adapters: dict, precision) -> dict:
    """``adapters``: ``{target: spec}`` for one layer, a spec being
    ``("quanta", T, S, dims_in, pairs)``, ``("lora", A, B, scale)`` or
    ``("mix", [(weight, spec), ...])``, the weighted sum of those."""
    return {tgt: _delta_fn(a, precision) for tgt, a in adapters.items()}


def _delta_fn(a, precision):
    if a[0] == "quanta":
        _, ts, ss, dims_in, pairs = a
        return lambda h: (quanta_apply(h, ts, dims_in, pairs, precision)
                          - quanta_apply(h, ss, dims_in, pairs, precision))
    if a[0] == "lora":
        _, am, bm, scale = a
        return lambda h: scale * mm(mm(h, am, precision), bm, precision)
    parts = [(w, _delta_fn(sp, precision)) for w, sp in a[1]]
    return lambda h: sum(w * fn(h) for w, fn in parts)


def head(m: dict, key):
    """Output head ``(d, vocab)`` in fp32."""
    if m["tie_word_embeddings"]:
        return W.embed_weight(key, m["vocab_size"],
                              m["hidden_size"]).astype(jnp.float32).T
    return W.head_weight(key, m["hidden_size"],
                         m["vocab_size"])[:, :m["vocab_size"]].astype(
                             jnp.float32)


def hidden(m, key, tokens, layer_adapters, stacked, nf4: bool,
           precision="fp32"):
    """Final-normed hidden states ``(T, d)`` of one sequence.
    ``stacked`` is a pytree of per-layer arrays (leading layer axis)
    scanned beside the layer index; ``layer_adapters(layer, sliced)``
    gives that layer's adapters for :func:`adapter_fns`."""
    emb = W.embed_weight(key, m["vocab_size"], m["hidden_size"])
    x = emb[tokens].astype(jnp.float32)
    pos = jnp.arange(tokens.shape[0])

    def body(x, xs):
        layer, sl = xs
        w = layer_weights(m, key, layer, nf4)
        ad = adapter_fns(layer_adapters(layer, sl), precision)
        return layer_forward(m, w, ad, x, pos, precision), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x,
                        (jnp.arange(m["num_hidden_layers"]), stacked))
    fw = W.norm_weight(key, "final_norm", 0, m["hidden_size"])
    return rms_norm(x, fw.astype(jnp.float32), m["rms_norm_eps"])


def logits_at(m, key, hid, start, count, precision="fp32"):
    """fp32 logits of rows ``start .. start+count`` of ``hid`` (rows past
    its end read zeros: a slice is never moved back to fit)."""
    hid = jnp.pad(hid, ((0, count), (0, 0)))
    rows = jax.lax.dynamic_slice_in_dim(hid, start, count)
    return mm(rows, head(m, key), precision)


def mean_nll(m, key, hid, labels, precision="fp32", chunk: int = 512):
    """Mean next-token NLL of ``hid (T, d)`` against ``labels (T,)``,
    over vocabulary chunks of rows so one chunk's logits are live."""
    w = head(m, key)
    t = hid.shape[0]
    c = min(chunk, t)

    @jax.checkpoint
    def one(args):
        h, lab = args
        lg = mm(h, w, precision)
        return jnp.sum(jax.nn.logsumexp(lg, -1)
                       - jnp.take_along_axis(lg, lab[:, None], -1)[:, 0])

    tot = jax.lax.map(one, (hid.reshape(-1, c, hid.shape[1]),
                            labels.reshape(-1, c)))
    return jnp.sum(tot) / t
