"""Seeded weights and tenant adapters, one layer at a time.

Every value is a function of ``(seed, leaf, layer[, tenant])`` alone, so
the plain reference regenerates exactly the layer it needs, and the
program is handed the same values stacked in its own layout.  Nothing
here depends on the program except the stacking order the harness
builds from it.

Projection weights are ``N(0, 1/d_in)`` in fp32, rounded to bf16 (the
type they are stored in); norm scales are ``1 + 0.1 N``; the embedding
``0.02 N``.  A QuanTA tenant's frozen copy ``S`` is an identity-like
tensor plus ``init_noise N`` (the method's own initialisation); a served
tenant's trained chain is ``T = S + tenant_noise N / sqrt(in_m in_n)``,
a training run starts from ``T = S``.  A LoRA tenant is ``A = N / sqrt(d_in)``,
``B = b_std N``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

PROJ = ("q_proj", "k_proj", "v_proj", "o_proj",
        "gate_proj", "up_proj", "down_proj")
_LEAF_IDS = {n: i for i, n in enumerate(
    PROJ + ("embed", "lm_head", "ln1", "ln2", "final_norm",
            "quanta_s", "quanta_t", "lora_a", "lora_b", "tokens"))}


def root_key(seed: int) -> jax.Array:
    """A threefry key from any whole-number seed (64 bits and more)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def leaf_key(key, name: str, *idx) -> jax.Array:
    k = jax.random.fold_in(key, _LEAF_IDS[name])
    for i in idx:
        k = jax.random.fold_in(k, i)
    return k


def proj_shapes(m: dict) -> dict:
    """``(d_in, d_out)`` of each projection of one layer."""
    d, ff = m["hidden_size"], m["intermediate_size"]
    ad = m["num_attention_heads"] * m["head_dim"]
    kvd = m["num_key_value_heads"] * m["head_dim"]
    return {"q_proj": (d, ad), "k_proj": (d, kvd), "v_proj": (d, kvd),
            "o_proj": (ad, d), "gate_proj": (d, ff), "up_proj": (d, ff),
            "down_proj": (ff, d)}


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 128) * 128


# --------------------------------------------------------------- base model

def proj_weight(key, name: str, layer, shape) -> jnp.ndarray:
    """One layer's projection, bf16."""
    w = jax.random.normal(leaf_key(key, name, layer), shape, jnp.float32)
    return (w / math.sqrt(shape[0])).astype(jnp.bfloat16)


def norm_weight(key, name: str, layer, d: int) -> jnp.ndarray:
    w = 1.0 + 0.1 * jax.random.normal(leaf_key(key, name, layer), (d,),
                                      jnp.float32)
    return w.astype(jnp.bfloat16)


def embed_weight(key, vocab_rows: int, d: int) -> jnp.ndarray:
    """``(vocab_rows, d)`` rows of the token embedding, bf16."""
    w = jax.random.normal(leaf_key(key, "embed"), (padded_vocab(vocab_rows), d),
                          jnp.float32)
    return (0.02 * w[:vocab_rows]).astype(jnp.bfloat16)


def head_weight(key, d: int, vocab: int) -> jnp.ndarray:
    """Untied LM head ``(d, padded vocab)``, bf16."""
    w = jax.random.normal(leaf_key(key, "lm_head"), (d, padded_vocab(vocab)),
                          jnp.float32)
    return (w / math.sqrt(d)).astype(jnp.bfloat16)


def base_params(m: dict, key, proj_fn=None) -> dict:
    """The whole base in the program's stacked layout (leaves with a
    leading layer axis), built one layer at a time under ``lax.map``.
    ``proj_fn(w_bf16)`` may turn each layer's projection into its
    served type (nf4) as it is made.  Call inside ``jax.jit``."""
    n_layers, d = m["num_hidden_layers"], m["hidden_size"]
    layers = jnp.arange(n_layers)
    proj_fn = proj_fn or (lambda w: w)
    shapes = proj_shapes(m)

    def stack(name):
        return jax.lax.map(
            lambda l: proj_fn(proj_weight(key, name, l, shapes[name])), layers)

    vpad = padded_vocab(m["vocab_size"])
    embed = jax.random.normal(leaf_key(key, "embed"), (vpad, d), jnp.float32)
    params = {
        "layers": {
            "attn": {n: stack(n) for n in ("q_proj", "k_proj", "v_proj",
                                           "o_proj")},
            "mlp": {n: stack(n) for n in ("gate_proj", "up_proj",
                                          "down_proj")},
            "ln1": jax.lax.map(lambda l: norm_weight(key, "ln1", l, d), layers),
            "ln2": jax.lax.map(lambda l: norm_weight(key, "ln2", l, d), layers),
        },
        "final_norm": norm_weight(key, "final_norm", 0, d),
        "embed": {"tokens": (0.02 * embed).astype(jnp.bfloat16)},
    }
    if not m["tie_word_embeddings"]:
        params["lm_head"] = head_weight(key, d, m["vocab_size"])
    return params


# ----------------------------------------------------------------- adapters

def quanta_shapes(dims_in, dims_out, pairs):
    """``(out_m, out_n, in_m, in_n)`` of each two-axis tensor: the first
    tensor that touches axis 0 maps ``dims_in[0]`` to ``dims_out[0]``."""
    cur = list(dims_in)
    shapes = []
    for m_, n_ in pairs:
        om = dims_out[m_] if m_ == 0 else cur[m_]
        on = dims_out[n_] if n_ == 0 else cur[n_]
        shapes.append((om, on, cur[m_], cur[n_]))
        cur[m_], cur[n_] = om, on
    return shapes


def _identity_like(shape):
    om, on, im, in_ = shape
    k = min(om * on, im * in_)
    eye = jnp.zeros((om * on, im * in_), jnp.float32)
    eye = eye.at[jnp.arange(k), jnp.arange(k)].set(1.0)
    return eye.reshape(shape)


def quanta_layer(key, spec: dict, target: str, tenant, layer, trained: bool):
    """``(T, S)``: tuples of one layer's two-axis tensors for ``target``."""
    t = spec["targets"][target]
    shapes = quanta_shapes(t["dims_in"], t["dims_out"], spec["pairs"])
    tid = list(spec["targets"]).index(target)
    s_list, t_list = [], []
    for i, shp in enumerate(shapes):
        s = _identity_like(shp) + spec["init_noise"] * jax.random.normal(
            leaf_key(key, "quanta_s", tenant, tid, layer, i), shp, jnp.float32)
        s_list.append(s)
        if trained:
            # scaled so each tensor's change has an operator norm near
            # 2 * tenant_noise whatever its size
            t_list.append(s + spec["tenant_noise"] / math.sqrt(
                shp[2] * shp[3]) * jax.random.normal(
                leaf_key(key, "quanta_t", tenant, tid, layer, i), shp,
                jnp.float32))
        else:
            t_list.append(s)
    return tuple(t_list), tuple(s_list)


def lora_layer(key, spec: dict, target: str, shape, tenant, layer):
    """``(A, B)`` of one layer."""
    d_in, d_out = shape
    tid = list(spec["targets"]).index(target)
    r = spec["rank"]
    a = jax.random.normal(leaf_key(key, "lora_a", tenant, tid, layer),
                          (d_in, r), jnp.float32) / math.sqrt(d_in)
    b = spec["b_std"] * jax.random.normal(
        leaf_key(key, "lora_b", tenant, tid, layer), (r, d_out), jnp.float32)
    return a, b


def quanta_stacked(m: dict, key, spec: dict, tenants, trained: bool) -> dict:
    """``{target: (T tensors, S tensors)}`` for every tenant in
    ``tenants`` (an int array), leaves ``(n_tenants, L, ...)``."""
    layers = jnp.arange(m["num_hidden_layers"])
    return {
        tgt: jax.vmap(lambda tn: jax.lax.map(
            lambda l: quanta_layer(key, spec, tgt, tn, l, trained), layers)
        )(tenants)
        for tgt in spec["targets"]
    }


def lora_stacked(m: dict, key, spec: dict, tenants) -> dict:
    """``{target: (A, B)}``, leaves ``(n_tenants, L, ...)``."""
    layers = jnp.arange(m["num_hidden_layers"])
    shapes = proj_shapes(m)
    return {
        tgt: jax.vmap(lambda tn: jax.lax.map(
            lambda l: lora_layer(key, spec, tgt, shapes[tgt], tn, l), layers)
        )(tenants)
        for tgt in spec["targets"]
    }
