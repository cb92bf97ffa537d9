"""Quantized frozen base: blockwise NF4/int8 format, the fused
dequant-matmul kernel, and quantized-base serving.

The correctness bar is BITWISE: ``kernels.quantized_matmul`` must equal
dequantize-then-matmul in the same dtype on every tested shape (the
kernel and the reference share one elementwise ``dequant_values`` and the
tiled full-K dots reassociate nothing — see the kernel's module
docstring), and a quantized-base engine must be token-for-token
deterministic across cache layouts and against the bank.  Quantization
itself is lossy; its guarantees are the blockwise round-trip bounds the
property tests pin.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_peft, get_smoke
from repro.core.bank import AdapterBank
from repro.core.peft import PeftConfig, attach
from repro.core.quantize import (
    NF4_CODEBOOK,
    QUANT_TARGETS,
    QuantizedLinear,
    base_matmul,
    blockwise_round,
    blockwise_scales,
    dequantize,
    expand_scales,
    matmul_ref,
    quantize_linear,
    quantize_params,
    quantized_nbytes,
)
from repro.kernels.quantized_matmul import quantized_matmul
from repro.models import build_model
from repro.serve import Request, ServingEngine

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

FMTS = ("nf4", "int8")
# largest adjacent codebook gap: the nf4 nearest-code error bound
_NF4_GAP = float(np.max(np.diff(NF4_CODEBOOK)))


def _bitwise_equal(a, b) -> bool:
    a, b = np.atleast_1d(np.asarray(a)), np.atleast_1d(np.asarray(b))
    return (
        a.dtype == b.dtype and a.shape == b.shape
        and np.array_equal(a.view(np.uint8), b.view(np.uint8))
    )


def _rand(key, shape, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(key), shape,
                                     jnp.float32)


# --------------------------------------------------------------------------
# blockwise helpers (shared with optim.compress): properties
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,bs", [(64, 64), (100, 64), (64, None),
                                  (7, 4), (129, 64)])
def test_blockwise_scales_positive_and_block_count(n, bs):
    x = _rand(0, (n, 5))
    scales = blockwise_scales(x, bs, axis=0, levels=127.0)
    n_blocks = 1 if bs is None else -(-n // bs)
    assert scales.shape == (n_blocks, 5)
    assert bool(jnp.all(scales > 0))          # eps floor: never divides by 0
    # all-zero input still yields positive scales
    z = blockwise_scales(jnp.zeros((n, 5)), bs, axis=0)
    assert bool(jnp.all(z > 0))


@pytest.mark.parametrize("n,bs", [(64, 64), (100, 64), (129, 32), (5, 8)])
def test_blockwise_int8_roundtrip_error_bound(n, bs):
    """|x - q*scale| <= scale/2 elementwise — including the remainder
    block, whose scale comes from its own (shorter) extent."""
    x = _rand(1, (n, 3), scale=2.0)
    scales = blockwise_scales(x, bs, axis=0, levels=127.0)
    q = blockwise_round(x, scales, bs, axis=0, levels=127)
    assert bool(jnp.all(jnp.abs(q) <= 127))
    per_row = expand_scales(scales, bs, n, axis=0)
    err = jnp.abs(x - q * per_row)
    assert bool(jnp.all(err <= per_row / 2 + 1e-7))


@pytest.mark.parametrize("fmt", FMTS)
@pytest.mark.parametrize("n,bs", [(64, 64), (100, 64), (130, 32)])
def test_dequantize_roundtrip_error_bound(fmt, n, bs):
    w = _rand(2, (n, 12), scale=0.5)
    qw = quantize_linear(w, fmt, block_size=bs)
    deq = dequantize(qw)
    assert deq.shape == w.shape and deq.dtype == w.dtype
    per_row = expand_scales(qw.scales.astype(jnp.float32), bs, n, axis=-2)
    # nf4 scales are absmax (codes in [-1,1]): error <= scale * gap/2;
    # int8 scales are absmax/127 (integer codes): error <= scale / 2
    half_gap = (_NF4_GAP / 2) if fmt == "nf4" else 0.5
    assert bool(jnp.all(jnp.abs(w - deq) <= per_row * half_gap + 1e-6))
    # the packed format is genuinely smaller than the fp32 matrix
    assert quantized_nbytes(qw) < w.size * 4


if HAVE_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(
        n=st.integers(1, 200),
        cols=st.integers(1, 6),
        bs=st.one_of(st.none(), st.integers(1, 64)),
        seed=st.integers(0, 2**31 - 1),
        scale=st.floats(1e-3, 1e3),
    )
    def test_hypothesis_blockwise_roundtrip(n, cols, bs, seed, scale):
        x = _rand(seed, (n, cols), scale=scale)
        s = blockwise_scales(x, bs, axis=0, levels=127.0)
        n_blocks = 1 if bs is None else -(-n // bs)
        assert s.shape == (n_blocks, cols)
        assert bool(jnp.all(s > 0))
        q = blockwise_round(x, s, bs, axis=0, levels=127)
        per = expand_scales(s, bs, n, axis=0)
        assert bool(jnp.all(jnp.abs(x - q * per) <= per / 2 + 1e-5 * scale))


def test_compress_int8_shares_blockwise_helpers():
    """optim.compress grad compression is the single-block special case of
    the shared helpers: one whole-tensor scale, same round, 0-d scale."""
    from repro.optim.compress import compress_int8, decompress_int8

    g = _rand(3, (37, 5), scale=3.0)
    q, scale = compress_int8(g)
    assert q.dtype == jnp.int8 and scale.shape == ()
    flat = g.reshape(-1)
    s_ref = blockwise_scales(flat, None, axis=0, levels=127.0)
    q_ref = blockwise_round(flat, s_ref, flat.shape[0], axis=0, levels=127)
    assert _bitwise_equal(q, q_ref.astype(jnp.int8).reshape(g.shape))
    assert _bitwise_equal(scale, s_ref[0])
    err = jnp.abs(g - decompress_int8(q, scale))
    assert bool(jnp.all(err <= scale / 2 + 1e-7))


# --------------------------------------------------------------------------
# format construction + validation
# --------------------------------------------------------------------------

def test_quantize_linear_validation():
    w = _rand(4, (64, 8))
    with pytest.raises(ValueError, match="even"):
        quantize_linear(_rand(5, (63, 8)), "nf4")
    with pytest.raises(ValueError, match="format"):
        quantize_linear(w, "fp4")
    with pytest.raises(ValueError, match="normalize"):
        quantize_linear(w, "nf4", normalize="diag")
    qw = quantize_linear(w, "nf4", block_size=16)
    assert qw.packed.dtype == jnp.uint8
    assert qw.packed.shape == (32, 8)          # two codes per byte
    assert qw.scales.shape == (4, 8)
    assert qw.shape == (64, 8) and qw.d_in == 64 and qw.ndim == 2
    q8 = quantize_linear(w, "int8", block_size=16)
    assert q8.packed.dtype == jnp.int8 and q8.packed.shape == (64, 8)


def test_quantize_linear_stacked_and_normalizers():
    w = _rand(6, (3, 32, 10), scale=0.3)       # scan-stacked (L, d_in, d_out)
    for normalize in (None, "row", "col", "rowcol"):
        qw = quantize_linear(w, "nf4", block_size=16, normalize=normalize)
        assert qw.shape == w.shape
        deq = dequantize(qw)
        assert deq.shape == w.shape
        # normalizers reduce dynamic range; round-trip stays close
        assert float(jnp.max(jnp.abs(w - deq))) < 0.12
        if normalize in ("row", "rowcol"):
            assert qw.row_norm is not None and qw.row_norm.shape == (3, 32)
        if normalize in ("col", "rowcol"):
            assert qw.col_norm is not None and qw.col_norm.shape == (3, 10)


def test_quantize_params_targets_and_idempotency():
    cfg = get_smoke("qwen2-0.5b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    qp = quantize_params(params, "nf4", block_size=cfg.quant_block_size)
    flat_q = {p: l for p, l in _flat(qp)}
    flat_fp = {p: l for p, l in _flat(params)}
    hit = [p for p, leaf in flat_q.items() if isinstance(leaf, QuantizedLinear)]
    assert hit, "no projection was quantized"
    for path in hit:
        assert path.split("/")[-1] in QUANT_TARGETS
    # embedding / norms / biases stay dense
    assert all(
        not isinstance(leaf, QuantizedLinear)
        for p, leaf in flat_q.items() if "embed" in p or "norm" in p
    )
    assert any(p not in hit for p in flat_fp)
    # idempotent: re-quantizing passes QuantizedLinear leaves through
    qp2 = quantize_params(qp, "nf4", block_size=cfg.quant_block_size)
    for (p1, l1), (p2, l2) in zip(_flat(qp), _flat(qp2)):
        assert p1 == p2
        if isinstance(l1, QuantizedLinear):
            assert l1 is l2


def _flat(tree, prefix=""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _flat(v, prefix + "/" + str(k))
        return
    yield prefix, tree


# --------------------------------------------------------------------------
# kernel vs reference: bitwise parity
# --------------------------------------------------------------------------

# (rows, d_in, d_out, block_size, normalize) — remainder rows, a ragged
# final scale block (d_in % block_size != 0), an under-full column block,
# and every normalizer layout
_PARITY_SHAPES = [
    (33, 100, 50, 64, None),
    (16, 72, 144, 16, "rowcol"),
    (8, 256, 640, 64, None),
    (5, 200, 136, 64, "row"),
    (12, 64, 96, 32, "col"),
]


@pytest.mark.parametrize("fmt", FMTS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_bitwise_parity_sweep(fmt, dtype):
    for i, (rows, d_in, d_out, bs, normalize) in enumerate(_PARITY_SHAPES):
        w = _rand(10 + i, (d_in, d_out), scale=0.4)
        qw = quantize_linear(w, fmt, block_size=bs, normalize=normalize)
        x = _rand(20 + i, (rows, d_in)).astype(dtype)
        ref = matmul_ref(x, qw)
        out = quantized_matmul(x, qw, block_rows=16, block_cols=128,
                               interpret=True)
        assert out.dtype == x.dtype
        assert _bitwise_equal(out, ref), (fmt, dtype, _PARITY_SHAPES[i])


def test_kernel_parity_3d_input_and_jit():
    w = _rand(30, (64, 48), scale=0.4)
    qw = quantize_linear(w, "nf4", block_size=16)
    x = _rand(31, (2, 7, 64)).astype(jnp.bfloat16)
    ref = matmul_ref(x, qw)
    out = jax.jit(
        lambda x: quantized_matmul(x, qw, block_rows=8, block_cols=48,
                                   interpret=True)
    )(x)
    assert _bitwise_equal(out, ref)


def test_base_matmul_dispatch():
    """Plain arrays keep the exact ``x @ w``; QuantizedLinear dispatches to
    the kernel under backend="pallas" and the reference otherwise — all
    three bitwise-identical on CPU."""
    w = _rand(40, (64, 32), scale=0.4)
    x = _rand(41, (9, 64))
    assert _bitwise_equal(base_matmul(x, w, "pallas"), x @ w)
    qw = quantize_linear(w, "int8", block_size=16)
    ref = base_matmul(x, qw, "reference")
    assert _bitwise_equal(ref, matmul_ref(x, qw))
    assert _bitwise_equal(base_matmul(x, qw, "pallas"), ref)


def test_vmem_gate_falls_back_to_reference():
    """The kernel never hands a weight back to the reference: the wrapper
    narrows the column tile until one grid step fits the VMEM budget
    (same result as the reference), and raises where no tile fits."""
    from repro.kernels.quantized_matmul import quantized_tile_cols

    w = _rand(50, (4096, 4096), scale=0.3)
    qw = quantize_linear(w, "nf4", block_size=64)
    bc = quantized_tile_cols(qw, block_rows=512)
    assert 128 <= bc < 512
    x = _rand(51, (600, 4096)).astype(jnp.bfloat16)
    out = quantized_matmul(x, qw, block_rows=512, interpret=True)
    assert _bitwise_equal(out, matmul_ref(x, qw))

    huge = jax.eval_shape(
        lambda w: quantize_linear(w, "nf4", block_size=64),
        jax.ShapeDtypeStruct((65536, 128), jnp.bfloat16),
    )
    with pytest.raises(ValueError, match="VMEM budget"):
        jax.eval_shape(
            lambda x, q: quantized_matmul(x, q, interpret=True),
            jax.ShapeDtypeStruct((2, 65536), jnp.bfloat16), huge,
        )


# --------------------------------------------------------------------------
# serving: quantized base end to end
# --------------------------------------------------------------------------

MAX_NEW = 5
PROMPTS = [[5, 9, 13], [40, 2], [7, 7, 7, 7, 21, 3, 99], [100, 101],
           [1], [13, 5, 88, 4, 2]]


def _serve(model, params, peft=None, adapters=None, assignments=None,
           **kw):
    engine = ServingEngine(model, params, peft, adapters=adapters,
                           n_slots=3, max_len=64, **kw)
    assignments = assignments or [(i, p, None) for i, p in enumerate(PROMPTS)]
    reqs = []
    for uid, prompt, tenant in assignments:
        r = Request(uid=uid, prompt=list(prompt), max_new_tokens=MAX_NEW)
        engine.submit(r, adapter=tenant if adapters is not None else None)
        reqs.append(r)
    engine.run()
    assert all(r.done for r in reqs)
    return {r.uid: r.output for r in reqs}, engine


@pytest.mark.parametrize("fmt", FMTS)
def test_quantized_engine_matches_reference_greedy(fmt):
    """The engine's quantized decode must equal a hand-rolled greedy loop
    over ``model.forward`` with the SAME quantized params — the engine adds
    no numerics of its own on top of the format."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    qparams = quantize_params(params, fmt, block_size=cfg.quant_block_size)

    def reference_greedy(prompt, n_new):
        toks = list(prompt)
        for _ in range(n_new):
            logits, _ = model.forward(
                qparams, {"tokens": jnp.asarray([toks])}, None
            )
            toks.append(int(jnp.argmax(logits[0, -1, : cfg.vocab_size])))
        return toks[len(prompt):]

    outs, engine = _serve(model, params, base_quant=fmt)
    assert engine.stats["base_quant"] == fmt
    for uid, prompt, _ in [(i, p, None) for i, p in enumerate(PROMPTS)]:
        assert outs[uid] == reference_greedy(prompt, MAX_NEW), uid
    engine.compile_guard.assert_ok()


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "recurrentgemma-2b",
                                  "mamba2-1.3b"])
def test_quantized_dense_paged_and_prequantized_agree(arch):
    """nf4 engine invariances: dense == paged token-for-token, and
    passing pre-quantized params equals quantizing inside the engine
    (idempotent ``quantize_params``).  ``param_bytes`` gauge shrinks."""
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dense, e_dense = _serve(model, params, base_quant="nf4")
    fp, e_fp = _serve(model, params)
    assert e_dense.stats["param_bytes"] < e_fp.stats["param_bytes"]
    assert e_fp.stats["base_quant"] == "none"
    qparams = quantize_params(params, "nf4", block_size=cfg.quant_block_size)
    pre, _ = _serve(model, qparams)
    assert pre == dense
    if arch != "mamba2-1.3b":   # mamba2 has no pageable leaves
        paged, e_paged = _serve(model, params, base_quant="nf4",
                                cache="paged", block_size=8)
        assert paged == dense
        e_paged.compile_guard.assert_ok()
    e_dense.compile_guard.assert_ok()


def _noise(tree, key, scale=0.15):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        leaf + scale * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)
    ])


@pytest.mark.parametrize("cache", ["dense", "paged"])
def test_quantized_bank_matches_single_tenant(cache):
    """Mixed QuanTA + LoRA + base waves on a QUANTIZED shared base must be
    token-for-token what per-tenant engines over the SAME quantized params
    produce.  The QuanTA tenant's folded base is quantized up front (the
    bank's RebasedAdapter then carries QuantizedLinear bases); the engine's
    idempotent re-quantization accepts all of it unchanged."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    targets = get_peft("qwen2-0.5b").targets
    qbase, qset = attach(
        jax.random.PRNGKey(1), params,
        PeftConfig(method="quanta", scheme=None, n_axes=3,
                   noise_scale=0.3, targets=targets),
    )
    _, lset = attach(jax.random.PRNGKey(2), params,
                     PeftConfig(method="lora", rank=4, targets=targets))
    lset = _noise(lset, jax.random.PRNGKey(3))
    bs = cfg.quant_block_size
    shared_q = quantize_params(params, "nf4", block_size=bs)
    folded_q = quantize_params(qbase, "nf4", block_size=bs)
    bank = AdapterBank.build(shared_q, {"qa": (folded_q, qset), "lo": lset})

    rotation = ["qa", "lo", None]
    mixed = [(i, p, rotation[i % 3]) for i, p in enumerate(PROMPTS)]
    kw = dict(cache=cache, block_size=8)
    outs, engine = _serve(model, shared_q, adapters=bank, base_quant="nf4",
                          assignments=mixed, **kw)
    assert engine.stats["adapter_tenants"] == 2
    assert engine.stats["base_quant"] == "nf4"
    per = {
        "qa": _serve(model, folded_q, peft=qset, base_quant="nf4",
                     assignments=[a for a in mixed if a[2] == "qa"], **kw)[0],
        "lo": _serve(model, shared_q, peft=lset,
                     assignments=[a for a in mixed if a[2] == "lo"], **kw)[0],
        None: _serve(model, shared_q,
                     assignments=[a for a in mixed if a[2] is None], **kw)[0],
    }
    for uid, _p, tenant in mixed:
        assert outs[uid] == per[tenant][uid], (uid, tenant)
    engine.compile_guard.assert_ok()


# --------------------------------------------------------------------------
# quality gate: quantized-base fine-tuning within tolerance of fp base
# --------------------------------------------------------------------------

def test_quantized_base_quanta_finetune_within_tolerance():
    """QLoRA-style: QuanTA trained against an nf4 frozen base on the RTE
    proxy must land within tolerance of the fp run.  The teacher is
    planted on the fake-quantized base (``benchmarks.common.make_task``
    docstring: on this d=64 toy, nf4's weight error swamps the planted
    strength-0.1 delta, so a fp-teacher comparison would measure format
    noise, not fine-tuning) — the gate isolates whether ADAPTATION
    against a quantized-stored base is as good as against fp storage."""
    common = pytest.importorskip(
        "benchmarks.common", reason="benchmarks importable from repo root"
    )
    fp = common.finetune("quanta", common.make_task("low"), steps=150,
                         n_axes=3)
    q = common.finetune("quanta", common.make_task("low", base_quant="nf4"),
                        steps=150, n_axes=3, base_quant="nf4")
    assert q.accuracy > fp.accuracy - 0.05, (q.accuracy, fp.accuracy)
    assert q.accuracy > 0.9, q.accuracy
