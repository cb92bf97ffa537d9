"""Paged KV-cache subsystem: allocator invariants, paged-vs-dense engine
equivalence (token for token, with slot churn), chunked-prefill admission,
the paged flash-decode kernel, and the roofline's allocated-blocks
billing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import build_model
from repro.models.attention import paged_decode_attention
from repro.serve import Request, ServingEngine
from repro.serve.paging import NULL_BLOCK, BlockAllocator, PagedCacheView

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ------------------------------------------------------------- allocator
def test_allocator_never_double_assigns():
    alloc = BlockAllocator(17)
    held = set()
    rng = np.random.default_rng(0)
    for _ in range(200):
        if held and rng.random() < 0.4:
            n = rng.integers(1, len(held) + 1)
            victims = rng.choice(sorted(held), size=n, replace=False)
            alloc.free(victims)
            held -= set(int(v) for v in victims)
        else:
            n = int(rng.integers(1, 4))
            if n <= alloc.available:
                got = alloc.alloc(n)
                assert not (set(got) & held), "double-assigned a block"
                assert NULL_BLOCK not in got
                held |= set(got)
        assert alloc.in_use == len(held)


def test_allocator_fragmentation_then_drain_returns_all():
    alloc = BlockAllocator(33)
    total = alloc.available
    slabs = [alloc.alloc(4) for _ in range(8)]
    # free every other slab (fragmentation), realloc odd sizes, then drain
    for s in slabs[::2]:
        alloc.free(s)
    odd = [alloc.alloc(3) for _ in range(5)]
    for s in slabs[1::2] + odd:
        alloc.free(s)
    assert alloc.available == total
    assert alloc.in_use == 0
    assert alloc.peak_in_use == 8 * 4


def test_allocator_errors():
    alloc = BlockAllocator(5)
    got = alloc.alloc(4)
    with pytest.raises(MemoryError):
        alloc.alloc(1)
    alloc.free(got[:2])
    with pytest.raises(ValueError):
        alloc.free(got[:1])          # double free
    with pytest.raises(ValueError):
        alloc.free([NULL_BLOCK])     # reserved
    with pytest.raises(ValueError):
        alloc.free([99])             # foreign


if HAVE_HYPOTHESIS:

    @settings(max_examples=30, deadline=None)
    @given(
        n_blocks=st.integers(min_value=2, max_value=40),
        ops=st.lists(st.integers(min_value=0, max_value=6), max_size=60),
    )
    def test_allocator_property_alloc_free_reuse(n_blocks, ops):
        alloc = BlockAllocator(n_blocks)
        total = alloc.available
        held = []
        for op in ops:
            if op == 0 and held:
                alloc.free([held.pop()])
            elif op <= alloc.available and op > 0:
                got = alloc.alloc(op)
                assert len(set(got) | set(held)) == len(got) + len(held)
                held += got
        alloc.free(held)
        assert alloc.available == total


# ------------------------------------------------- paged cache view
def test_paged_view_tables_and_clamp():
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    view = PagedCacheView(model, n_slots=2, max_len=64, block_size=8)
    assert view.paged and view.tokens_per_slot == 64
    view.init_cache()
    view.ensure(0, 20)               # 3 blocks
    view.ensure(1, 1)                # 1 block
    t = np.asarray(view.device_tables())
    assert (t[0, :3] > 0).all() and (t[0, 3:] == t[0, 2]).all()
    assert (t[1, 1:] == t[1, 0]).all()
    view.ensure(0, 21)               # no boundary crossing: no new block
    assert view.allocator.in_use == 4
    view.release(0)
    assert view.allocator.in_use == 1
    assert (np.asarray(view.device_tables())[0] == NULL_BLOCK).all()


def test_mamba2_view_is_trivially_dense():
    cfg = get_smoke("mamba2-1.3b")
    model = build_model(cfg)
    view = PagedCacheView(model, n_slots=2, max_len=64, block_size=8)
    assert not view.paged
    cache = view.init_cache()
    ref = jax.eval_shape(lambda: model.init_cache(2, 64))
    assert jax.tree_util.tree_map(
        lambda a, b: a.shape == b.shape, cache, ref
    )


# ------------------------------------------------- paged decode attention
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("h,kv,hd,bs", [
    (8, 4, 16, 8),      # GQA: the per-head body
    (6, 6, 64, 16),     # MHA, two heads a 128-lane vreg: the all-heads body
])
def test_paged_decode_attention_kernel_matches_gather(h, kv, hd, bs, dtype,
                                                      tol):
    nb = 8
    lens = jnp.array([5, 37, 1, 16, 17, nb * bs], jnp.int32)
    b = lens.shape[0]
    n_pool = b * nb + 1
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd)).astype(dtype)
    k_pool = jax.random.normal(ks[1], (n_pool, bs, kv, hd)).astype(dtype)
    v_pool = jax.random.normal(ks[2], (n_pool, bs, kv, hd)).astype(dtype)
    rng = np.random.default_rng(1)
    perm = rng.permutation(np.arange(1, n_pool))
    tables = np.zeros((b, nb), np.int32)
    off = 0
    for i in range(b):
        n_alloc = -(-int(lens[i]) // bs)
        tables[i, :n_alloc] = perm[off:off + n_alloc]
        tables[i, n_alloc:] = tables[i, n_alloc - 1]
        off += n_alloc
    tables = jnp.asarray(tables)
    for window in (None, 12):
        ref = paged_decode_attention(q, k_pool, v_pool, tables, lens,
                                     window=window)
        out = paged_decode_attention(q, k_pool, v_pool, tables, lens,
                                     window=window, backend="pallas")
        assert out.dtype == dtype
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)


def test_decode_attn_body_choice():
    """The all-heads body engages for one query row per KV head over an
    fp pool (minicpm-2b's paged serving shapes) and nowhere else."""
    from repro.configs.minicpm_2b import FULL as MINICPM
    from repro.configs.qwen2_0_5b import FULL as QWEN
    from repro.kernels.flash_attention import _all_heads_body

    def body(cfg, fmt, bk):
        kv = cfg.n_kv_heads
        return _all_heads_body(kv, cfg.n_heads // kv, fmt, bk,
                               kv * cfg.head_dim)

    assert body(MINICPM, None, 16)
    assert not body(QWEN, None, 16)                 # 14 / 2 heads: GQA
    for fmt in ("nf4", "int8"):
        assert not body(MINICPM, fmt, 16)           # nibble / code planes


# (the odd-max_len pad+slice decode fix is covered in test_attention.py:
#  test_decode_non_divisible_cache_stays_on_pallas)


# --------------------------------------------------- engine equivalence
@pytest.mark.parametrize("arch", ["qwen2-0.5b", "recurrentgemma-2b",
                                  "mamba2-1.3b"])
def test_paged_engine_matches_dense(arch):
    """Paged and dense caches must produce IDENTICAL greedy outputs on
    mixed prompt lengths with more requests than slots (slot churn: blocks
    free on eviction and are re-used by later admissions)."""
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [[5, 9, 13], [40, 2], [7, 7, 7, 7, 21, 3, 99], [100, 101],
               [1], [13, 5, 88, 4, 2], [250, 3, 17], [9] * 11]
    outs = {}
    for mode in ("dense", "paged"):
        engine = ServingEngine(model, params, n_slots=3, max_len=64,
                               cache=mode, block_size=8)
        reqs = [Request(uid=i, prompt=list(p), max_new_tokens=6)
                for i, p in enumerate(prompts)]
        for r in reqs:
            engine.submit(r)
        engine.run()
        assert all(r.done for r in reqs)
        outs[mode] = [r.output for r in reqs]
    assert outs["paged"] == outs["dense"]


@pytest.mark.parametrize("mode", ["dense", "paged"])
def test_teacher_forced_logits_follow_greedy_decode(mode):
    """Forcing an engine's own greedy tokens reproduces them: row ``i``'s
    argmax is the token greedy decoding emitted after continuation
    ``i``, and the engine is idle again afterwards (a repeat call
    admits and returns the same logits)."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, n_slots=2, max_len=64,
                           cache=mode, block_size=8)
    prompt = [5, 9, 13, 40, 2]
    req = Request(uid=0, prompt=list(prompt), max_new_tokens=6)
    engine.submit(req)
    engine.run()
    logits = engine.teacher_forced_logits(prompt, req.output[:-1])
    assert logits.shape == (5, cfg.vocab_size)
    assert np.argmax(logits, axis=-1).tolist() == req.output[1:]
    again = engine.teacher_forced_logits(prompt, req.output[:-1])
    np.testing.assert_array_equal(again, logits)


def test_paged_engine_pallas_backend_matches_dense_reference():
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [[5, 9, 13], [40, 2, 17, 3], [7] * 9]
    outs = {}
    for backend, mode in (("reference", "dense"), ("pallas", "paged")):
        m = build_model(cfg.replace(attn_backend=backend, kv_block=16))
        engine = ServingEngine(m, params, n_slots=3, max_len=64,
                               cache=mode, block_size=16)
        reqs = [Request(uid=i, prompt=list(p), max_new_tokens=5)
                for i, p in enumerate(prompts)]
        for r in reqs:
            engine.submit(r)
        engine.run()
        outs[mode] = [r.output for r in reqs]
    assert outs["paged"] == outs["dense"]


def test_paged_engine_frees_blocks_and_reports_gauges():
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, n_slots=2, max_len=64,
                           cache="paged", block_size=8)
    reqs = [Request(uid=i, prompt=[i + 1] * 10, max_new_tokens=4)
            for i in range(4)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert all(r.done for r in reqs)
    s = engine.stats
    assert s["blocks_in_use"] == 0                 # all freed on drain
    assert s["peak_blocks_in_use"] > 0
    assert 0.0 < s["peak_block_utilization"] <= 1.0
    assert s["blocks_total"] == 2 * (64 // 8)
    # dense engine reports the full stripe bytes as a constant gauge
    dense = ServingEngine(model, params, n_slots=2, max_len=64)
    assert dense.stats["cache_bytes_allocated"] > 0
    assert dense.stats["blocks_in_use"] == 0


# --------------------------------------------------- chunked prefill
def test_chunked_prefill_matches_one_shot_and_interleaves_decode():
    """A long prompt admitted in fixed-size chunks must produce the same
    greedy output as one-shot prefill admission, while the fused decode
    tick keeps running between chunks (admission does not block decode)."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    long_prompt = [int(t) for t in
                   np.random.default_rng(0).integers(1, 255, (40,))]
    outs = {}
    for chunk in (None, 8):
        engine = ServingEngine(model, params, n_slots=2, max_len=64,
                               cache="paged", block_size=8,
                               prefill_chunk=chunk)
        short = Request(uid=0, prompt=[3, 1, 4], max_new_tokens=20)
        long = Request(uid=1, prompt=list(long_prompt), max_new_tokens=6)
        engine.submit(short)
        engine.step()                       # short active and decoding
        decode_before = engine.stats["decode_calls"]
        engine.submit(long)
        engine.run()
        assert short.done and long.done
        outs[chunk] = (short.output, long.output)
        if chunk is not None:
            assert engine.stats["chunk_calls"] == -(-40 // 8)
            # decode ticks fired during the 5 chunked-admission ticks
            assert engine.stats["decode_calls"] - decode_before >= 5
    assert outs[8] == outs[None]


def test_chunked_prefill_non_chunk_aligned_bucket():
    """Regression: a prompt whose seq-bucketed length is NOT a multiple of
    the chunk size (31 tokens, chunk 6, bucket 16) must still match
    one-shot prefill — the staging buffer is chunk-aligned so the final
    chunk's slab write cannot clamp and overwrite earlier K/V rows."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = [int(t) for t in
              np.random.default_rng(2).integers(1, 255, (31,))]
    outs = {}
    for chunk in (None, 6):
        for mode in ("dense", "paged"):
            engine = ServingEngine(model, params, n_slots=1, max_len=40,
                                   cache=mode, block_size=8,
                                   prefill_chunk=chunk)
            r = Request(uid=0, prompt=list(prompt), max_new_tokens=5)
            engine.submit(r)
            engine.run()
            outs[(chunk, mode)] = r.output
    assert len(set(map(tuple, outs.values()))) == 1, outs


def test_paged_admission_reserves_blocks_under_pressure():
    """Regression: with a pool too small for the whole wave, admission
    must defer the requests that don't fit (and admit them later as
    blocks free) instead of tearing mid-wave on a MemoryError."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # 5 usable blocks of 8 tokens; each 20-token prompt needs 3 blocks,
    # so only one fits at a time.
    engine = ServingEngine(model, params, n_slots=2, max_len=64,
                           cache="paged", block_size=8, n_blocks=6)
    reqs = [Request(uid=i, prompt=[i + 1] * 20, max_new_tokens=4)
            for i in range(3)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert all(r.done and len(r.output) == 4 for r in reqs)
    assert engine.stats["blocks_in_use"] == 0
    # a request that could NEVER fit (prompt + generation budget exceeds
    # the whole pool) is rejected at submit
    with pytest.raises(ValueError, match="never be admitted"):
        engine.submit(Request(uid=9, prompt=[1] * 30, max_new_tokens=30))


def test_paged_decode_growth_preempts_and_resumes_exactly():
    """Regression: when GENERATION (not admission) exhausts the pool, the
    engine preempts a slot vLLM-recompute-style instead of crashing —
    and the preempted stream resumes token-for-token identical to an
    amply-provisioned engine, because re-prefilling ``prompt + output``
    is numerically the same as having kept decoding."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def run(n_blocks):
        engine = ServingEngine(model, params, n_slots=2, max_len=64,
                               cache="paged", block_size=8,
                               n_blocks=n_blocks)
        reqs = [Request(uid=i, prompt=[7 + i] * 8, max_new_tokens=24)
                for i in range(2)]
        for r in reqs:
            engine.submit(r)
        engine.run()
        assert all(r.done and len(r.output) == 24 for r in reqs)
        return [r.output for r in reqs], engine.stats["preemptions"]

    # each request alone needs 4 blocks; 5 usable forces mid-decode
    # preemption, 2*8+1 provisions the worst case (no preemption).
    tight, n_preempt = run(6)
    ample, none = run(2 * 8 + 1)
    assert n_preempt > 0 and none == 0
    assert tight == ample


def test_chunked_prefill_dense_cache_too():
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = [int(t) for t in
              np.random.default_rng(1).integers(1, 255, (23,))]
    outs = {}
    for chunk in (None, 6):
        engine = ServingEngine(model, params, n_slots=1, max_len=64,
                               prefill_chunk=chunk)
        r = Request(uid=0, prompt=list(prompt), max_new_tokens=5)
        engine.submit(r)
        engine.run()
        outs[chunk] = r.output
    assert outs[6] == outs[None]


# --------------------------------------------------- roofline billing
def test_roofline_bills_paged_decode_by_allocated_blocks():
    from repro.configs import get_config
    from repro.configs.shapes import SHAPES
    from repro.launch.roofline import paged_cache_adjustment

    cfg = get_config("minicpm-2b")
    shape = next(s for s in SHAPES if s.name == "decode_32k")
    assert paged_cache_adjustment(cfg, shape) is None       # dense default
    adj = paged_cache_adjustment(cfg.replace(kv_cache="paged"), shape)
    assert adj is not None
    assert adj["paged_rows_per_slot"] < adj["dense_rows_per_slot"]
    assert adj["kv_bytes_saved"] > 0
    # block-granular rounding: occupancy just over a block boundary bills
    # the whole next block
    tiny = paged_cache_adjustment(
        cfg.replace(kv_cache="paged", kv_occupancy=1 / 32768 + 1e-9,
                    kv_block_size=64),
        shape,
    )
    assert tiny["paged_rows_per_slot"] == 64
    train = next(s for s in SHAPES if s.name == "train_4k")
    assert paged_cache_adjustment(
        cfg.replace(kv_cache="paged"), train
    ) is None                                               # decode-only


# ------------------------------------------------ quantized KV blocks
def test_quantize_kv_roundtrip_and_remainder_blocks():
    from repro.core.quantize import fake_quantize_kv, kv_dequant_values, \
        quantize_kv

    rng = jax.random.PRNGKey(3)
    for d, fmt, qb in [(64, "nf4", 64), (80, "nf4", 64), (24, "int8", 16),
                       (64, "int8", 64)]:
        x = jax.random.normal(rng, (5, 7, 2, d), jnp.float32)
        codes, scales = quantize_kv(x, fmt, block_size=qb)
        n_sb = -(-d // qb)
        assert scales.shape == (5, 7, 2, n_sb)
        assert scales.dtype == jnp.float32
        assert codes.dtype == (jnp.uint8 if fmt == "nf4" else jnp.int8)
        assert codes.shape[-1] == (d // 2 if fmt == "nf4" else d)
        deq = kv_dequant_values(codes, scales, fmt=fmt, block_size=qb, d=d)
        assert deq.shape == x.shape
        # nf4's worst case is half the largest codebook gap (~0.152)
        # times the block absmax; int8 is absmax / 254
        tol = 0.16 if fmt == "nf4" else 0.02
        err = float(jnp.max(jnp.abs(deq - x)))
        amax = float(jnp.max(jnp.abs(x)))
        assert err <= tol * amax
        # fake_quantize_kv IS the round trip (the dense-reference write)
        np.testing.assert_array_equal(
            np.asarray(fake_quantize_kv(x, fmt, block_size=qb)),
            np.asarray(deq.astype(x.dtype)))
    # per-token-row granularity: quantizing a stripe == quantizing rows
    x = jax.random.normal(rng, (3, 8, 2, 64), jnp.float32)
    c_all, s_all = quantize_kv(x, "nf4")
    c_one, s_one = quantize_kv(x[:, 2:3], "nf4")
    np.testing.assert_array_equal(np.asarray(c_all[:, 2:3]),
                                  np.asarray(c_one))
    np.testing.assert_array_equal(np.asarray(s_all[:, 2:3]),
                                  np.asarray(s_one))
    with pytest.raises(ValueError):
        quantize_kv(x[..., :63], "nf4")          # nf4 needs even head_dim


@pytest.mark.parametrize("fmt,hd,qb", [("nf4", 16, 16), ("nf4", 80, 64),
                                       ("int8", 24, 16)])
def test_paged_quant_decode_kernel_matches_reference(fmt, hd, qb):
    """Pallas dequant-in-VMEM kernel vs the reference gather-and-dequant
    path — including remainder scale blocks (hd=80, qb=64) and windows."""
    from repro.core.quantize import quantize_kv

    b, h, kv, bs, nb = 3, 8, 4, 8, 8
    n_pool = b * nb + 1
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd))
    k_pool = jax.random.normal(ks[1], (n_pool, bs, kv, hd))
    v_pool = jax.random.normal(ks[2], (n_pool, bs, kv, hd))
    kc, ksc = quantize_kv(k_pool, fmt, block_size=qb)
    vc, vsc = quantize_kv(v_pool, fmt, block_size=qb)
    lens = jnp.array([5, 37, 64], jnp.int32)
    rng = np.random.default_rng(2)
    perm = rng.permutation(np.arange(1, n_pool))
    tables = np.zeros((b, nb), np.int32)
    off = 0
    for i in range(b):
        n_alloc = -(-int(lens[i]) // bs)
        tables[i, :n_alloc] = perm[off:off + n_alloc]
        tables[i, n_alloc:] = tables[i, n_alloc - 1]
        off += n_alloc
    tables = jnp.asarray(tables)
    quant = dict(kv_quant=fmt, k_scales=ksc, v_scales=vsc, quant_block=qb)
    for window in (None, 12):
        ref = paged_decode_attention(q, kc, vc, tables, lens,
                                     window=window, **quant)
        out = paged_decode_attention(q, kc, vc, tables, lens,
                                     window=window, backend="pallas",
                                     **quant)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "recurrentgemma-2b"])
@pytest.mark.parametrize("fmt", ["nf4", "int8"])
def test_paged_quant_engine_matches_dense_fake_quant(arch, fmt):
    """Quantized paged pools vs the dense fake-quantized cache: greedy
    outputs must be IDENTICAL (same codes at commit, same dequant_values
    on read), under slot churn."""
    cfg = get_smoke(arch).replace(kv_quant=fmt)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [[5, 9, 13], [40, 2], [7, 7, 7, 7, 21, 3, 99], [100, 101],
               [1], [13, 5, 88, 4, 2]]
    outs = {}
    for mode in ("dense", "paged"):
        engine = ServingEngine(model, params, n_slots=3, max_len=64,
                               cache=mode, block_size=8, kv_quant=fmt)
        reqs = [Request(uid=i, prompt=list(p), max_new_tokens=6)
                for i, p in enumerate(prompts)]
        for r in reqs:
            engine.submit(r)
        engine.run()
        assert all(r.done for r in reqs)
        assert engine.stats["kv_quant"] == fmt
        outs[mode] = [r.output for r in reqs]
    assert outs["paged"] == outs["dense"]


def test_paged_quant_engine_pallas_backend_matches_reference():
    cfg = get_smoke("qwen2-0.5b").replace(kv_quant="nf4", kv_block=16)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    prompts = [[5, 9, 13], [40, 2, 17, 3], [7] * 9]
    outs = {}
    for backend, mode in (("reference", "dense"), ("pallas", "paged")):
        m = build_model(cfg.replace(attn_backend=backend))
        engine = ServingEngine(m, params, n_slots=3, max_len=64,
                               cache=mode, block_size=16)
        reqs = [Request(uid=i, prompt=list(p), max_new_tokens=5)
                for i, p in enumerate(prompts)]
        for r in reqs:
            engine.submit(r)
        engine.run()
        outs[mode] = [r.output for r in reqs]
    assert engine.stats["decode_attn_body"] == "per_head"
    assert outs["paged"] == outs["dense"]


@pytest.mark.parametrize("arch,body", [("minicpm-2b", "all_heads"),
                                       ("qwen2-0.5b", "per_head")])
def test_paged_pallas_engine_decode_body(arch, body, capfd):
    """The paged Pallas engine (MHA: the all-heads body; GQA: per head)
    serves what the dense reference engine serves, and reports and logs
    the body its decode kernel runs."""
    cfg = get_smoke(arch).replace(kv_block=16)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    prompts = [[5, 9, 13], [40, 2, 17, 3], [7] * 19]
    outs = {}
    for backend, mode in (("reference", "dense"), ("pallas", "paged")):
        m = build_model(cfg.replace(attn_backend=backend))
        engine = ServingEngine(m, params, n_slots=3, max_len=64,
                               cache=mode, block_size=16)
        reqs = [Request(uid=i, prompt=list(p), max_new_tokens=5)
                for i, p in enumerate(prompts)]
        for r in reqs:
            engine.submit(r)
        engine.run()
        outs[mode] = [r.output for r in reqs]
    assert engine.stats["decode_attn_body"] == body
    assert capfd.readouterr().err.count(f"decode_attn_body: {body}") == 1
    assert outs["paged"] == outs["dense"]


def test_engine_kv_quant_kwarg_validation():
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="unknown kv_quant"):
        ServingEngine(model, params, n_slots=1, max_len=32, kv_quant="fp8")
    with pytest.raises(ValueError, match="requires the model cfg"):
        ServingEngine(model, params, n_slots=1, max_len=32, kv_quant="nf4")
    qmodel = build_model(cfg.replace(kv_quant="int8"))
    with pytest.raises(ValueError, match="conflicts"):
        ServingEngine(qmodel, params, n_slots=1, max_len=32,
                      kv_quant="nf4")


def test_quant_view_serve_spec_and_block_bytes():
    """The quantized view's serve_spec carries packed-code leaves plus
    fp32 ``_qscale`` siblings, and the materialized pool block is
    smaller than the fp pool block."""
    cfg = get_smoke("qwen2-0.5b")
    sizes = {}
    for fmt in (None, "nf4", "int8"):
        m = build_model(cfg.replace(kv_quant=fmt) if fmt else cfg)
        engine = ServingEngine(m, m.init(jax.random.PRNGKey(0)),
                               n_slots=2, max_len=32, cache="paged",
                               block_size=8, kv_quant=fmt)
        sizes[fmt] = engine.pager._bytes_per_block
        names = list(engine.pager.serve_spec)
        if fmt:
            assert any(n.endswith("_qscale") for n in names)
        else:
            assert not any(n.endswith("_qscale") for n in names)
    assert sizes["nf4"] < sizes["int8"] < sizes[None]


def test_paged_view_ensure_out_of_blocks_is_atomic():
    """A failed grow must raise MemoryError and leave the view exactly as
    it was: no table mutation, no count bump, no leaked blocks — the
    engine's admission retry path depends on this."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    # deliberately over-committed pool: 7 allocatable blocks for two
    # slots that can hold 8 each
    view = PagedCacheView(model, n_slots=2, max_len=64, block_size=8,
                          n_blocks=8)
    view.ensure(0, 40)                        # 5 blocks -> 2 left
    arena = view._arenas[view.shard_of(1)]
    assert arena.available == 2
    tables = view._tables.copy()
    counts = view._counts.copy()
    with pytest.raises(MemoryError):
        view.ensure(1, 4 * 8)                 # wants 4, has 2
    np.testing.assert_array_equal(view._tables, tables)
    np.testing.assert_array_equal(view._counts, counts)
    assert arena.available == 2               # nothing leaked
    view.ensure(1, 2 * 8)                     # what's left still works
    assert int(view._counts[1]) == 2


def test_roofline_quantized_kv_adjustment():
    from repro.configs import get_config
    from repro.configs.shapes import SHAPES
    from repro.launch.roofline import quantized_kv_adjustment

    cfg = get_config("minicpm-2b")
    shape = next(s for s in SHAPES if s.name == "decode_32k")
    assert quantized_kv_adjustment(cfg, shape) is None       # fp default
    paged = cfg.replace(kv_cache="paged", kv_quant="nf4")
    adj = quantized_kv_adjustment(paged, shape)
    assert adj is not None and adj["fmt"] == "nf4"
    assert adj["kv_read_bytes_quant"] < adj["kv_read_bytes_fp"]
    # nf4: 0.5 B/elem + fp32 scale per 64 elems vs 2 B fp16 -> ~3.56x
    assert 3.0 < adj["kv_stream_cut"] < 4.0
    i8 = quantized_kv_adjustment(cfg.replace(kv_cache="paged",
                                             kv_quant="int8"), shape)
    assert 1.5 < i8["kv_stream_cut"] < 2.0
    train = next(s for s in SHAPES if s.name == "train_4k")
    assert quantized_kv_adjustment(paged, train) is None     # decode-only


def test_roofline_paged_rows_ceil_before_block_round():
    """occupancy * seq_len fractionally ABOVE a block boundary must bill
    the next whole block: the old int() truncation dropped the fraction
    and under-billed one block (satellite fix)."""
    from repro.configs import get_config
    from repro.configs.shapes import SHAPES
    from repro.launch.roofline import paged_cache_adjustment

    cfg = get_config("minicpm-2b")
    shape = next(s for s in SHAPES if s.name == "decode_32k")
    s = shape.seq_len
    occ = (16.0 + 1e-4) / s                   # occupancy * s = 16.0001
    adj = paged_cache_adjustment(
        cfg.replace(kv_cache="paged", kv_occupancy=occ, kv_block_size=16),
        shape)
    assert adj["paged_rows_per_slot"] == 32   # 2 blocks, not int()->16
