"""Sharded serving: mesh-aware ``ServingEngine`` equivalence + the paged
pool sharding rules.

The ``multidevice`` tests run a 2x`data` . 4x`model` mesh on 8 virtual
CPU devices (see ``tests/conftest.py`` for how the device count is
forced) and pin the PR's acceptance bar: a sharded engine must produce
token-for-token IDENTICAL greedy outputs to the single-device engine —
dense and paged caches, all three model families, through slot churn,
mid-decode preemption, and chunked prefill.  The plain tests cover the
``cache_shardings`` pool rules on abstract meshes (no devices needed)
and the subprocess fallback that keeps the suite exercised in tier-1
runs where jax already initialized with one device.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke
from repro.launch.mesh import make_abstract_mesh, make_host_mesh
from repro.launch.shardings import cache_shardings
from repro.models import build_model
from repro.serve import Request, ServingEngine
from repro.serve.paging import PagedCacheView, addressable_nbytes

ARCHES = ["qwen2-0.5b", "recurrentgemma-2b", "mamba2-1.3b"]
PROMPTS = [[5, 9, 13], [40, 2], [7, 7, 7, 7, 21, 3, 99], [100, 101],
           [1], [13, 5, 88, 4, 2], [250, 3, 17], [9] * 11]

multidevice = pytest.mark.multidevice


def _mesh():
    return make_host_mesh(2, 4)


def _serve(model, params, prompts=PROMPTS, max_new=5, **kw):
    engine = ServingEngine(model, params, **kw)
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert all(r.done for r in reqs)
    return [r.output for r in reqs], engine


# ------------------------------------------------ sharded == single-device
@multidevice
@pytest.mark.parametrize("arch", ARCHES)
def test_sharded_engine_matches_single_device(arch):
    """Mesh 2x`data` . 4x`model`: dense AND paged sharded engines must
    generate token-for-token what the single-device engine does, with
    more requests than slots (slot churn: freed-slot reset, block
    free/reuse, and the scatter all interact across waves)."""
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    base, _ = _serve(model, params, n_slots=4, max_len=64)
    mesh = _mesh()
    for mode in ("dense", "paged"):
        out, engine = _serve(model, params, n_slots=4, max_len=64,
                             mesh=mesh, cache=mode, block_size=8)
        assert out == base, (arch, mode)
        if mode == "paged" and engine.pager.paged:
            # the pool really was arena-partitioned over the data axis
            assert engine.pager.data_shards == 2
            assert engine.stats["blocks_in_use"] == 0


@multidevice
def test_sharded_frontend_matches_single_device():
    """The async SLA front end over a mesh-sharded engine (dense AND
    paged): seeded open-loop arrivals, EDF class queues, and chained
    double-buffered dispatch must still be token-for-token identical to
    the single-device closed loop."""
    from repro.serve import ServeFrontend, VirtualClock, poisson_arrivals

    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    base, _ = _serve(model, params, n_slots=4, max_len=64)
    mesh = _mesh()
    for mode in ("dense", "paged"):
        engine = ServingEngine(model, params, n_slots=4, max_len=64,
                               mesh=mesh, cache=mode, block_size=8)
        engine.clock = VirtualClock()
        fe = ServeFrontend(engine)
        arrivals = poisson_arrivals(
            np.random.default_rng(0), 200.0, len(PROMPTS)
        )
        reqs = [
            Request(uid=i, prompt=list(p), max_new_tokens=5,
                    arrival_time=float(arrivals[i]),
                    latency_class="interactive" if i % 2 == 0 else "batch")
            for i, p in enumerate(PROMPTS)
        ]
        streams = [fe.submit(r) for r in reqs]
        fe.drain()
        assert [r.output for r in reqs] == base, mode
        assert all(s.closed and s.tokens == r.output
                   for s, r in zip(streams, reqs))
        assert fe.stats["chained"] > 0
        engine.compile_guard.assert_ok()


@multidevice
def test_sharded_paged_pallas_backend_matches_reference():
    """The shard_map-wrapped paged flash-decode kernel (per-shard block
    indices translated to arena-local pool rows) must match the
    single-device reference engine token for token."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [[5, 9, 13], [40, 2, 17, 3], [7] * 9, [3, 1, 4, 1, 5], [2, 7]]
    base, _ = _serve(model, params, prompts=prompts, n_slots=4, max_len=64)
    pl = build_model(cfg.replace(attn_backend="pallas", kv_block=16))
    out, engine = _serve(pl, params, prompts=prompts, n_slots=4, max_len=64,
                         mesh=_mesh(), cache="paged", block_size=16)
    assert out == base
    assert engine.pager.data_shards == 2


@multidevice
def test_sharded_preemption_resumes_exactly():
    """Mid-decode pool exhaustion under a mesh preempts within the
    failing slot's arena (a victim from another data shard frees nothing
    useful) and the stream resumes token-for-token."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [[7 + i] * 8 for i in range(4)]

    def run(n_blocks, mesh):
        out, engine = _serve(
            model, params, prompts=prompts, max_new=24, n_slots=4,
            max_len=64, mesh=mesh, cache="paged", block_size=8,
            n_blocks=n_blocks,
        )
        assert all(len(o) == 24 for o in out)
        return out, engine.stats["preemptions"]

    base, none = run(4 * 8 + 2, None)
    tight, n_preempt = run(12, _mesh())        # 2 arenas of 6 (5 usable)
    ample, none2 = run(4 * 8 + 2, _mesh())
    assert none == 0 and none2 == 0 and n_preempt > 0
    assert tight == base and ample == base


@multidevice
def test_sharded_admission_skips_full_arena():
    """Regression: a full arena must not head-of-line block admission.
    Slot 1 is free but its arena (shard 0) is exhausted by the hog in
    slot 0 — the next request must admit into a shard-1 slot whose arena
    is empty, not wait for the hog to finish."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # 2 arenas of 6 rows (5 usable each); slots 0-1 = arena 0, 2-3 = 1
    engine = ServingEngine(model, params, n_slots=4, max_len=64,
                           mesh=_mesh(), cache="paged", block_size=8,
                           n_blocks=12)
    hog = Request(uid=0, prompt=[7] * 8, max_new_tokens=30)
    quick = [Request(uid=1 + i, prompt=[3 + i] * 8, max_new_tokens=2)
             for i in range(3)]
    engine.submit(hog)
    for r in quick:
        engine.submit(r)
    # hog -> slot 0 (arena 0); after ~25 ticks it holds all 5 usable
    # arena-0 blocks (8 prompt + >24 generated tokens = 5 blocks) and
    # the quick requests have long drained slots 1-3.
    engine.run(max_ticks=26)
    assert all(r.done for r in quick) and not hog.done
    assert engine.pager.can_admit(8, 0) is False       # arena 0 full
    late = Request(uid=9, prompt=[5] * 8, max_new_tokens=4)
    engine.submit(late)
    engine.step()
    assert any(r is late for r in engine.slots), (
        "admission stalled on the full arena instead of using shard 1"
    )
    engine.run()
    assert late.done and hog.done


@multidevice
def test_sharded_chunked_prefill_matches_one_shot():
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    long_prompt = [int(t) for t in
                   np.random.default_rng(0).integers(1, 255, (40,))]
    base, _ = _serve(model, params, prompts=[long_prompt], max_new=6,
                     n_slots=2, max_len=64)
    out, engine = _serve(model, params, prompts=[long_prompt], max_new=6,
                         n_slots=2, max_len=64, mesh=_mesh(), cache="paged",
                         block_size=8, prefill_chunk=8)
    assert out == base
    assert engine.stats["chunk_calls"] == -(-40 // 8)


@multidevice
def test_sharded_adapter_bank_matches_single_device():
    """Multi-tenant acceptance, mesh leg: a bank engine serving a mixed
    QuanTA + LoRA + base wave on the 2x`data` . 4x`model` mesh must
    produce token-for-token what the single-device bank engine does
    (which tests/test_adapter_bank.py pins against per-tenant
    single-tenant engines) — dense AND paged, through slot churn."""
    from repro.core.bank import AdapterBank
    from repro.core.peft import PeftConfig, attach

    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    qbase, qset = attach(
        jax.random.PRNGKey(1), params,
        PeftConfig(method="quanta", scheme=None, n_axes=3, noise_scale=0.3),
    )
    _, lset = attach(jax.random.PRNGKey(2), params,
                     PeftConfig(method="lora", rank=4))
    lset = jax.tree_util.tree_map(
        lambda x: x + 0.15 * jax.random.normal(
            jax.random.PRNGKey(3), x.shape, x.dtype
        ),
        lset,
    )
    bank = AdapterBank.build(params, {"qa": (qbase, qset), "lo": lset})
    tenants = ["qa", "lo", None, "qa", "lo", None, "qa", "lo"]

    def run(mesh, cache):
        engine = ServingEngine(model, params, adapters=bank, n_slots=4,
                               max_len=64, mesh=mesh, cache=cache,
                               block_size=8)
        reqs = [Request(uid=i, prompt=list(p), max_new_tokens=5, adapter=t)
                for i, (p, t) in enumerate(zip(PROMPTS, tenants))]
        for r in reqs:
            engine.submit(r)
        engine.run()
        assert all(r.done for r in reqs)
        return [r.output for r in reqs]

    base = run(None, "dense")
    for mode in ("dense", "paged"):
        assert run(_mesh(), mode) == base, mode


@multidevice
def test_sharded_adapter_pool_matches_single_device():
    """Hot-swap lifecycle, mesh leg: an ``AdapterPool`` engine churning
    4 LoRA tenants through a capacity-2 resident bank on the
    2x`data` . 4x`model` mesh — swaps rewrite replicated bank rows
    between ticks (``pool.place`` + the bank traced-argument shardings)
    and must generate token-for-token what the single-device pool
    engine does, with zero serving-jit recompiles on both."""
    from repro.core.peft import PeftConfig, attach
    from repro.serve import AdapterPool, AdapterStore

    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def lora(key):
        _, s = attach(jax.random.PRNGKey(key), params,
                      PeftConfig(method="lora", rank=4))
        return jax.tree_util.tree_map(
            lambda x: x + 0.15 * jax.random.normal(
                jax.random.PRNGKey(key + 100), x.shape, x.dtype
            ),
            s,
        )

    tenants = ["t0", "t1", "t2", None, "t3", "t0", "t2", "t1"]

    def run(mesh):
        store = AdapterStore(max_tenants=8)
        for i in range(4):
            store.register(f"t{i}", lora(i + 1))
        pool = AdapterPool.build(params, store, capacity=2)
        engine = ServingEngine(model, params, adapters=pool, n_slots=4,
                               max_len=64, mesh=mesh)
        reqs = [Request(uid=i, prompt=list(p), max_new_tokens=5, adapter=t)
                for i, (p, t) in enumerate(zip(PROMPTS, tenants))]
        for r in reqs:
            engine.submit(r)
        engine.run()
        assert all(r.done for r in reqs)
        engine.compile_guard.assert_ok()
        assert engine.stats["adapter_evictions"] > 0, "no churn exercised"
        return [r.output for r in reqs]

    assert run(_mesh()) == run(None)


@multidevice
def test_uneven_slot_split_rejected():
    """n_slots not divisible by the mesh data-parallel size must raise:
    the slot axis shards over the data axes, and an uneven split used to
    silently generate wrong tokens (XLA pads the ragged shard)."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="multiple of the mesh"):
        ServingEngine(model, params, n_slots=3, max_len=64, mesh=_mesh())


@multidevice
def test_sharded_quantized_base_matches_single_device():
    """Quantized-base mesh leg: with ``base_quant="nf4"`` the packed
    uint8 codes and per-block scales take the projection sharding rules
    (launch.shardings routes QuantizedLinear children by their parent
    path), and the sharded engine — dense AND paged — must generate
    token-for-token what the single-device quantized engine does."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    base, e0 = _serve(model, params, n_slots=4, max_len=64,
                      base_quant="nf4")
    fp_bytes = _serve(model, params, n_slots=4, max_len=64,
                      mesh=_mesh())[1].stats["param_bytes"]
    for mode in ("dense", "paged"):
        out, engine = _serve(model, params, n_slots=4, max_len=64,
                             mesh=_mesh(), cache=mode, block_size=8,
                             base_quant="nf4")
        assert out == base, mode
        assert engine.stats["base_quant"] == "nf4"
        # the per-host gauge shrinks vs the fp engine on the same mesh
        assert 0 < engine.stats["param_bytes"] < fp_bytes


@multidevice
@pytest.mark.parametrize("fmt", ["nf4", "int8"])
def test_sharded_quantized_kv_matches_single_device(fmt):
    """Quantized-KV mesh leg: with ``cfg.kv_quant`` the packed-code pools
    and their ``_qscale`` siblings take the spec-driven pool rules (DP on
    the block axis), and the sharded paged engine — reference AND pallas
    backends — must generate token-for-token what the single-device
    DENSE fake-quantized engine does, with compile-guard bounds held."""
    cfg = get_smoke("qwen2-0.5b").replace(kv_quant=fmt)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    base, _ = _serve(model, params, n_slots=4, max_len=64)
    out, engine = _serve(model, params, n_slots=4, max_len=64,
                         mesh=_mesh(), cache="paged", block_size=8,
                         kv_quant=fmt)
    assert out == base
    assert engine.stats["kv_quant"] == fmt
    assert engine.pager.data_shards == 2
    assert any(n.endswith("_qscale") for n in engine.pager.serve_spec)
    engine.compile_guard.assert_ok()
    if fmt == "nf4":
        pl = build_model(cfg.replace(attn_backend="pallas", kv_block=16))
        out, engine = _serve(pl, params, n_slots=4, max_len=64,
                             mesh=_mesh(), cache="paged", block_size=16)
        assert out == base
        engine.compile_guard.assert_ok()


@multidevice
def test_sharded_prefill_admission_is_o1_dispatches():
    """O(1) jitted dispatch per admitted wave must survive the mesh: one
    prefill call and the tick's one fused decode, regardless of prompt
    length.  Asserted through the sanitizer's compile guard — under a
    mesh the insert scatter is jitted too, so all four entry points are
    held to their documented compilation bounds."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, n_slots=4, max_len=64,
                           admission="prefill", mesh=_mesh())
    for i in range(4):
        engine.submit(Request(uid=i, prompt=[3 + i] * 20, max_new_tokens=1))
    engine.step()
    assert engine.stats["prefill_calls"] == 1
    assert engine.stats["decode_calls"] == 1
    counts = engine.compile_guard.counts()
    assert counts["prefill"] == 1
    assert counts["decode"] == 1
    # mesh-only: the jitted insert scatter is guarded as well
    assert "insert" in counts and counts["insert"] >= 1
    engine.compile_guard.assert_ok()


@multidevice
def test_sharded_paged_decode_compile_guard():
    """Mesh + paged cache: slot churn and block growth across shard-local
    arenas never retrace the fused decode — the compile guard's bounds
    hold on every jitted entry point (decode, prefill, insert)."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, n_slots=4, max_len=64,
                           admission="prefill", mesh=_mesh(),
                           cache="paged", block_size=8)
    prompts = [[5, 9, 13], [7] * 21, [40, 2], [9] * 11, [1], [3, 3, 3]]
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=8)
            for i, p in enumerate(prompts)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert all(r.done for r in reqs)
    # one real compile + the first tick's placement-signature entry
    # (see ServingEngine.compilation_bounds mesh slack)
    assert engine.compile_guard.counts()["decode"] \
        <= engine.compilation_bounds()["decode"]
    engine.compile_guard.assert_ok()


@multidevice
def test_gauges_report_per_host_addressable_bytes():
    """Byte gauges must report per-host (addressable) device memory once
    leaves shard: DP-sharded leaves bill only local partitions, model-
    replicated leaves bill every local copy.  Computed independently
    from the engine's cache leaves here."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = _mesh()

    dense = ServingEngine(model, params, n_slots=4, max_len=64, mesh=mesh)
    expect = sum(
        addressable_nbytes(leaf)
        for leaf in jax.tree_util.tree_leaves(dense.cache)
    )
    assert dense.stats["cache_bytes_allocated"] == expect
    # the slot axis shards 2-way over `data` but replicates over the
    # 4-way `model` axis: per-host bytes exceed the logical array bytes
    logical = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(dense.cache)
    )
    assert expect > logical

    paged = ServingEngine(model, params, n_slots=4, max_len=64, mesh=mesh,
                          cache="paged", block_size=8)
    pool_bytes = sum(
        addressable_nbytes(leaf)
        for leaf in jax.tree_util.tree_leaves(paged.cache)
        if leaf.ndim == 5                      # the K/V pools
    )
    per_block = pool_bytes / paged.pager.n_blocks
    _, engine = _serve(model, params, n_slots=4, max_len=64, mesh=mesh,
                       cache="paged", block_size=8)
    # drained engine: every block freed, only dense leaves remain billed
    dense_leaf_bytes = sum(
        addressable_nbytes(leaf)
        for leaf in jax.tree_util.tree_leaves(engine.cache)
        if leaf.ndim != 5
    )
    assert engine.stats["blocks_in_use"] == 0
    assert engine.stats["cache_bytes_allocated"] == int(dense_leaf_bytes)
    assert paged.pager._bytes_per_block == per_block


def test_dense_gauge_equals_addressable_bytes_single_device():
    """Regression pin for the per-host accounting on the DENSE path: on
    one device addressable bytes equal plain ``nbytes``, and the gauge
    must report exactly that (no double counting, no global-vs-local
    drift)."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, n_slots=2, max_len=32)
    expect = sum(
        int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(engine.cache)
    )
    assert engine.stats["cache_bytes_allocated"] == expect
    assert addressable_nbytes(
        jax.tree_util.tree_leaves(engine.cache)[0]
    ) == int(jax.tree_util.tree_leaves(engine.cache)[0].nbytes)


def test_peft_shardings_bank_axis_rules():
    """Adapter placement rules (no devices needed): single sets replicate
    every leaf; ``bank_dp=True`` shards exactly the bank axis of
    bank-stacked group leaves over `data` (when divisible), keeping
    ``id_maps`` and everything else replicated."""
    from repro.core.bank import AdapterBank
    from repro.core.peft import PeftConfig, attach
    from repro.launch.shardings import peft_shardings

    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    _, l1 = attach(jax.random.PRNGKey(1), params,
                   PeftConfig(method="lora", rank=4))
    _, l2 = attach(jax.random.PRNGKey(2), params,
                   PeftConfig(method="lora", rank=4))
    mesh = make_abstract_mesh((2, 4), ("data", "model"))

    # single adapter set: all replicated
    for s in jax.tree_util.tree_leaves(peft_shardings(mesh, l1)):
        assert s.spec == P()

    bank = AdapterBank.build(params, {"a": l1, "b": l2})
    # default: bank replicated too (per-slot ids may need any tenant)
    for s in jax.tree_util.tree_leaves(peft_shardings(mesh, bank)):
        assert s.spec == P()
    # bank_dp: stacked group leaves (L, G+1=3, ...) have a 3-extent bank
    # axis — NOT divisible by data=2, so they stay replicated...
    sh = peft_shardings(mesh, bank, bank_dp=True)
    for s in jax.tree_util.tree_leaves(sh):
        assert s.spec == P()
    # ...while a 4-tenant bank (bank extent 5) still replicates, and a
    # 3-tenant one (extent 4) DP-splits exactly the bank axis.
    _, l3 = attach(jax.random.PRNGKey(3), params,
                   PeftConfig(method="lora", rank=4))
    bank3 = AdapterBank.build(params, {"a": l1, "b": l2, "c": l3})
    sh3 = peft_shardings(mesh, bank3, bank_dp=True)
    path = bank3.tree["layers"]["attn"]["q_proj"]
    sh_path = sh3.tree["layers"]["attn"]["q_proj"]
    group_specs = {
        s.spec for s in jax.tree_util.tree_leaves(sh_path.groups)
    }
    assert group_specs == {P(None, ("data",), None, None)}
    assert all(
        leaf.shape[1] == 1 + bank3.num_tenants
        for leaf in jax.tree_util.tree_leaves(path.groups)
    )
    for s in sh_path.id_maps:
        assert s.spec == P()


# ------------------------------------------------ pool sharding rules
def test_cache_shardings_paged_pool_rules():
    """Pool leaves: block-pool axis over `data`, block_size axis NEVER
    sharded, KV-heads/head_dim per the model rule; dense leaves keep the
    slot-stripe rules."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    view = PagedCacheView(model, n_slots=4, max_len=64, block_size=8,
                          data_shards=2)
    mesh = make_abstract_mesh((2, 4), ("data", "model"))
    sh = cache_shardings(cfg, mesh, view.struct(), spec=view.spec,
                         paged=True)
    # k/v pools (L, n_blocks, block_size, KV=2, hd=16): KV (2) does not
    # divide the 4-way model axis -> head_dim shards instead
    assert sh["k"].spec == P(None, ("data",), None, None, "model")
    assert sh["v"].spec == P(None, ("data",), None, None, "model")
    assert sh["len"].spec == P(("data",))

    # non-divisible pool-row count -> pool axis replicated
    odd = PagedCacheView(model, n_slots=4, max_len=64, block_size=8,
                         n_blocks=33)
    sh = cache_shardings(cfg, mesh, odd.struct(), spec=odd.spec, paged=True)
    assert sh["k"].spec == P(None, None, None, None, "model")


def test_cache_shardings_paged_non_divisible_gqa_heads():
    """36 KV heads on an 8-way model axis: the pool's KV axis cannot
    shard, head_dim (128) takes the model rule — and block_size stays
    unsharded even though it divides."""
    cfg = get_smoke("qwen2-0.5b").replace(
        n_heads=36, n_kv_heads=36, head_dim=128
    )
    model = build_model(cfg)
    view = PagedCacheView(model, n_slots=2, max_len=64, block_size=16,
                          data_shards=2)
    mesh = make_abstract_mesh((2, 8), ("data", "model"))
    sh = cache_shardings(cfg, mesh, view.struct(), spec=view.spec,
                         paged=True)
    # (L, n_blocks, 16, 36, 128): 36 % 8 != 0, 128 % 8 == 0
    assert sh["k"].spec == P(None, ("data",), None, None, "model")
    assert sh["v"].spec == P(None, ("data",), None, None, "model")


def test_cache_shardings_griffin_ring_pool_leaves():
    """Griffin's ring-buffer pools: K/V pools take data+model, the int32
    ``pos`` pool has no dims past block_size -> pool axis only; O(1)
    LRU/conv/tail leaves keep the dense slot rules."""
    cfg = get_smoke("recurrentgemma-2b")
    model = build_model(cfg)
    view = PagedCacheView(model, n_slots=4, max_len=64, block_size=8,
                          data_shards=2)
    assert view.paged
    mesh = make_abstract_mesh((2, 4), ("data", "model"))
    sh = cache_shardings(cfg, mesh, view.struct(), spec=view.spec,
                         paged=True)
    # PartitionSpec normalizes a one-axis tuple ("data",) to "data"
    assert sh["pos"].spec == P(None, "data", None)
    assert sh["k"].spec[1] == "data" and sh["k"].spec[2] is None
    # dense leaves: slot axis over data
    assert sh["lru1"].spec[1] == "data"
    assert sh["tail_lru1"].spec[0] == "data"

    # paged=False (dense engine) must keep the original stripe rules for
    # the SAME spec tree — paging is strictly additive
    dense_struct = jax.eval_shape(lambda: model.init_cache(4, 64))
    with_spec = cache_shardings(cfg, mesh, dense_struct, spec=view.spec,
                                paged=False)
    without = cache_shardings(cfg, mesh, dense_struct)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a.spec == b.spec, with_spec, without
    ))


def test_paged_view_arena_partitioning():
    """data_shards=2: slots allocate only from their own arena, each
    arena has its own null row, release returns blocks to the right
    arena, and a request can never exceed one arena."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    view = PagedCacheView(model, n_slots=4, max_len=64, block_size=8,
                          data_shards=2)
    a = view.arena_size
    assert view.n_blocks == 4 * 8 + 2 and a == (4 * 8 + 2) // 2
    assert view.shard_of(0) == 0 and view.shard_of(1) == 0
    assert view.shard_of(2) == 1 and view.shard_of(3) == 1
    assert view.null_of(1) == a
    assert view.max_request_blocks == a - 1
    view.ensure(0, 20)          # 3 blocks from arena 0
    view.ensure(3, 9)           # 2 blocks from arena 1
    t = np.asarray(view.device_tables())
    assert (t[0, :3] > 0).all() and (t[0, :3] < a).all()
    assert (t[3, :2] > a).all() and (t[3, :2] < 2 * a).all()
    assert (t[1] == 0).all() and (t[2] == a).all()      # per-arena nulls
    assert view.wave_tables(np.array([3]), 4)[0, 2] == a  # arena-1 pad
    view.release(3)
    assert (np.asarray(view.device_tables())[3] == a).all()
    stats = view.stats()
    assert stats["blocks_in_use"] == 3
    assert stats["blocks_total"] == view.n_blocks - 2
    # odd n_blocks rounds UP to keep arenas equal
    odd = PagedCacheView(model, n_slots=4, max_len=64, block_size=8,
                         n_blocks=7, data_shards=2)
    assert odd.n_blocks == 8 and odd.arena_size == 4


# ------------------------------------------------ subprocess fallback
def test_multidevice_suite_subprocess_fallback():
    """When this process initialized jax with < 8 devices (the flag can't
    apply post-init), run the multidevice suite in a spawned child with
    ``REPRO_FORCE_MULTIDEVICE=1`` so tier-1 still executes it."""
    if jax.device_count() >= 8:
        pytest.skip("suite already ran in-process on >= 8 devices")
    if os.environ.get("REPRO_MULTIDEVICE_SUBPROCESS", "1") == "0":
        pytest.skip("subprocess fallback disabled "
                    "(REPRO_MULTIDEVICE_SUBPROCESS=0)")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"       # virtual devices; never the chip
    env["REPRO_FORCE_MULTIDEVICE"] = "1"
    env["REPRO_MULTIDEVICE_SUBPROCESS"] = "0"     # no recursion
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-m", "multidevice",
         os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=3000,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    assert out.returncode == 0, out.stdout + out.stderr
    # the child must have RUN the suite, not skipped it
    assert "passed" in out.stdout and "skipped" not in out.stdout.split(
        "passed")[-1], out.stdout
