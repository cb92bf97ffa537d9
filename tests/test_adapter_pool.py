"""Adapter lifecycle subsystem: ``AdapterStore`` registry + hot-swap
``AdapterPool`` residency under the serving engine.

The acceptance bar: a large tenant registry churning through a small
fixed-capacity resident bank serves every request token-for-token
identical to cold single-tenant engines, with ZERO serving-jit
recompiles across loads/evictions (compile_guard), pinned in-flight
tenants refusing eviction (deferred admission instead of torn waves),
preemption requeueing across an evict + reload, allocator
double-free/leak invariants, and the fold-free QuanTA byte pin —
resident rows cost factor bytes, never a dense ``(d_in, d_out)`` copy.
"""

import jax
import numpy as np
import pytest

from repro.configs import get_peft, get_smoke
from repro.core.peft import PeftConfig, attach, flatten_paths
from repro.models import build_model
from repro.serve import (
    AdapterPool, AdapterStore, Request, RowAllocator, ServingEngine,
)
from repro.serve.paging import addressable_nbytes

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

PROMPTS = [[5, 9, 13], [40, 2], [7, 7, 7, 7, 21, 3, 99], [100, 101],
           [1], [13, 5, 88, 4, 2], [250, 3, 17], [9] * 11]
MAX_NEW = 5


# ------------------------------------------------------------- allocator
def test_row_allocator_basics():
    alloc = RowAllocator(3)
    assert alloc.available == 3 and alloc.in_use == 0
    rows = [alloc.alloc() for _ in range(3)]
    assert rows == [1, 2, 3]          # row 0 is the neutral, never issued
    with pytest.raises(MemoryError, match="bank full"):
        alloc.alloc()
    alloc.free(2)
    assert alloc.alloc() == 2
    with pytest.raises(ValueError, match="double free"):
        alloc.free(3) or alloc.free(3)
    with pytest.raises(ValueError, match="invalid bank row"):
        alloc.free(0)
    with pytest.raises(ValueError, match="invalid bank row"):
        alloc.free(4)
    assert alloc.peak_in_use == 3
    with pytest.raises(ValueError, match="at least one"):
        RowAllocator(0)


def test_row_allocator_never_double_assigns():
    """Deterministic random alloc/free trace (the hypothesis-free
    mirror of the BlockAllocator invariant test)."""
    alloc = RowAllocator(9)
    held = set()
    rng = np.random.default_rng(0)
    for _ in range(300):
        if held and rng.random() < 0.45:
            victim = int(rng.choice(sorted(held)))
            alloc.free(victim)
            held.discard(victim)
        elif alloc.available:
            row = alloc.alloc()
            assert row not in held, "double-assigned a bank row"
            assert 0 < row <= 9, "neutral/out-of-range row issued"
            held.add(row)
        assert alloc.in_use == len(held)
        assert alloc.available == 9 - len(held)
    for row in sorted(held):
        alloc.free(row)
    assert alloc.in_use == 0 and alloc.available == 9


if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(
        cap=st.integers(min_value=1, max_value=12),
        ops=st.lists(st.integers(min_value=0, max_value=2 ** 16),
                     max_size=60),
    )
    def test_row_allocator_trace_property(cap, ops):
        """Any alloc/free interleaving keeps the free-list leak-free:
        no row handed out twice, counts conserved, drain restores all."""
        alloc = RowAllocator(cap)
        held = []
        for op in ops:
            if held and op % 2:
                alloc.free(held.pop(op % len(held)))
            elif alloc.available:
                row = alloc.alloc()
                assert row not in held
                held.append(row)
            assert alloc.in_use == len(held)
        for row in held:
            alloc.free(row)
        assert alloc.available == cap


# ------------------------------------------------------------- registry
def _base(arch="qwen2-0.5b"):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params, get_peft(arch).targets


def _noise(tree, key, scale=0.15):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        leaf + scale * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)
    ])


def _np_variant(aset, seed, scale=0.1):
    """Cheap host-side tenant variant: numpy noise, no device dispatch —
    registry tenants are host state, so numpy leaves are the idiom."""
    rng = np.random.default_rng(seed)
    leaves, treedef = jax.tree_util.tree_flatten(aset)
    return jax.tree_util.tree_unflatten(treedef, [
        np.asarray(leaf)
        + (scale * rng.standard_normal(np.shape(leaf))).astype(
            np.asarray(leaf).dtype)
        for leaf in leaves
    ])


def _lora(params, targets, key, rank=4):
    _, lset = attach(jax.random.PRNGKey(key), params,
                     PeftConfig(method="lora", rank=rank, targets=targets))
    return _noise(lset, jax.random.PRNGKey(key + 1000))


def test_store_validation():
    model, params, targets = _base()
    lset = _lora(params, targets, 1)
    store = AdapterStore(max_tenants=2)
    assert store.register("a", lset) == 1
    with pytest.raises(ValueError, match="already registered"):
        store.register("a", lset)
    assert store.register("b", _lora(params, targets, 2)) == 2
    with pytest.raises(ValueError, match="registry full"):
        store.register("c", _lora(params, targets, 3))
    with pytest.raises(KeyError, match="unknown adapter"):
        store.get("zzz")
    with pytest.raises(KeyError, match="unknown adapter"):
        store.id_of("zzz")
    assert store.id_of(None) == 0
    assert store.id_of("a") == 1 and store.id_of("b") == 2
    assert store.names == ("a", "b") and store.num_tenants == 2
    assert store.nbytes > 0

    # folded QuanTA must arrive as the (params, set) pair attach returned
    qbase, qset = attach(
        jax.random.PRNGKey(9), params,
        PeftConfig(method="quanta", scheme=None, n_axes=3, targets=targets),
    )
    fresh = AdapterStore(max_tenants=4)
    with pytest.raises(ValueError, match="folds the frozen copy"):
        fresh.register("q", qset)
    fresh.register("q", (qbase, qset))        # the pair is fine
    with pytest.raises(ValueError, match="max_tenants"):
        AdapterStore(max_tenants=0)


def test_pool_build_validation():
    model, params, targets = _base()
    store = AdapterStore(max_tenants=4)
    with pytest.raises(ValueError, match="at least one tenant"):
        AdapterPool.build(params, store, capacity=2)
    store.register("a", _lora(params, targets, 1))
    with pytest.raises(ValueError, match="capacity"):
        AdapterPool.build(params, store, capacity=0)


# ------------------------------------------------------------ lifecycle
def test_pool_lifecycle_lru_pins_and_late_registration():
    model, params, targets = _base()
    store = AdapterStore(max_tenants=8)
    for i in range(4):
        store.register(f"t{i}", _lora(params, targets, i + 1))
    pool = AdapterPool.build(params, store, capacity=2)
    bytes0 = pool.resident_nbytes()

    # fill: t0, t1 resident; LRU is t0
    assert pool.load("t0") and pool.load("t1")
    assert pool.num_resident == 2 and pool.is_resident("t0")
    # t2 evicts the least-recently-used unpinned tenant (t0)
    assert pool.acquire("t2")
    assert not pool.is_resident("t0") and pool.is_resident("t1")
    assert pool.evictions == 1 and pool.loads == 3

    # pinned tenants refuse eviction...
    assert pool.pins_of("t2") == 1
    assert pool.evict("t2") is False and pool.evict_denied == 1
    # ...and with every row pinned, acquire defers instead of tearing
    assert pool.acquire("t1")
    assert pool.acquire("t3") is False and pool.acquire_denied == 1
    # releasing t1 frees a victim; t3 now loads (evicting t1)
    pool.release("t1")
    assert pool.acquire("t3") and not pool.is_resident("t1")
    pool.release("t2")
    pool.release("t3")
    assert pool.evict("t3") is True and pool.evict("t3") is False

    with pytest.raises(ValueError, match="without a matching acquire"):
        pool.release("t2") or pool.release("t2")
    assert pool.acquire(None) is True         # base model: always ready
    pool.release(None)                        # and a no-op to release

    # device footprint is capacity-fixed: churn never grew it (summed
    # afresh here; resident_nbytes() reports the figure taken at build)
    assert pool.resident_nbytes() == bytes0 == sum(
        addressable_nbytes(leaf)
        for leaf in jax.tree_util.tree_leaves(pool.tree)
    )

    # late registration with a MATCHING structure hot-loads fine
    store.register("late", _lora(params, targets, 77))
    assert pool.load("late")
    # ...but a novel structure (different rank -> new group) needs rebuild
    store.register("r8", _lora(params, targets, 88, rank=8))
    with pytest.raises(ValueError, match="matching no resident group"):
        pool.load("r8")

    stats = pool.stats()
    assert stats["adapter_capacity"] == 2
    assert stats["adapter_bytes_resident"] == bytes0
    # registry bytes are summed at registration; sum them afresh here
    assert stats["adapter_bytes_registry"] == store.nbytes == sum(
        addressable_nbytes(leaf)
        for name in store.names
        for adapter, _ in store.get(name).values()
        for leaf in jax.tree_util.tree_leaves(adapter)
    )
    # exact load seconds replace the log2 swap histogram
    assert stats["adapter_load_s"] == pool.load_s > 0.0


# -------------------------------------------------------------- serving
def _serve(model, params, assignments, peft=None, adapters=None, **kw):
    engine = ServingEngine(model, params, peft, adapters=adapters,
                           n_slots=kw.pop("n_slots", 3),
                           max_len=kw.pop("max_len", 64), **kw)
    reqs = []
    for uid, prompt, tenant in assignments:
        r = Request(uid=uid, prompt=list(prompt), max_new_tokens=MAX_NEW)
        engine.submit(r, adapter=tenant if adapters is not None else None)
        reqs.append(r)
    engine.run()
    assert all(r.done for r in reqs)
    return {r.uid: r.output for r in reqs}, engine


def _mixed_tenants(params, targets):
    """One of each structure family: fold-free QuanTA, LoRA, DoTA."""
    _, qset = attach(
        jax.random.PRNGKey(1), params,
        PeftConfig(method="quanta", scheme=None, n_axes=3,
                   noise_scale=0.3, fold=False, targets=targets),
    )
    lset = _lora(params, targets, 2)
    _, dset = attach(jax.random.PRNGKey(3), params,
                     PeftConfig(method="dota", rank=4, n_axes=3,
                                targets=targets))
    dset = _noise(dset, jax.random.PRNGKey(4), scale=0.05)
    return {"qa": qset, "lo": lset, "do": dset}


@pytest.mark.parametrize("cache", ["dense", "paged"])
def test_churn_matches_cold_engines(cache):
    """Mixed fold-free-QuanTA / LoRA / DoTA tenants churning through a
    capacity-2 pool: token-for-token vs dedicated engines, zero serving
    recompiles, and the resident/registry byte split."""
    model, params, targets = _base()
    tenants = _mixed_tenants(params, targets)
    store = AdapterStore(max_tenants=8)
    for name, aset in tenants.items():
        store.register(name, aset)
    pool = AdapterPool.build(params, store, capacity=2)

    rotation = ["qa", "lo", "do", None]
    mixed = [(i, p, rotation[i % 4]) for i, p in enumerate(PROMPTS)]
    kw = dict(cache=cache, block_size=8)
    outs, engine = _serve(model, params, mixed, adapters=pool, **kw)

    counts = engine.compile_guard.counts()
    engine.compile_guard.assert_ok()
    assert counts["decode"] == 1 and counts["prefill"] == 1
    assert counts["swap"] <= pool.n_profiles
    assert engine.stats["adapter_loads"] >= 3
    assert engine.stats["adapter_bytes_registry"] == store.nbytes
    assert engine.stats["adapter_bytes"] == pool.resident_nbytes()
    assert all(pool.pins_of(n) == 0 for n in tenants), "leaked a pin"

    for name, aset in tenants.items():
        per = _serve(model, params,
                     [a for a in mixed if a[2] == name], peft=aset, **kw)[0]
        for uid, _p, t in mixed:
            if t == name:
                assert outs[uid] == per[uid], (uid, t)
    base = _serve(model, params, [a for a in mixed if a[2] is None], **kw)[0]
    for uid, _p, t in mixed:
        if t is None:
            assert outs[uid] == base[uid], uid


def test_preemption_and_deferral_across_evict_reload():
    """Paged + tight blocks + capacity-1 pool: requests defer while their
    group's only row is pinned, preempted requests requeue and re-acquire
    (reloading after eviction), and the stream still matches an
    ample-resources pool run token-for-token."""
    model, params, targets = _base()
    l0, l1 = _lora(params, targets, 1), _lora(params, targets, 2)
    prompts = [[7 + i] * 8 for i in range(4)]
    assigns = [(i, p, ["l0", "l1", None, "l0"][i])
               for i, p in enumerate(prompts)]

    def run(capacity, n_blocks):
        store = AdapterStore(max_tenants=4)
        store.register("l0", l0)
        store.register("l1", l1)
        pool = AdapterPool.build(params, store, capacity=capacity)
        outs, engine = _serve(model, params, assigns, adapters=pool,
                              cache="paged", block_size=8,
                              n_blocks=n_blocks)
        engine.compile_guard.assert_ok()
        return outs, engine.stats, pool

    ample, astats, _ = run(capacity=2, n_blocks=4 * 8 + 2)
    # capacity 1 defers the second tenant, so at most TWO slots decode
    # concurrently: 3 blocks lets both prefill (1 block each) but only
    # one grow past its first block — the other preempts mid-decode
    tight, tstats, tpool = run(capacity=1, n_blocks=3)
    assert astats["preemptions"] == 0
    assert tstats["preemptions"] > 0
    # capacity 1, two same-structure tenants: someone had to wait...
    assert tstats["adapter_acquire_denied"] > 0
    # ...and serving both meant evicting + reloading within one run
    assert tstats["adapter_evictions"] >= 1
    assert tstats["adapter_loads"] >= 3
    assert all(tpool.pins_of(n) == 0 for n in ("l0", "l1"))
    assert tight == ample


def test_thousand_tenant_registry_32_row_bank():
    """The headline scenario: a 1000-tenant registry over a 32-row
    resident bank.  A churning 40-tenant slice serves token-for-token
    (spot-checked vs cold engines), swaps never recompile the serving
    jits, and the byte split shows registry >> resident."""
    model, params, targets = _base()
    _, proto = attach(jax.random.PRNGKey(1), params,
                      PeftConfig(method="lora", rank=4, targets=targets))
    store = AdapterStore(max_tenants=1000)
    sets = {}
    for i in range(1000):
        name = f"t{i:04d}"
        aset = _np_variant(proto, seed=i)
        sets[name] = aset
        assert store.register(name, aset) == i + 1
    assert store.num_tenants == 1000
    pool = AdapterPool.build(params, store, capacity=32)

    # serve one request each for 40 distinct tenants spread across the
    # registry: 40 > 32 forces eviction churn mid-run
    served = [f"t{i * 25:04d}" for i in range(40)]
    assigns = [(i, PROMPTS[i % len(PROMPTS)], name)
               for i, name in enumerate(served)]
    outs, engine = _serve(model, params, assigns, adapters=pool,
                          n_slots=4)

    counts = engine.compile_guard.counts()
    engine.compile_guard.assert_ok()
    assert counts["decode"] == 1 and counts["swap"] == 1
    assert engine.stats["adapter_tenants"] == 1000
    assert engine.stats["adapter_loads"] >= 40
    assert engine.stats["adapter_evictions"] >= 8
    assert engine.stats["adapter_residents"] <= 32
    # the split the subsystem exists for: host registry bytes dwarf the
    # capacity-fixed device bank
    assert (engine.stats["adapter_bytes_registry"]
            > 4 * engine.stats["adapter_bytes_resident"])

    # spot-check token-for-token against cold single-tenant engines
    for name in (served[0], served[17], served[39]):
        cold = _serve(model, params,
                      [a for a in assigns if a[2] == name],
                      peft=jax.tree_util.tree_map(
                          lambda x: jax.numpy.asarray(x), sets[name]))[0]
        for uid, _p, t in assigns:
            if t == name:
                assert outs[uid] == cold[uid], (uid, t)


# ------------------------------------------------------- fold-free bytes
def test_foldfree_quanta_resident_bytes_are_factor_bytes():
    """The QuanTA paper's serving pitch, pinned: a fold-free tenant's
    marginal resident cost is its factor rows — each bank group holds
    ``capacity + 1`` stacks of the factor leaves and NOTHING dense."""
    model, params, targets = _base()
    _, qset = attach(
        jax.random.PRNGKey(1), params,
        PeftConfig(method="quanta", scheme=None, n_axes=3, fold=False,
                   targets=targets),
    )
    store = AdapterStore(max_tenants=2)
    store.register("qa", qset)
    capacity = 3
    pool = AdapterPool.build(params, store, capacity=capacity)

    flat_base = flatten_paths(params)
    for path, (adapter, _spec) in store.get("qa").items():
        factor_bytes = sum(
            addressable_nbytes(leaf)
            for leaf in jax.tree_util.tree_leaves(adapter)
        )
        node = pool.tree
        for k in path.split("/"):
            node = node[k]
        group_bytes = sum(
            addressable_nbytes(leaf)
            for leaf in jax.tree_util.tree_leaves(node.groups)
        )
        # exactly (capacity + 1) factor stacks; a folded tenant would
        # add a dense (d_in, d_out) RebasedAdapter base per row
        assert group_bytes == (capacity + 1) * factor_bytes, path
        w0 = flat_base[path]
        assert group_bytes < (capacity + 1) * w0.nbytes, (
            "resident rows cost more than dense copies — fold-free "
            "QuanTA lost its factor-only advantage at " + path
        )
