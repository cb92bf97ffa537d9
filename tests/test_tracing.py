"""Tracing of the serving engine and the QuanTA path, read back on the CPU.

One tiny ``ServeFrontend`` run is recorded with the JAX profiler: a
paged KV pool small enough to preempt, a prompt long enough for chunked
prefill, and an adapter pool of one row per structure serving two LoRA
tenants and a fold-free QuanTA tenant (so tenants load and evict).  The
profile must hold every host span of the engine, nested inside the front
end's ``serve.tick`` and carrying each request's ``uid`` or ``tenant``;
the pool's load counter must agree with it; every program
must run under a stable name; and the lowered decode and train steps
must carry the ``adapter`` and ``quanta`` name scopes in their op
metadata.
"""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_peft, get_smoke
from repro.core.peft import PeftConfig, attach
from repro.models import build_model
from repro.serve import Request, ServeFrontend, ServingEngine
from repro.serve.adapter_pool import AdapterPool, AdapterStore

ARCH = "qwen2-0.5b"
SPANS = {
    "serve.tick", "serve.chunk", "serve.admit", "serve.prefill",
    "serve.insert", "serve.grow", "serve.preempt", "serve.dispatch",
    "serve.land", "serve.wait", "serve.stream", "serve.gauges",
    "pool.load", "pool.evict",
}
PROMPTS = [[3] * 12, [5] * 6, [7] * 6, [9] * 6, [11] * 5]
TENANTS = ["l0", "l1", "q0", None, "l1"]


def _base():
    cfg = get_smoke(ARCH)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params, get_peft(ARCH).targets


def _store(params, targets):
    store = AdapterStore(max_tenants=4)
    for name, key in (("l0", 1), ("l1", 2)):
        store.register(name, attach(
            jax.random.PRNGKey(key), params,
            PeftConfig(method="lora", rank=4, targets=targets))[1])
    store.register("q0", attach(
        jax.random.PRNGKey(9), params,
        PeftConfig(method="quanta", scheme=None, n_axes=3, noise_scale=0.3,
                   fold=False, targets=targets))[1])
    return store


def _host_spans(trace_dir):
    """``[(name, start_ns, end_ns, stats)]`` of the program's host spans."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    pd = ProfileData.from_file(path[-1])
    spans, modules = [], set()
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if isinstance(stats.get("hlo_module"), str):
                    modules.add(stats["hlo_module"])
                if e.name.startswith(("serve.", "pool.")):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, stats))
    return spans, modules


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    model, params, targets = _base()
    pool = AdapterPool.build(params, _store(params, targets), capacity=1)
    engine = ServingEngine(model, params, adapters=pool, n_slots=3,
                           max_len=64, cache="paged", block_size=4,
                           n_blocks=8, prefill_chunk=8)
    front = ServeFrontend(engine)
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=8)
            for i, p in enumerate(PROMPTS)]
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    # host annotations and XLA events only: the Python tracer's per-call
    # events would multiply the profile's size for nothing read here
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        for r, tenant in zip(reqs, TENANTS):
            front.submit(r, adapter=tenant)
        front.drain()
    finally:
        jax.profiler.stop_trace()
    assert all(r.done for r in reqs)
    # the run reached every layer the spans cover
    assert engine.stats["preemptions"] > 0
    assert engine.stats["chunk_calls"] > 1
    assert pool.loads > pool.evictions > 0
    spans, modules = _host_spans(trace_dir)
    return engine, front, pool, spans, modules


def test_every_span_is_recorded(traced):
    _, _, _, spans, _ = traced
    assert SPANS <= {name for name, *_ in spans}


def test_spans_nest_inside_ticks(traced):
    _, front, _, spans, _ = traced
    ticks = [(s, e) for name, s, e, _ in spans if name == "serve.tick"]
    assert len(ticks) == front.stats["ticks"]
    for name, s, e, _ in spans:
        if name != "serve.tick":
            assert any(ts <= s and e <= te for ts, te in ticks), name

    def inside(child, parents):
        outer = [(s, e) for n, s, e, _ in spans if n in parents]
        return all(any(ps <= s and e <= pe for ps, pe in outer)
                   for n, s, e, _ in spans if n == child)

    assert inside("serve.wait", {"serve.land", "serve.prefill",
                                 "serve.chunk", "pool.load"})
    assert inside("serve.insert", {"serve.prefill", "serve.chunk"})
    assert inside("serve.preempt", {"serve.grow"})
    assert inside("pool.load", {"serve.admit"})
    assert inside("serve.prefill", {"serve.admit"})


def test_spans_carry_request_metadata(traced):
    _, _, _, spans, _ = traced
    uids = set(range(len(PROMPTS)))
    for name, _, _, stats in spans:
        if name in ("serve.chunk", "serve.preempt"):
            assert stats["uid"] in uids
        if name == "serve.chunk":
            assert stats["pos"] % 8 == 0
        if name in ("pool.load", "pool.evict"):
            assert stats["tenant"] in {"l0", "l1", "q0"}
        if name == "serve.tick":
            assert {"tick", "live"} <= set(stats)
        if name == "serve.dispatch":
            assert stats["chained"] in (0, 1)
    chunked = {s["uid"] for n, _, _, s in spans if n == "serve.chunk"}
    assert 0 in chunked                  # the 12-token prompt


def test_counters_match_the_spans(traced):
    engine, _, pool, spans, _ = traced
    # one span per load, and the counter's clock pair sits inside each
    loads = [e - s for n, s, e, _ in spans if n == "pool.load"]
    assert len(loads) == pool.loads
    assert 0 < pool.load_s <= sum(loads) * 1e-9
    assert engine.stats["adapter_load_s"] == pool.load_s
    assert "adapter_swap_p50" not in engine.stats


def test_programs_have_stable_names(traced):
    _, _, _, _, modules = traced
    assert {"jit_decode_fn", "jit_prefill_fn", "jit_chunk_fn",
            "jit_insert_fn", "jit_sample_fn", "jit_swap_fn",
            "jit_merge_tokens_fn"} <= modules
    assert not [m for m in modules if "lambda" in m or m == "jit__insert"]


def test_two_pools_count_their_compiles_apart():
    model, params, targets = _base()
    store = _store(params, targets)
    a = AdapterPool.build(params, store, capacity=1)
    b = AdapterPool.build(params, store, capacity=1)
    assert a.load("l0") and a.load("l1")
    assert a.swap_fn._cache_size() == 1 and b.swap_fn._cache_size() == 0
    assert b.load("l0")
    assert a.swap_fn._cache_size() == b.swap_fn._cache_size() == 1


def _op_names(lowered):
    """The name-stack locations of a lowered program's ops: ``scope/op``
    paths, not the source files or the Python frames (whose names may
    mention quanta too)."""
    locs = re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))
    return [n for n in locs if "/" in n and not n.endswith(".py")]


def test_decode_program_carries_adapter_and_quanta_scopes():
    model, params, targets = _base()
    pool = AdapterPool.build(params, _store(params, targets), capacity=2)
    engine = ServingEngine(model, params, adapters=pool, n_slots=2,
                           max_len=32)
    toks = jnp.zeros((2, 1), jnp.int32)
    names = _op_names(engine._decode.lower(*engine._decode_args(toks)))
    assert any(n.startswith("adapter/vmap(quanta)/") for n in names)
    # the QuanTA chain only runs inside the bank's adapter scope, and the
    # shared base matmul outside it
    assert all("adapter/" in n for n in names if "quanta" in n)
    assert any(n.endswith("dot_general") and "adapter" not in n
               for n in names)


def test_train_step_carries_quanta_scope_through_the_backward():
    from repro.optim import AdamW
    from repro.train import TrainState, make_train_step

    model, params, targets = _base()
    base, peft = attach(
        jax.random.PRNGKey(1), params,
        PeftConfig(method="quanta", scheme=None, n_axes=3, fold=False,
                   targets=targets))
    opt = AdamW(lr=1e-3)
    state = TrainState.create(base, peft, opt)
    tokens = np.zeros((2, 16), np.int32)
    batch = {"tokens": tokens, "labels": tokens}
    step = jax.jit(make_train_step(model, opt))
    names = _op_names(step.lower(state, batch))
    assert any(n.startswith("jit(train_step)/transpose(jvp())")
               for n in names)
    quanta = [n for n in names if "quanta/" in n]
    assert any(n.endswith("dot_general") for n in quanta)
    # the backward's own ops (transposes, cotangent sums) carry it too
    assert any(n.endswith(("transpose", "add_any")) for n in quanta)
