"""Serving engine behaviour + pipeline-parallel numerical equality."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke
from repro.models import build_model
from repro.serve import Request, ServingEngine


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-1.3b"])
def test_engine_matches_reference_greedy(arch):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def reference_greedy(prompt, n_new):
        toks = list(prompt)
        for _ in range(n_new):
            logits, _ = model.forward(
                params, {"tokens": jnp.asarray([toks])}, None
            )
            toks.append(int(jnp.argmax(logits[0, -1, : cfg.vocab_size])))
        return toks[len(prompt):]

    engine = ServingEngine(model, params, n_slots=3, max_len=64)
    prompts = [[5, 9, 13], [40, 2], [7, 7, 7, 7], [100, 101]]
    reqs = [Request(uid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    for r in reqs:
        assert r.done
        ref = reference_greedy(r.prompt, 6)
        assert r.output[:6] == ref, (r.uid, r.output, ref)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "recurrentgemma-2b",
                                  "mamba2-1.3b"])
def test_admission_paths_equivalent(arch):
    """Prefill-wave admission must produce IDENTICAL greedy outputs to
    decode-replay admission: mixed prompt lengths inside a wave (padding
    must be exact, not approximate) and more requests than slots (slot
    churn across multiple waves, so freed-slot reset + scatter interact)."""
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [[5, 9, 13], [40, 2], [7, 7, 7, 7, 21, 3, 99], [100, 101],
               [1], [13, 5, 88, 4, 2], [250, 3, 17], [9] * 11]
    outs = {}
    for mode in ("replay", "prefill"):
        engine = ServingEngine(model, params, n_slots=3, max_len=64,
                               admission=mode)
        reqs = [Request(uid=i, prompt=list(p), max_new_tokens=6)
                for i, p in enumerate(prompts)]
        for r in reqs:
            engine.submit(r)
        engine.run()
        assert all(r.done for r in reqs)
        outs[mode] = [r.output for r in reqs]
    assert outs["prefill"] == outs["replay"]


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "recurrentgemma-2b",
                                  "mamba2-1.3b"])
def test_prefill_admission_is_o1_dispatches(arch):
    """A prefill wave admits in ONE jitted call regardless of prompt length
    (replay admission needs max_prompt_len decode dispatches).  Asserted
    through the sanitizer's compile guard: each entry point's actual
    compile count stays within its documented bound."""
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, n_slots=2, max_len=64,
                           admission="prefill")
    for i in range(2):
        engine.submit(Request(uid=i, prompt=[3 + i] * 20, max_new_tokens=1))
    engine.step()
    assert engine.stats["prefill_calls"] == 1
    assert engine.stats["decode_calls"] == 1   # the tick's fused decode
    counts = engine.compile_guard.counts()
    assert counts["prefill"] == 1              # one compile for the wave
    assert counts["decode"] == 1               # the single fused decode
    engine.compile_guard.assert_ok()


def test_paged_decode_compile_guard():
    """Paged-path O(1) compilation: slot churn, block-table growth, and
    preemption-free decode across many ticks never retrace — the guard's
    documented bounds hold with the actual jit cache sizes."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, n_slots=2, max_len=64,
                           admission="prefill", cache="paged",
                           block_size=8, n_blocks=32)
    # churn: mixed prompt lengths, more requests than slots, enough new
    # tokens that slots cross block boundaries (alloc-on-append)
    prompts = [[5, 9, 13], [7] * 21, [40, 2], [9] * 11, [1], [3, 3, 3]]
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=10)
            for i, p in enumerate(prompts)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert all(r.done for r in reqs)
    counts = engine.compile_guard.counts()
    assert counts["decode"] == 1               # block tables are traced args
    assert counts["prefill"] <= engine.compilation_bounds()["prefill"]
    engine.compile_guard.assert_ok()


def test_engine_eos_and_backfill():
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, n_slots=1, max_len=32)
    # 3 requests through 1 slot forces queue backfill
    reqs = [Request(uid=i, prompt=[i + 1, i + 2], max_new_tokens=4)
            for i in range(3)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert all(r.done and len(r.output) >= 4 for r in reqs)


_PIPE = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.train.pipeline import pipeline_apply, bubble_fraction

    mesh = make_mesh((4,), ("stage",))
    L, M, MB, D = 8, 6, 2, 16
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (L, D, D)) / np.sqrt(D)
    b = jax.random.normal(jax.random.PRNGKey(1), (L, D)) * 0.1
    params = {"w": w, "b": b}

    def layer_fn(lp, h):
        return jnp.tanh(h @ lp["w"] + lp["b"])

    x = jax.random.normal(jax.random.PRNGKey(2), (M, MB, D))
    out = pipeline_apply(layer_fn, params, x, mesh=mesh)

    # sequential reference
    def seq(h):
        for i in range(L):
            h = layer_fn({"w": w[i], "b": b[i]}, h)
        return h
    ref = jax.vmap(seq)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    # gradients flow through ppermute (GPipe backward)
    def loss(params):
        return jnp.sum(pipeline_apply(layer_fn, params, x, mesh=mesh) ** 2)
    g = jax.grad(loss)(params)
    def loss_ref(params):
        def seq2(h):
            def body(h, lp):
                return layer_fn(lp, h), None
            h, _ = jax.lax.scan(body, h, params)
            return h
        return jnp.sum(jax.vmap(seq2)(x) ** 2)
    g_ref = jax.grad(loss_ref)(params)
    for a, b_ in zip(jax.tree_util.tree_leaves(g),
                     jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4)
    assert abs(bubble_fraction(6, 4) - 3/9) < 1e-9
    print("PIPELINE_OK")
""")


def test_pipeline_parallel_subprocess_4_stages():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"       # virtual devices; never the chip
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _PIPE], env=env, capture_output=True,
        text=True, timeout=420,
    )
    assert "PIPELINE_OK" in out.stdout, out.stdout + out.stderr
