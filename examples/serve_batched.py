"""Batched serving with merged QuanTA weights (zero inference overhead).

    PYTHONPATH=src python examples/serve_batched.py

Fine-tunes briefly, merges the adapter into the weights, then serves a
wave of prompts through the continuous-batching engine — and verifies the
merged deployment matches the adapter-attached model token-for-token.

Admission runs on the prefill-wave fast path: each wave of prompts is
right-padded, prefilled in ONE jitted call, and its cache stripes are
scattered into free slots (``admission="prefill"``, the default for
token-frontend models).

The merged engine serves through the PAGED KV cache (``cache="paged"``:
block-pool cache, block tables allocated at admission and freed on
completion — see ``repro.serve.paging``) while the adapter engine keeps
dense slot stripes, so the token-for-token assert below also exercises
paged == dense equivalence end to end.

The merged engine is also MESH-AWARE (``mesh=make_host_mesh(...)``):
weights shard over the `model` axis (decode TP rules), cache slots and
paged block-pool arenas over `data`, and every jitted serving call
carries explicit in/out shardings.  This example builds a mesh over
whatever devices exist (1x1 on a laptop — same code, trivial layout; run
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to see a
real 2x`data` . 4x`model` layout, which generates the SAME tokens —
that equivalence is CI-gated in tests/test_sharded_serve.py).

Finally, MULTI-TENANT serving: the trained QuanTA tenant and a second
LoRA tenant are packed into an ``AdapterBank`` over the one shared base
model, and a single engine serves a wave that mixes both tenants with
base-model requests — ``submit(req, adapter="quanta")`` picks the
adapter per request, and the mixed batch stays one fused decode program
(tenant outputs match the dedicated engines above token for token).

``--base-quant nf4|int8`` stores the merged frozen weights in the
blockwise quantized format and serves them through the fused
dequant-matmul kernels (``ServingEngine(base_quant=...)``).
Quantization perturbs the weights, so the fp adapter-attached engine is
no longer the token-for-token reference — the paged quantized engine is
instead asserted identical to a dense-cache engine over the SAME
quantized base, and the stats line shows the ``param_bytes`` cut."""

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_smoke
from repro.launch.mesh import make_host_mesh
from repro.core.bank import AdapterBank
from repro.core.peft import PeftConfig, attach, merge_all
from repro.data import SyntheticSeq2Task
from repro.models import build_model
from repro.optim import AdamW
from repro.serve import Request, ServingEngine
from repro.train import TrainState, make_train_step


def main(base_quant=None):
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    base, peft = attach(jax.random.PRNGKey(1), params,
                        PeftConfig(method="quanta", n_axes=3, scheme=None))
    opt = AdamW(lr=5e-3)
    state = TrainState.create(base, peft, opt)
    step = jax.jit(make_train_step(model, opt))
    data = SyntheticSeq2Task(vocab_size=cfg.vocab_size, seq_len=24,
                             global_batch=16, task_rank=8)
    for i in range(20):
        state, _ = step(state, {k: jnp.asarray(v)
                                for k, v in data.batch(i).items()})

    merged = merge_all(state.params, state.peft)

    # serve sharded when devices allow: slots + block arenas over `data`,
    # weights + KV heads/head_dim over `model` (1x1 mesh on one device)
    n_dev = jax.device_count()
    mesh = make_host_mesh(2, 4) if n_dev >= 8 else make_host_mesh(1, 1)
    engine = ServingEngine(model, merged, n_slots=4, max_len=64,
                           admission="prefill", cache="paged",
                           block_size=16, mesh=mesh, base_quant=base_quant)
    if base_quant is None:
        ref_name = "adapter"
        engine_ref = ServingEngine(model, state.params, state.peft,
                                   n_slots=4, max_len=64,
                                   admission="prefill")
    else:
        # the quantized base no longer equals merged fp weights, so the
        # reference is a dense-cache engine over the same quantized base
        ref_name = f"{base_quant}-dense"
        engine_ref = ServingEngine(model, merged, n_slots=4, max_len=64,
                                   admission="prefill",
                                   base_quant=base_quant)
    prompts = [[3, 141, 59], [26, 5], [35, 89, 79, 32], [38, 46], [2, 7, 18]]
    reqs_m = [Request(uid=i, prompt=p, max_new_tokens=8)
              for i, p in enumerate(prompts)]
    reqs_a = [Request(uid=i, prompt=list(p), max_new_tokens=8)
              for i, p in enumerate(prompts)]
    for rm, ra in zip(reqs_m, reqs_a):
        engine.submit(rm)
        engine_ref.submit(ra)
    engine.run()
    engine_ref.run()
    for rm, ra in zip(reqs_m, reqs_a):
        status = "==" if rm.output == ra.output else "!="
        print(f"req {rm.uid}: merged {rm.output} {status} "
              f"{ref_name} {ra.output}")
        assert rm.output == ra.output, \
            f"merged serving must match {ref_name}"
    print(f"all merged-weight generations match the {ref_name} engine")
    print(f"paged engine stats: {engine.stats} "
          f"(prefill admission: O(1) jitted calls per wave; blocks freed "
          f"on completion)")
    if base_quant is not None:
        fp = ServingEngine(model, merged, n_slots=4, max_len=64)
        print(f"base_quant={base_quant}: param_bytes "
              f"{fp.stats['param_bytes']} fp -> "
              f"{engine.stats['param_bytes']} quantized "
              f"({fp.stats['param_bytes'] / engine.stats['param_bytes']:.2f}x"
              f" smaller weight stream)")
    print(f"mesh: {dict(mesh.shape)} over {n_dev} device(s); cache bytes "
          f"are per-host (addressable) memory")

    # ---- multi-tenant: one engine, per-request adapter selection -------
    # a second tenant (LoRA) trained against the SAME base model; the
    # QuanTA tenant enters the bank as the (folded_params, set) pair
    # attach/TrainState carry, so both share `params` at serving time.
    _, lora = attach(jax.random.PRNGKey(7), params,
                     PeftConfig(method="lora", rank=4))
    lora = jax.tree_util.tree_map(
        lambda x: x + 0.1 * jax.random.normal(
            jax.random.PRNGKey(8), x.shape, x.dtype
        ),
        lora,
    )
    bank = AdapterBank.build(
        params, {"quanta": (state.params, state.peft), "lora": lora}
    )
    multi = ServingEngine(model, params, adapters=bank, n_slots=4,
                          max_len=64)
    tenants = ["quanta", "lora", None, "quanta", "lora"]
    reqs_b = [Request(uid=i, prompt=list(p), max_new_tokens=8, adapter=t)
              for i, (p, t) in enumerate(zip(prompts, tenants))]
    for r in reqs_b:
        multi.submit(r)
    multi.run()
    for r, ra in zip(reqs_b, reqs_a):
        tag = r.adapter or "base"
        print(f"req {r.uid} [{tag:6s}]: {r.output}")
        # (with --base-quant reqs_a came from the quantized reference,
        # while the bank serves the fp base — no cross-format assert)
        if r.adapter == "quanta" and base_quant is None:
            assert r.output == ra.output, \
                "banked tenant must match its dedicated engine"
    print(f"one engine, {bank.num_tenants} tenants + base in one decode "
          f"batch ({multi.stats['adapter_bytes']} adapter bytes)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--base-quant", default=None, choices=("nf4", "int8"),
                    help="store the merged frozen weights blockwise "
                         "quantized and serve through the fused "
                         "dequant-matmul kernels")
    main(base_quant=ap.parse_args().base_quant)
