#!/usr/bin/env python3
"""Bring-up smoke test: QuanTA fine-tuning and multi-tenant serving on TPU.

Drives the main path once, through the entry points a user calls, at the
full published width of minicpm-2b (40 layers, d_model 2304, 36 MHA
heads, d_ff 5760, vocab 122753).  Weights and data are random, made from
``--seed``; nothing is downloaded.

One chip (the default) runs two phases in one process:

* **train** — QuanTA (fold-free, scheme 16-12-12) on q/v with the Pallas
  flash-attention backend: a few donated ``make_train_step`` steps at
  B=4, S=1024 on one repeated batch.  Every loss must be finite, the loss
  must fall, and the step-0 loss must match the reference attention
  backend within ``LOSS_RTOL``.
* **serve** — ``ServeFrontend`` over a ``ServingEngine`` with an nf4
  frozen base, a paged KV pool and the Pallas attention / adapter
  kernels, multi-tenant through an ``AdapterPool`` smaller than its
  ``AdapterStore`` (the tenant just trained plus seeded fold-free QuanTA
  and LoRA tenants), then a short engine with an nf4 KV pool.  Each is
  compared with the same engine on the reference backends (the same nf4
  base through ``matmul_ref``, the same KV format): teacher-forced
  logits of one prompt and every request's first-token logits within
  ``LOGIT_TOL``, and every request's first greedy token identical — or,
  where the reference itself scores the two tokens within that
  tolerance, a near tie, printed as such.

``--chips 4`` runs only sharded serving on a 2x2 (data x model) mesh over
the first four chips, compared in the same process with a one-chip
engine on device 0 by the same checks.

Each phase reports compile and run seconds, peak device bytes and the
Mosaic kernels in its compiled programs.  The script exits non-zero when
JAX finds no TPU or a check fails; otherwise its last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python3 chip_smoke.py [--chips 1|4] [--seed N]
"""

from __future__ import annotations

import argparse
import gc
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, TRAIN_LR = 4, 1024, 4, 1e-4
N_REQUESTS, PROMPT_LEN, MAX_NEW = 8, 512, 32
KV_REQUESTS, KV_MAX_NEW = 4, 16
N_SLOTS, MAX_LEN, SEQ_BUCKET, BLOCK_SIZE, POOL_ROWS = 8, 640, 256, 16, 2
TF_TOKENS = 16              # teacher-forced continuation length
# Step-0 training loss, Pallas vs reference attention: bf16 activations,
# fp32 loss — a 1% relative gap is ~2.5 bf16 roundings of the loss.
LOSS_RTOL = 1e-2
# Teacher-forced logits, Pallas vs reference backends: the largest gap
# over the continuation, relative to the largest |reference logit|.
# Both run 40 bf16 layers whose kernels accumulate in different orders.
LOGIT_TOL = 5e-2
# XLA backend compilation (tracing and lowering are counted as run time:
# their monitoring events nest and would count twice)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Collects pass/fail lines; the run fails if any check failed."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> None:
        log(f"  [{'PASS' if ok else 'FAIL'}] {name}{': ' + detail if detail else ''}")
        if not ok:
            self.failed.append(name)


class Phase:
    """Wall time of a phase, split into XLA backend compilation (from
    JAX's monitoring events) and the rest."""

    def __init__(self, name: str):
        import jax

        self.name = name
        self.compile_s = 0.0
        self._jax = jax

    def _listen(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compile_s += duration

    def __enter__(self):
        self._jax.monitoring.register_event_duration_secs_listener(
            self._listen)
        self._t0 = time.perf_counter()
        log(f"== {self.name}")
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self._t0
        self._jax.monitoring.unregister_event_duration_listener(self._listen)
        peaks = [d.memory_stats().get("peak_bytes_in_use")
                 for d in self._jax.local_devices() if d.memory_stats()]
        log(f"  {self.name}: compile_s={self.compile_s:.1f} "
            f"run_s={wall - self.compile_s:.1f} "
            f"peak_bytes_in_use={peaks[0] if len(peaks) == 1 else peaks}")
        return False


def kernel_census(*compiled) -> dict:
    """Mosaic kernels per name in compiled programs (``tpu_custom_call``
    ops, named by the kernel's ``pallas_call`` name)."""
    counts: dict = {}
    for c in compiled:
        for line in c.as_text().splitlines():
            if "tpu_custom_call" not in line:
                continue
            # op_name ".../<kernel>/pallas_call", or ".../jvp(<kernel>)/..."
            m = re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line)
            name = m.group(1) if m else "unnamed"
            counts[name] = counts.get(name, 0) + 1
    return counts


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_phase(cfg, peft_cfg, check, *, batch, seq, steps, lr, seed):
    """QuanTA fine-tuning steps; returns ``{"params": frozen base,
    "peft": trained adapters}`` and the kernel census."""
    import jax
    import numpy as np

    from repro.core.peft import attach
    from repro.models import build_model
    from repro.optim import AdamW
    from repro.train import TrainState, make_train_step

    model = build_model(cfg.replace(attn_backend="pallas"))
    ref_model = build_model(cfg.replace(attn_backend="reference"))
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    base, peft = attach(jax.random.PRNGKey(seed + 1), params, peft_cfg)
    del params
    opt = AdamW(lr=lr)
    state = TrainState.create(base, peft, opt)
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
    data = {"tokens": jax.numpy.asarray(toks[:, :-1]),
            "labels": jax.numpy.asarray(toks[:, 1:])}

    ref_loss = float(jax.jit(ref_model.loss)(state.params, state.peft, data))
    step = jax.jit(make_train_step(model, opt), donate_argnums=(0,))
    compiled = step.lower(state, data).compile()
    mem = compiled.memory_analysis()
    log(f"  train step: argument_bytes={mem.argument_size_in_bytes} "
        f"output_bytes={mem.output_size_in_bytes} "
        f"alias_bytes={mem.alias_size_in_bytes} "
        f"temp_bytes={mem.temp_size_in_bytes}")
    losses = []
    for _ in range(steps):
        state, metrics = compiled(state, data)
        losses.append(float(metrics["loss"]))
    log(f"  losses={losses} reference step-0 loss={ref_loss}")
    check("train losses finite", all(np.isfinite(losses)))
    check("train loss falls on the repeated batch", losses[-1] < losses[0],
          f"{losses[0]:.6f} -> {losses[-1]:.6f}")
    gap = abs(losses[0] - ref_loss) / abs(ref_loss)
    check("step-0 loss matches reference attention", gap <= LOSS_RTOL,
          f"relative gap {gap:.2e} <= {LOSS_RTOL}")
    return {"params": state.params, "peft": state.peft}, \
        kernel_census(compiled)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def seeded_tenant(params, peft_cfg, seed: int, scale: float = 0.02):
    """A tenant with a nonzero update: attach (which starts at an exact
    zero delta), then perturb every factor with seeded noise."""
    import jax

    from repro.core.peft import attach

    _, aset = attach(jax.random.PRNGKey(seed), params, peft_cfg)
    leaves, treedef = jax.tree_util.tree_flatten(aset)
    keys = jax.random.split(jax.random.PRNGKey(seed + 7), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        leaf + scale * jax.random.normal(k, leaf.shape, leaf.dtype)
        for k, leaf in zip(keys, leaves)
    ])


def make_store(params, peft_cfg, trained, seed: int):
    """Registry: the trained tenant (if any), two seeded fold-free QuanTA
    tenants and two seeded LoRA tenants."""
    from repro.core.peft import PeftConfig
    from repro.serve.adapter_pool import AdapterStore

    lora = PeftConfig(method="lora", rank=8, targets=peft_cfg.targets)
    tenants = [] if trained is None else [("tuned", trained)]
    tenants += [(f"quanta{i}", seeded_tenant(params, peft_cfg, seed + 10 + i))
                for i in range(2)]
    tenants += [(f"lora{i}", seeded_tenant(params, lora, seed + 20 + i))
                for i in range(2)]
    store = AdapterStore(max_tenants=len(tenants))
    for name, aset in tenants:
        store.register(name, aset)
    return store


def make_requests(cfg, store, n: int, prompt_len: int, max_new: int,
                  seed: int):
    """Seeded prompts of prompt_len-31 .. prompt_len tokens (one prefill
    bucket), cycling over every tenant and the base model."""
    import numpy as np

    rng = np.random.default_rng(seed)
    tenants = list(store.names) + [None]
    reqs = []
    for i in range(n):
        length = int(rng.integers(prompt_len - 31, prompt_len + 1))
        prompt = rng.integers(0, cfg.vocab_size, length).tolist()
        reqs.append((prompt, max_new, tenants[i % len(tenants)]))
    return reqs


def serve_once(cfg, qparams, store, requests, *, attn, peft_backend,
               kv_quant=None, mesh=None, prefill_chunk=None,
               tf_cont=None):
    """Serve ``requests`` through a fresh engine + front end; returns the
    outputs, the teacher-forced logits of request 0, every request's
    first-token logits, the forced continuation and the engine."""
    import numpy as np

    from repro.models import build_model
    from repro.serve import Request, ServeFrontend, ServingEngine
    from repro.serve.adapter_pool import AdapterPool

    model = build_model(cfg.replace(attn_backend=attn,
                                    peft_backend=peft_backend,
                                    kv_quant=kv_quant))
    pool = AdapterPool.build(qparams, store, capacity=POOL_ROWS)
    engine = ServingEngine(
        model, qparams, adapters=pool, n_slots=N_SLOTS, max_len=MAX_LEN,
        seq_bucket=SEQ_BUCKET, cache="paged", block_size=BLOCK_SIZE,
        base_quant="nf4", kv_quant=kv_quant, mesh=mesh,
        prefill_chunk=prefill_chunk,
    )
    front = ServeFrontend(engine)
    streams = [
        front.submit(Request(uid=i, prompt=p, max_new_tokens=m), adapter=a)
        for i, (p, m, a) in enumerate(requests)
    ]
    front.drain()
    outputs = [s.result() for s in streams]
    prompt, _, tenant = requests[0]
    cont = outputs[0][:TF_TOKENS] if tf_cont is None else tf_cont
    logits = engine.teacher_forced_logits(prompt, cont, adapter=tenant)
    # each request's first-token logits (its last prompt token forced)
    firsts = np.stack([
        engine.teacher_forced_logits(p[:-1], p[-1:], adapter=a)[0]
        for p, _, a in requests
    ])
    log(f"  engine attn={attn} peft={peft_backend} kv={kv_quant or 'bf16'} "
        f"mesh={'x'.join(map(str, mesh.devices.shape)) if mesh else 'none'}: "
        f"{sum(map(len, outputs))} tokens, loads={pool.loads} "
        f"evictions={pool.evictions}, chained={front.stats['chained']}")
    return outputs, logits, firsts, cont, engine


def compare(check, label, got, ref):
    """The kernel engine against its reference twin.

    Random weights at full width leave some first-token logits nearly
    tied, and the two engines' bf16 sums differ in order, so a first
    greedy token may flip between them.  A flip passes only as a near
    tie: the reference scores the kernel engine's token within the logit
    tolerance of its own; every flip is printed."""
    import numpy as np

    (out, logits, firsts, _, _) = got
    (ref_out, ref_logits, ref_firsts, _, _) = ref
    scale = float(np.max(np.abs(ref_logits)))
    tol = LOGIT_TOL * scale
    gap = float(np.max(np.abs(logits - ref_logits)))
    check(f"{label}: teacher-forced logits within tolerance",
          bool(np.isfinite(logits).all()) and gap <= tol,
          f"max |diff| {gap:.4f} <= {LOGIT_TOL} x max |ref| {scale:.4f} "
          f"over {logits.shape[0]} positions")
    first_gap = float(np.max(np.abs(firsts - ref_firsts)))
    check(f"{label}: every request's first-token logits within tolerance",
          bool(np.isfinite(firsts).all()) and first_gap <= tol,
          f"max |diff| {first_gap:.4f} <= {tol:.4f} over "
          f"{firsts.shape[0]} requests")
    same = [a[0] == b[0] for a, b in zip(out, ref_out)]
    flips = [(i, a[0], b[0], float(r[b[0]] - r[a[0]]))
             for i, (a, b, r) in enumerate(zip(out, ref_out, ref_firsts))
             if a[0] != b[0]]
    check(f"{label}: first greedy token identical for every request, "
          f"or a near tie",
          all(margin <= tol for *_, margin in flips),
          f"{sum(same)}/{len(same)} identical"
          + "".join(f"; request {i}: {a} vs reference {b}, reference "
                    f"margin {m:.4f} <= {tol:.4f}" for i, a, b, m in flips))
    match = sum(x == y for a, b in zip(out, ref_out) for x, y in zip(a, b))
    total = sum(len(b) for b in ref_out)
    log(f"  {label}: {match}/{total} greedy tokens match the reference")


def engine_census(engine, prefill: bool = True) -> dict:
    """Kernels in the engine's compiled decode (and wave-prefill)
    programs, re-lowered with the exact call arguments: the persistent
    compilation cache returns the executables the run already built."""
    import jax.numpy as jnp
    import numpy as np

    toks = jnp.zeros((engine.n_slots, 1), jnp.int32)
    decode = engine._decode.lower(*engine._decode_args(toks)).compile()
    if not prefill:
        return kernel_census(decode)
    wave = np.zeros((engine.n_slots, engine._bucket(PROMPT_LEN)), np.int32)
    prefill = engine._prefill.lower(
        engine._weights, jnp.asarray(wave),
        jnp.ones((engine.n_slots,), jnp.int32),
        engine._ids_arg(np.zeros((engine.n_slots,), np.int32)),
        engine._bank_arg(),
    ).compile()
    return kernel_census(prefill, decode)


def serve_phase(cfg, trained, peft_cfg, check, seed):
    """``trained`` is the train phase's result; its bf16 base is dropped
    once quantized, so only the nf4 copy stays on the device."""
    from repro.core.quantize import quantize_params

    params = trained.pop("params")
    store = make_store(params, peft_cfg, trained["peft"], seed)
    qparams = quantize_params(params, "nf4",
                              block_size=cfg.quant_block_size)
    del params
    gc.collect()
    reqs = make_requests(cfg, store, N_REQUESTS, PROMPT_LEN, MAX_NEW, seed)
    got = serve_once(cfg, qparams, store, reqs, attn="pallas",
                     peft_backend="pallas")
    census = engine_census(got[-1])
    check("adapter pool swapped a tenant", got[-1].pool.evictions >= 1,
          f"evictions={got[-1].pool.evictions}")
    got = got[:-1] + (None,)
    gc.collect()
    ref = serve_once(cfg, qparams, store, reqs, attn="reference",
                     peft_backend="reference", tf_cont=got[3])
    compare(check, "serve bf16 KV", got, ref)
    del got, ref
    gc.collect()

    kv_reqs = make_requests(cfg, store, KV_REQUESTS, PROMPT_LEN, KV_MAX_NEW,
                            seed + 1)
    got = serve_once(cfg, qparams, store, kv_reqs, attn="pallas",
                     peft_backend="pallas", kv_quant="nf4")
    kv_census = engine_census(got[-1])
    got = got[:-1] + (None,)
    gc.collect()
    ref = serve_once(cfg, qparams, store, kv_reqs, attn="reference",
                     peft_backend="reference", kv_quant="nf4",
                     tf_cont=got[3])
    compare(check, "serve nf4 KV", got, ref)
    for k, v in kv_census.items():
        census[k] = census.get(k, 0) + v
    return census


def sharded_phase(cfg, peft_cfg, check, seed, devices):
    """Sharded serving on a 2x2 data x model mesh vs one chip."""
    import jax

    from repro.core.quantize import quantize_params
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model

    params = jax.jit(build_model(cfg).init)(jax.random.PRNGKey(seed))
    store = make_store(params, peft_cfg, None, seed)
    qparams = quantize_params(params, "nf4",
                              block_size=cfg.quant_block_size)
    del params
    gc.collect()
    reqs = make_requests(cfg, store, N_REQUESTS, PROMPT_LEN, MAX_NEW, seed)
    # The Pallas paged-decode kernel runs per data shard under shard_map;
    # the adapter and quantized-matmul kernels have no sharded form, so
    # the adapters and the nf4 base take the reference backend here.
    # Every prompt is longer than the prefill chunk, so admission runs
    # chunked (its staging buffer is model-sharded under the mesh).
    kw = dict(attn="pallas", peft_backend="reference",
              prefill_chunk=SEQ_BUCKET)
    one = serve_once(cfg, qparams, store, reqs, **kw)
    one = one[:-1] + (None,)
    gc.collect()
    mesh = make_host_mesh(2, 2, devices=devices)
    four = serve_once(cfg, qparams, store, reqs, mesh=mesh, tf_cont=one[3],
                      **kw)
    for d in devices:
        stats = d.memory_stats() or {}
        log(f"  device {d.id}: bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    census = engine_census(four[-1], prefill=False)
    compare(check, "sharded 2x2 vs one chip", four, one)
    return census


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); there is no CPU fallback")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} device(s)")

    from repro.configs.minicpm_2b import FULL, PEFT
    from repro.kernels.dispatch import resolve_interpret
    from repro.launch.compile_cache import enable_compile_cache

    log(f"devices: {devices[:args.chips]}")
    log(f"compile cache: {enable_compile_cache()}")
    if resolve_interpret(None):
        sys.exit("chip_smoke: Pallas kernels would run in interpret mode")
    peft_cfg = PEFT.replace(fold=False)
    check = Checks()

    if args.chips == 4:
        with Phase("serve 2x2 mesh"):
            census = sharded_phase(FULL, peft_cfg, check, args.seed,
                                   devices[:4])
        log(f"  kernels: {census}")
        check("paged decode ran as a Mosaic kernel under the mesh",
              census.get("paged_decode", 0) > 0)
    else:
        with Phase("train"):
            trained, train_k = train_phase(
                FULL, peft_cfg, check, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                steps=TRAIN_STEPS, lr=TRAIN_LR, seed=args.seed)
        log(f"  kernels: {train_k}")
        check("train step runs flash attention as a Mosaic kernel",
              train_k.get("flash_attention", 0) > 0)
        with Phase("serve"):
            serve_k = serve_phase(FULL, trained, peft_cfg, check,
                                  args.seed)
        log(f"  kernels: {serve_k}")
        for name in ("flash_attention", "paged_decode", "paged_decode_nf4",
                     "quantized_matmul", "banked_lora_delta"):
            check(f"serving ran {name} as a Mosaic kernel",
                  serve_k.get(name, 0) > 0)

    if check.failed:
        log(f"FAILED: {check.failed}")
        return 1
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
