"""Serving-engine benchmark: admission modes and cache layouts.

For each model family (transformer / griffin / mamba2 smoke configs),
measures on a steady engine (after a warmup batch that pays all jit
compiles):

* **admission latency** — wall time of the engine tick that admits a full
  wave of ``PROMPT_LEN``-token prompts (the paper's zero-overhead serving
  claim is only visible if admission does not replay prompts
  token-by-token),
* **jitted dispatches per wave** — prefill admission must issue O(1)
  model calls per wave vs O(max_prompt_len) for replay (asserted here),
* **steady-state tokens/sec** — generated tokens over the full drain,
* **dense vs paged cache** — same prefill admission through the block-
  pool cache (``cache="paged"``): rows report the engine's cache-memory
  gauges (``peak bytes allocated``, ``peak blocks``, peak utilization)
  next to the dense stripes' constant footprint, and outputs are asserted
  token-for-token identical to dense,
* **quantized frozen base** (``base_quant="nf4"``) — the same dense and
  paged engines over the blockwise-NF4 base served through the fused
  dequant-matmul path: rows report tokens/sec plus the per-host
  ``param_bytes`` gauge next to the fp engine's, and the two quantized
  engines are asserted token-for-token identical,
* **quantized KV cache** (``kv_quant="nf4" | "int8"``, transformer and
  griffin — mamba2 has no pageable leaves) — the paged engine over
  packed-code + per-block-scale pools, asserted token-for-token
  identical to the dense engine on the same model (whose stripes hold
  fake-quantized values through the same ``core.quantize`` helpers):
  rows report tokens/sec plus the per-block pool bytes next to the fp
  paged engine's (the KV-stream cut),
* **sharded engine** (``--sharded``) — the same dense/paged engines on a
  2x`data` . 4x`model` mesh over 8 virtual CPU devices
  (``ServingEngine(mesh=...)``): rows report per-host cache bytes and
  outputs are asserted token-for-token identical to the single-device
  engine.  ``--sharded`` must be on the command line at process start —
  it forces ``--xla_force_host_platform_device_count=8`` before jax
  initializes,
* **adapter serving modes** — the four ways the engine serves PEFT state,
  same wave each time, token-for-token asserts between them:
  ``single`` (one QuanTA ``AdapterSet`` for every request,
  ``peft_backend="reference"``), ``pallas`` (same set through the fused
  QuanTA kernels, parity-asserted against ``single``), ``bank8`` (an
  8-tenant ``AdapterBank`` — the QuanTA set + 7 LoRA tenants — with a
  2x``N_SLOTS`` wave round-robined across ALL 8 tenants; the QuanTA
  tenant's and a LoRA tenant's requests are asserted identical to their
  dedicated single-tenant engines), and ``merged`` (``merge_all``
  zero-overhead deployment, asserted identical to ``single``).  Rows
  report tokens/sec plus the ``adapter_bytes`` / ``adapter_tenants``
  gauges next to the cache bytes,
* **hot-swap adapter churn** — a 64-tenant ``AdapterStore`` registry
  served through an 8-row ``AdapterPool`` resident bank: two waves
  round-robined over 16 distinct tenants force load/evict churn
  mid-run.  The row reports steady tokens/sec, the donated row-scatter
  swap latency (p50), the ``adapter_bytes_resident`` (capacity-fixed
  device bank) vs ``adapter_bytes_registry`` (host factors, grows with
  tenants) split, and the load/eviction counts; two churned tenants are
  asserted token-for-token against dedicated cold engines and the
  compile guard asserts the serving jits never recompiled across swaps.

* **open-loop front end** (``--open-loop``) — a seeded Poisson arrival
  schedule (two SLA classes, ``interactive``/``batch``) streamed
  through ``ServeFrontend`` on the REAL clock, dense and paged: rows
  report exact (raw-timestamp) TTFT p50/p99, per-token latency (TPOT)
  p50/p99, SLO attainment, and goodput (tokens/sec from requests that
  met their class's TTFT target) per latency class, plus the engine's
  tick-latency / TTFT histogram gauges, peak queue depths, and the
  double-buffer chain rate.  Streamed outputs are asserted
  token-for-token identical to the closed-loop engine on the same
  requests (the open-loop CI gate), and at least one chained
  (double-buffered) dispatch must have engaged.  ``--record PATH``
  additionally writes the metrics as JSON — the committed baseline
  lives at ``benchmarks/results/serving/openloop_smoke.json``.

CSV rows via ``benchmarks.common.csv_row``:
``serve_admission_<family>_<mode>, <us per admitted wave>, <derived>``,
``serve_cache_<family>_<dense|paged>, <us per admitted wave>, <derived>``,
``serve_quant_<family>_nf4_<dense|paged>, ...``,
``serve_kvquant_<family>_<nf4|int8>, ...``,
``serve_adapters_<family>_<single|pallas|bank8|merged>, ...``,
``serve_churn_<family>_pool8, <us per token>, <derived>``,
``serve_sharded_<family>_<dense|paged>, ...`` and
``serve_openloop_<family>_<dense|paged>_<class|engine>, <ttft p50 us>,
<derived>``.

``--smoke`` (CI gate) runs the transformer family only, with the paged
vs dense, quantized-base (nf4 dense vs paged), quantized-KV (nf4 and
int8 paged vs dense fake-quantized), multi-adapter (bank8 / pallas /
merged vs single), hot-swap churn (pool vs cold engines, zero
recompiles), open-loop vs closed-loop (``--open-loop``), and —
with ``--sharded`` — sharded vs single-device equivalence assertions
intact.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

# --sharded needs 8 virtual devices, and the device count can only be set
# before jax first initializes — so peek at argv ahead of the jax import.
if "--sharded" in sys.argv and "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row
from repro.configs import get_peft, get_smoke
from repro.core.bank import AdapterBank
from repro.core.peft import PeftConfig, attach, merge_all
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.serve import (
    DEFAULT_CLASSES, AdapterPool, AdapterStore, Request, ServeFrontend,
    ServingEngine, poisson_arrivals,
)

FAMILIES = {
    "transformer": "qwen2-0.5b",
    "griffin": "recurrentgemma-2b",
    "mamba2": "mamba2-1.3b",
}
N_SLOTS = 4
MAX_LEN = 128
PROMPT_LEN = 48
MAX_NEW = 16
BLOCK_SIZE = 16


def _prompts(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(1, 255, (PROMPT_LEN,)).tolist() for _ in range(n)
    ]


def _run_wave(engine, prompts, uid0=0):
    reqs = [
        Request(uid=uid0 + i, prompt=list(p), max_new_tokens=MAX_NEW)
        for i, p in enumerate(prompts)
    ]
    for r in reqs:
        engine.submit(r)
    # first tick = admission (+ one fused decode step)
    calls0 = dict(
        (k, engine.stats[k]) for k in ("prefill_calls", "decode_calls")
    )
    t0 = time.perf_counter()
    engine.step()
    admit_s = time.perf_counter() - t0
    admit_calls = (
        engine.stats["prefill_calls"] - calls0["prefill_calls"]
        + engine.stats["decode_calls"] - calls0["decode_calls"]
        - 1                                   # the tick's own decode step
    )
    t0 = time.perf_counter()
    engine.run()
    drain_s = time.perf_counter() - t0
    toks = sum(len(r.output) for r in reqs)
    outs = [r.output for r in reqs]
    return admit_s, admit_calls, toks, admit_s + drain_s, outs


def bench_family(family: str, arch: str, sharded: bool = False):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rows = []
    for mode in ("replay", "prefill"):
        engine = ServingEngine(
            model, params, n_slots=N_SLOTS, max_len=MAX_LEN, admission=mode
        )
        _run_wave(engine, _prompts(N_SLOTS, seed=1))          # warmup/compile
        admit_s, admit_calls, toks, total_s, _ = _run_wave(
            engine, _prompts(N_SLOTS, seed=2), uid0=100
        )
        if mode == "prefill":
            assert admit_calls == 1, admit_calls   # O(1) dispatches per wave
        else:
            assert admit_calls == PROMPT_LEN, admit_calls  # O(prompt) replay
        rows.append(csv_row(
            f"serve_admission_{family}_{mode}",
            admit_s * 1e6,
            f"calls/wave={admit_calls} toks/s={toks / total_s:.0f} "
            f"wave={N_SLOTS}x{PROMPT_LEN}tok",
        ))
    cache_rows, dense_outs = bench_cache_modes(family, model, params)
    rows.extend(cache_rows)
    rows.extend(bench_quantized_base(family, model, params))
    if family != "mamba2":       # no pageable leaves: kv_quant is a no-op
        rows.extend(bench_kvquant_cache(family, cfg, params))
    rows.extend(bench_adapter_modes(family, arch, cfg, model, params))
    rows.extend(bench_adapter_churn(family, arch, model, params))
    if sharded:
        rows.extend(bench_sharded(family, model, params, dense_outs))
    return rows


def bench_cache_modes(family: str, model, params):
    """Dense vs paged cache under prefill admission: latency + the
    cache-memory gauges, with a token-for-token equivalence assert."""
    rows, outs = [], {}
    for mode in ("dense", "paged"):
        engine = ServingEngine(
            model, params, n_slots=N_SLOTS, max_len=MAX_LEN,
            admission="prefill", cache=mode, block_size=BLOCK_SIZE,
        )
        _run_wave(engine, _prompts(N_SLOTS, seed=1))          # warmup/compile
        admit_s, _calls, toks, total_s, outs[mode] = _run_wave(
            engine, _prompts(N_SLOTS, seed=2), uid0=100
        )
        s = engine.stats
        if mode == "paged" and s["blocks_total"]:
            mem = (
                f"peak_blocks={s['peak_blocks_in_use']}/{s['blocks_total']} "
                f"peak_util={s['peak_block_utilization']:.2f}"
            )
        else:
            mem = f"cache_bytes={s['cache_bytes_allocated']}"
        rows.append(csv_row(
            f"serve_cache_{family}_{mode}",
            admit_s * 1e6,
            f"toks/s={toks / total_s:.0f} {mem}",
        ))
    assert outs["paged"] == outs["dense"], (
        f"{family}: paged cache diverged from dense"
    )
    return rows, outs["dense"]


def bench_quantized_base(family: str, model, params):
    """fp vs blockwise-NF4 frozen base under prefill admission: tokens/sec
    plus the per-host ``param_bytes`` gauge next to the fp engine's, with
    a dense-vs-paged token-for-token equivalence assert on the quantized
    engine (the quantized-base CI gate)."""
    fp_bytes = ServingEngine(
        model, params, n_slots=N_SLOTS, max_len=MAX_LEN
    ).stats["param_bytes"]
    rows, outs = [], {}
    for mode in ("dense", "paged"):
        engine = ServingEngine(
            model, params, n_slots=N_SLOTS, max_len=MAX_LEN,
            admission="prefill", cache=mode, block_size=BLOCK_SIZE,
            base_quant="nf4",
        )
        _run_wave(engine, _prompts(N_SLOTS, seed=1))          # warmup/compile
        admit_s, _calls, toks, total_s, outs[mode] = _run_wave(
            engine, _prompts(N_SLOTS, seed=2), uid0=100
        )
        s = engine.stats
        rows.append(csv_row(
            f"serve_quant_{family}_nf4_{mode}",
            admit_s * 1e6,
            f"toks/s={toks / total_s:.0f} param_bytes={s['param_bytes']} "
            f"fp_param_bytes={fp_bytes} "
            f"cut={fp_bytes / max(s['param_bytes'], 1):.2f}x",
        ))
    assert outs["paged"] == outs["dense"], (
        f"{family}: quantized paged cache diverged from dense"
    )
    return rows


def bench_kvquant_cache(family: str, cfg, params):
    """Quantized KV-cache blocks (``kv_quant="nf4" | "int8"``) under
    prefill admission: the paged pool stores packed codes + per-block
    scales, and its outputs must be token-for-token IDENTICAL to the
    dense engine over the same model (whose stripes hold fake-quantized
    values through the same ``core.quantize`` helpers) — the
    quantized-KV CI gate.  Rows report tokens/sec plus the per-block
    pool bytes next to the fp paged engine's (the KV-stream cut the
    roofline's ``quantized_kv_adjustment`` models)."""
    fp_engine = ServingEngine(
        build_model(cfg), params, n_slots=N_SLOTS, max_len=MAX_LEN,
        admission="prefill", cache="paged", block_size=BLOCK_SIZE,
    )
    fp_block_bytes = fp_engine.pager._bytes_per_block
    rows = []
    for fmt in ("nf4", "int8"):
        qmodel = build_model(cfg.replace(kv_quant=fmt))
        outs, kept = {}, None
        for mode in ("dense", "paged"):
            engine = ServingEngine(
                qmodel, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                admission="prefill", cache=mode, block_size=BLOCK_SIZE,
                kv_quant=fmt,
            )
            _run_wave(engine, _prompts(N_SLOTS, seed=1))      # warmup/compile
            admit_s, _calls, toks, total_s, outs[mode] = _run_wave(
                engine, _prompts(N_SLOTS, seed=2), uid0=100
            )
            if mode == "paged":
                kept = (engine, admit_s, toks, total_s)
        assert outs["paged"] == outs["dense"], (
            f"{family}: {fmt} quantized paged KV diverged from the dense "
            "fake-quantized reference"
        )
        engine, admit_s, toks, total_s = kept
        q_block_bytes = engine.pager._bytes_per_block
        rows.append(csv_row(
            f"serve_kvquant_{family}_{fmt}",
            admit_s * 1e6,
            f"toks/s={toks / total_s:.0f} "
            f"block_bytes={q_block_bytes:.0f} "
            f"fp_block_bytes={fp_block_bytes:.0f} "
            f"cut={fp_block_bytes / max(q_block_bytes, 1.0):.2f}x "
            f"kv_quant={engine.stats['kv_quant']}",
        ))
    return rows


def bench_adapter_modes(family: str, arch: str, cfg, model, params):
    """The four adapter serving modes over one wave: single AdapterSet
    (reference vs pallas QuanTA kernels), an 8-tenant AdapterBank with
    per-request selection, and merged zero-overhead deployment — with
    token-for-token equivalence asserts (the multi-adapter CI gate).

    The bank wave carries 2 x ``N_SLOTS`` requests round-robined over ALL
    8 tenants (slot churn included), and per-request parity is asserted
    for both a QuanTA tenant (bank row 1 of its group) and a LoRA tenant
    against their dedicated single-tenant engines.
    """
    targets = get_peft(arch).targets
    qbase, qset = attach(
        jax.random.PRNGKey(1), params,
        PeftConfig(method="quanta", scheme=None, n_axes=3, targets=targets),
    )
    n_wave = 2 * N_SLOTS                  # more requests than slots: churn
    prompts = _prompts(n_wave, seed=2)

    def measure(m, ps, peft=None, adapters=None, tenant_of=None):
        engine = ServingEngine(m, ps, peft, adapters=adapters,
                               n_slots=N_SLOTS, max_len=MAX_LEN)
        for wave, uid0 in ((_prompts(n_wave, seed=1), 0), (prompts, 100)):
            reqs = [
                Request(uid=uid0 + i, prompt=list(p), max_new_tokens=MAX_NEW,
                        adapter=tenant_of(i) if tenant_of else None)
                for i, p in enumerate(wave)
            ]
            for r in reqs:
                engine.submit(r)
            t0 = time.perf_counter()           # warmup wave pays compiles
            engine.run()
            total_s = time.perf_counter() - t0
        toks = sum(len(r.output) for r in reqs)
        return [r.output for r in reqs], toks / total_s, engine.stats

    rows = []
    single, tps, stats = measure(model, qbase, peft=qset)
    rows.append(csv_row(
        f"serve_adapters_{family}_single", 1e6 / tps,
        f"toks/s={tps:.0f} adapter_bytes={stats['adapter_bytes']}",
    ))
    pl_model = build_model(cfg.replace(peft_backend="pallas"))
    pallas, tps, stats = measure(pl_model, qbase, peft=qset)
    assert pallas == single, (
        f"{family}: peft_backend='pallas' diverged from reference"
    )
    rows.append(csv_row(
        f"serve_adapters_{family}_pallas", 1e6 / tps,
        f"toks/s={tps:.0f} parity=ok",
    ))
    # 8 tenants over ONE base: the QuanTA set + 7 perturbed LoRA sets
    tenants = {"t0": (qbase, qset)}
    for i in range(1, 8):
        _, lset = attach(
            jax.random.PRNGKey(10 + i), params,
            PeftConfig(method="lora", rank=4, targets=targets),
        )
        tenants[f"t{i}"] = jax.tree_util.tree_map(
            lambda x: x + 0.1 * jax.random.normal(
                jax.random.PRNGKey(20 + i), x.shape, x.dtype
            ),
            lset,
        )
    bank = AdapterBank.build(params, tenants)
    banked, tps, stats = measure(
        model, params, adapters=bank, tenant_of=lambda i: f"t{i % 8}"
    )
    assert banked[0] == single[0], (
        f"{family}: bank tenant t0 (QuanTA) diverged from its "
        "single-tenant engine"
    )
    lora_single, _, _ = measure(model, params, peft=tenants["t1"])
    assert banked[1] == lora_single[1], (
        f"{family}: bank tenant t1 (LoRA) diverged from its "
        "single-tenant engine"
    )
    rows.append(csv_row(
        f"serve_adapters_{family}_bank8", 1e6 / tps,
        f"toks/s={tps:.0f} tenants={stats['adapter_tenants']} "
        f"adapter_bytes={stats['adapter_bytes']} "
        f"cache_bytes={stats['cache_bytes_allocated']}",
    ))
    merged_out, tps, stats = measure(model, merge_all(qbase, qset))
    assert merged_out == single, (
        f"{family}: merged deployment diverged from adapter-attached"
    )
    rows.append(csv_row(
        f"serve_adapters_{family}_merged", 1e6 / tps,
        f"toks/s={tps:.0f} adapter_bytes={stats['adapter_bytes']}",
    ))
    return rows


def bench_adapter_churn(family: str, arch: str, model, params):
    """Hot-swap adapter lifecycle: a 64-tenant ``AdapterStore`` registry
    served through an 8-row ``AdapterPool``, with waves round-robined
    over 16 distinct tenants so residency churns mid-run (loads + LRU
    evictions while earlier tenants still decode).  Asserts two churned
    tenants token-for-token against dedicated cold engines and that the
    serving jits never recompiled across swaps (one swap trace total:
    all tenants share one structure profile), then reports the byte
    split the registry/resident divide exists for.
    """
    targets = get_peft(arch).targets
    _, proto = attach(
        jax.random.PRNGKey(1), params,
        PeftConfig(method="lora", rank=4, targets=targets),
    )
    leaves, treedef = jax.tree_util.tree_flatten(proto)
    store = AdapterStore(max_tenants=64)
    sets = {}
    for i in range(64):
        rng = np.random.default_rng(i)
        sets[f"t{i:02d}"] = jax.tree_util.tree_unflatten(treedef, [
            np.asarray(leaf)
            + (0.1 * rng.standard_normal(np.shape(leaf))).astype(
                np.asarray(leaf).dtype)
            for leaf in leaves
        ])
        store.register(f"t{i:02d}", sets[f"t{i:02d}"])
    pool = AdapterPool.build(params, store, capacity=8)

    engine = ServingEngine(model, params, adapters=pool,
                           n_slots=N_SLOTS, max_len=MAX_LEN)
    n_wave = 2 * N_SLOTS
    served = [f"t{(i * 5) % 64:02d}" for i in range(16)]   # 16 > capacity
    outs = {}
    for wave_i, uid0 in enumerate((0, 100)):
        prompts = _prompts(n_wave, seed=1 + wave_i)
        reqs = [
            Request(uid=uid0 + i, prompt=list(p), max_new_tokens=MAX_NEW,
                    adapter=served[(wave_i * n_wave + i) % len(served)])
            for i, p in enumerate(prompts)
        ]
        for r in reqs:
            engine.submit(r)
        t0 = time.perf_counter()               # warmup wave pays compiles
        engine.run()
        total_s = time.perf_counter() - t0
        outs.update({r.uid: (r.adapter, list(r.prompt), r.output)
                     for r in reqs})
    toks = sum(len(o) for _, _, o in outs.values())
    tps = toks / total_s
    stats = engine.stats
    engine.compile_guard.assert_ok()
    counts = engine.compile_guard.counts()
    assert counts["swap"] == 1, (
        f"{family}: adapter hot-swap retraced ({counts['swap']} compiles "
        "for one structure profile)"
    )
    assert stats["adapter_loads"] > 8 and stats["adapter_evictions"] > 0, (
        f"{family}: churn wave never exercised the pool "
        f"(loads={stats['adapter_loads']} "
        f"evictions={stats['adapter_evictions']})"
    )

    # token-for-token: two churned tenants vs dedicated cold engines
    for name in (served[0], served[9]):
        mine = {u: (p, o) for u, (t, p, o) in outs.items() if t == name}
        cold = ServingEngine(
            model, params,
            jax.tree_util.tree_map(jnp.asarray, sets[name]),
            n_slots=N_SLOTS, max_len=MAX_LEN,
        )
        creqs = [Request(uid=u, prompt=list(p), max_new_tokens=MAX_NEW)
                 for u, (p, _) in sorted(mine.items())]
        for r in creqs:
            cold.submit(r)
        cold.run()
        for r in creqs:
            assert r.output == mine[r.uid][1], (
                f"{family}: pooled tenant {name} uid={r.uid} diverged "
                "from its cold single-tenant engine"
            )

    return [csv_row(
        f"serve_churn_{family}_pool8", 1e6 / tps,
        f"toks/s={tps:.0f} tenants={stats['adapter_tenants']} "
        f"loads={stats['adapter_loads']} "
        f"evictions={stats['adapter_evictions']} "
        f"swap_p50={stats['adapter_swap_p50'] * 1e6:.0f}us "
        f"resident_bytes={stats['adapter_bytes_resident']} "
        f"registry_bytes={stats['adapter_bytes_registry']}",
    )]


def bench_sharded(family: str, model, params, base):
    """Mesh-sharded engine (2x`data` . 4x`model` over 8 virtual CPU
    devices) vs the single-device engine: latency, per-host cache bytes,
    and a token-for-token equivalence assert for dense AND paged
    (``base`` = the single-device outputs bench_cache_modes measured on
    the same waves)."""
    if jax.device_count() < 8:
        raise SystemExit(
            "--sharded needs 8 devices; pass it on the command line so "
            "the device-count flag applies before jax initializes"
        )
    mesh = make_host_mesh(2, 4)
    rows = []
    for mode in ("dense", "paged"):
        engine = ServingEngine(
            model, params, n_slots=N_SLOTS, max_len=MAX_LEN,
            admission="prefill", cache=mode, block_size=BLOCK_SIZE,
            mesh=mesh,
        )
        _run_wave(engine, _prompts(N_SLOTS, seed=1))          # warmup/compile
        admit_s, _calls, toks, total_s, outs = _run_wave(
            engine, _prompts(N_SLOTS, seed=2), uid0=100
        )
        assert outs == base, (
            f"{family}: sharded {mode} engine diverged from single-device"
        )
        rows.append(csv_row(
            f"serve_sharded_{family}_{mode}",
            admit_s * 1e6,
            f"toks/s={toks / total_s:.0f} mesh=2x4 "
            f"host_bytes={engine.stats['cache_bytes_allocated']}",
        ))
    return rows


OPENLOOP_N = 24           # requests per open-loop schedule
OPENLOOP_RATE = 100.0     # Poisson arrivals/sec across both classes
OPENLOOP_SEED = 0


def bench_open_loop(family: str, model, params):
    """Open-loop Poisson load through the SLA front end, dense and paged:
    exact (raw stream-timestamp) latency percentiles and per-class
    goodput, with the streamed outputs asserted token-for-token equal to
    the closed-loop engine on the same requests and at least one chained
    (double-buffered) dispatch required."""
    rows, results = [], {}
    targets = {c.name: c.ttft_target for c in DEFAULT_CLASSES}
    for mode in ("dense", "paged"):
        kw = (
            dict(cache="paged", block_size=BLOCK_SIZE)
            if mode == "paged" else {}
        )
        prompts = _prompts(OPENLOOP_N, seed=3)

        # closed-loop reference: same engine config, plain FIFO run()
        ref_engine = ServingEngine(
            model, params, n_slots=N_SLOTS, max_len=MAX_LEN,
            admission="prefill", **kw,
        )
        ref_reqs = [
            Request(uid=i, prompt=list(p), max_new_tokens=MAX_NEW)
            for i, p in enumerate(prompts)
        ]
        for r in ref_reqs:
            ref_engine.submit(r)
        ref_engine.run()
        ref = {r.uid: r.output for r in ref_reqs}

        engine = ServingEngine(
            model, params, n_slots=N_SLOTS, max_len=MAX_LEN,
            admission="prefill", **kw,
        )
        fe = ServeFrontend(engine)
        warm = [
            Request(uid=1000 + i, prompt=list(p), max_new_tokens=MAX_NEW)
            for i, p in enumerate(_prompts(N_SLOTS, seed=1))
        ]
        for r in warm:                       # warmup pays the jit compiles
            fe.submit(r)
        fe.drain()

        # seeded Poisson schedule, pre-submitted with future arrival
        # times: the scheduler releases each request when the clock
        # reaches it (arrivals independent of service — open loop).
        arrivals = poisson_arrivals(
            np.random.default_rng(OPENLOOP_SEED), OPENLOOP_RATE,
            OPENLOOP_N, start=engine.clock() + 0.01,
        )
        reqs = [
            Request(uid=i, prompt=list(p), max_new_tokens=MAX_NEW,
                    arrival_time=float(arrivals[i]),
                    latency_class="interactive" if i % 2 == 0 else "batch")
            for i, p in enumerate(prompts)
        ]
        streams = [fe.submit(r) for r in reqs]
        t0 = time.perf_counter()
        fe.drain()
        wall_s = time.perf_counter() - t0

        outs = {r.uid: r.output for r in reqs}
        assert outs == ref, (
            f"{family}: open-loop {mode} front end diverged from the "
            "closed-loop engine"
        )
        assert fe.stats["chained"] > 0, (
            f"{family}: double-buffered dispatch never engaged"
        )

        per_class = {}
        for cls in targets:
            cs = [s for s in streams if s.request.latency_class == cls]
            ttfts = np.array([
                s.token_times[0] - s.request.arrival_time for s in cs
            ])
            tpots = np.concatenate([
                np.diff(s.token_times) for s in cs
                if len(s.token_times) > 1
            ])
            met = ttfts <= targets[cls]
            good_toks = sum(
                len(s.tokens) for s, ok in zip(cs, met) if ok
            )
            m = {
                "n_requests": len(cs),
                "ttft_p50_ms": float(np.percentile(ttfts, 50) * 1e3),
                "ttft_p99_ms": float(np.percentile(ttfts, 99) * 1e3),
                "tpot_p50_ms": float(np.percentile(tpots, 50) * 1e3),
                "tpot_p99_ms": float(np.percentile(tpots, 99) * 1e3),
                "slo_attainment": float(np.mean(met)),
                "goodput_toks_s": float(good_toks / wall_s),
            }
            per_class[cls] = m
            rows.append(csv_row(
                f"serve_openloop_{family}_{mode}_{cls}",
                m["ttft_p50_ms"] * 1e3,
                f"ttft_p99_ms={m['ttft_p99_ms']:.2f} "
                f"tpot_p50_ms={m['tpot_p50_ms']:.2f} "
                f"tpot_p99_ms={m['tpot_p99_ms']:.2f} "
                f"goodput_toks_s={m['goodput_toks_s']:.0f} "
                f"slo_attainment={m['slo_attainment']:.2f}",
            ))
        s = engine.stats
        depth = "/".join(
            f"{k}:{v}" for k, v in sorted(
                s.get("queue_depth_peak", {}).items()
            )
        )
        rows.append(csv_row(
            f"serve_openloop_{family}_{mode}_engine",
            s["tick_p50"] * 1e6,
            f"tick_p99_us={s['tick_p99'] * 1e6:.0f} "
            f"ttft_gauge_p50_ms={s['ttft_p50'] * 1e3:.2f} "
            f"ttft_gauge_p99_ms={s['ttft_p99'] * 1e3:.2f} "
            f"chained={fe.stats['chained']} ticks={fe.stats['ticks']} "
            f"preemptions={s['preemptions']} qdepth_peak={depth}",
        ))
        results[mode] = {
            "per_class": per_class,
            "wall_s": wall_s,
            "chained": fe.stats["chained"],
            "host_dispatch": fe.stats["host_dispatch"],
            "ticks": fe.stats["ticks"],
            "preemptions": s["preemptions"],
            "queue_depth_peak": s.get("queue_depth_peak", {}),
            "tick_hist": engine.tick_hist.to_dict(),
        }
    return rows, results


def main(
    smoke: bool = False, sharded: bool = False, open_loop: bool = False,
    record: str = None,
) -> None:
    families = (
        {"transformer": FAMILIES["transformer"]} if smoke else FAMILIES
    )
    recorded = {}
    for family, arch in families.items():
        for row in bench_family(family, arch, sharded=sharded):
            print(row)
        if open_loop:
            cfg = get_smoke(arch)
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            rows, results = bench_open_loop(family, model, params)
            for row in rows:
                print(row)
            recorded[family] = results
    if record and recorded:
        import json

        payload = {
            "bench": "serve_openloop",
            "config": {
                "n_slots": N_SLOTS, "max_len": MAX_LEN,
                "prompt_len": PROMPT_LEN, "max_new": MAX_NEW,
                "block_size": BLOCK_SIZE, "n_requests": OPENLOOP_N,
                "rate_per_s": OPENLOOP_RATE, "seed": OPENLOOP_SEED,
                "classes": {
                    c.name: c.ttft_target for c in DEFAULT_CLASSES
                },
            },
            "families": recorded,
        }
        os.makedirs(os.path.dirname(record) or ".", exist_ok=True)
        with open(record, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# open-loop record written to {record}", file=sys.stderr)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: transformer family only")
    ap.add_argument("--sharded", action="store_true",
                    help="add mesh-sharded engine rows (forces 8 virtual "
                         "CPU devices; must be set at process start)")
    ap.add_argument("--open-loop", action="store_true",
                    help="add open-loop Poisson load rows through the SLA "
                         "front end (TTFT/TPOT percentiles, goodput per "
                         "latency class)")
    ap.add_argument("--record", metavar="PATH",
                    help="with --open-loop: also write the metrics as JSON "
                         "(the committed baseline lives under "
                         "benchmarks/results/serving/)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    main(smoke=args.smoke, sharded=args.sharded, open_loop=args.open_loop,
         record=args.record)
