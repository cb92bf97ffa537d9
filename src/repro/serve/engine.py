"""Continuous-batching serving engine.

Design (vLLM-style, sized for the single-host example while keeping the
production structure):

* fixed ``n_slots`` decode batch; each slot owns either a dense stripe of
  the KV/state cache (``cache="dense"``) or a **block table** into a
  paged cache pool (``cache="paged"``),
* admission by **prefill wave** (the fast path, default whenever the model
  exposes ``prefill``): queued prompts are right-padded to a common bucketed
  length, prefilled in ONE jitted call, and their cache stripes scattered
  into free slots via the model's ``insert_cache`` — transformers scatter
  KV prefixes, recurrent/SSM families scatter O(1) final states,
* **chunked prefill** (``prefill_chunk=N``, models exposing
  ``prefill_chunk``): a prompt longer than ``N`` tokens is prefilled one
  fixed-size chunk per tick into a small dense staging buffer, with the
  regular fused decode step still running between chunks — admission
  latency is bounded by the chunk size and long prompts no longer stall
  active streams.  The finished staging buffer lands in the serving cache
  through the very same ``insert_cache`` scatter as a wave,
* **decode-replay admission** is kept as an explicit fallback
  (``admission="replay"``, or automatically for models without ``prefill``
  / with non-token frontends): prompts replay token-by-token into the slot
  stripes, batched across the wave,
* one fused decode step per tick for all active slots (greedy sampling),
* slots free on EOS/max-length; the queue backfills on the next tick.

Paged cache mode (``cache="paged"``, ``repro.serve.paging``): every cache
leaf whose spec is a ``PagedCacheLeafSpec`` (transformer KV, Griffin's
ring buffers) is stored as an ``(n_blocks, block_size, ...)`` pool.  A
host-side ``BlockAllocator`` hands blocks to slots at admission, extends
them as decode crosses block boundaries (alloc-on-append), and reclaims
them the moment a request completes — cache memory scales with tokens in
flight, not ``n_slots * max_len``.  The device sees one extra
``block_tables`` argument per decode step; with
``cfg.attn_backend="pallas"`` the paged flash-decode kernel gathers KV
blocks through that table at grid level, so per-slot reads also scale
with allocated blocks.  O(1) recurrent-state leaves (and all of Mamba2)
stay dense — the paged engine degenerates to the dense one when a model
has no pageable leaves.

Cache surgery (freeing a slot, masking a replay wave, scattering a prefill
wave) is driven by the model's declarative ``cache_spec()`` — a
``CacheLeafSpec`` per cache leaf naming the slot axis and reset fill value
(``repro.models.api.cache_slot_spec``) — never by shape/dtype guessing.
Per-slot sequence lengths are tracked host-side from that spec's
bookkeeping (admission sets them, each tick increments active slots), so
steady-state decode performs no device->host cache reads.

To bound recompilation, prefill waves are always padded to ``n_slots``
rows and the token axis is bucketed to a multiple of ``seq_bucket``:
at most ``max_len / seq_bucket`` distinct prefill shapes ever compile.
Block tables are traced arguments of fixed shape, so paged decode keeps
the dense mode's single compile.

``stats`` exposes jitted-dispatch counters (``prefill_calls`` /
``decode_calls`` / ``chunk_calls`` — benchmarks assert O(1) prefill
admission) and memory gauges (``cache_bytes_allocated``,
``blocks_in_use``, ``peak_block_utilization``, ``param_bytes`` —
per-host frozen-base weight bytes, ``adapter_bytes``, ...) that
``benchmarks/serve_bench.py`` reports for dense vs paged and fp vs
quantized bases.

Quantized frozen base (``base_quant="nf4" | "int8"``): every projection
the models apply through ``peft_linear`` is packed into a blockwise
``core.quantize.QuantizedLinear`` (4-bit NF4 codebook or int8, per-block
scales along ``d_in``) before device placement — the QLoRA serving
pattern: ~4x fewer weight bytes per decode tick, full-precision
adapters (single sets AND banks) composing on top of the dequant-matmul
(``cfg.peft_backend="pallas"`` fuses it in VMEM via
``kernels.quantized_matmul``; the reference path is bitwise identical).
Quantization is idempotent, so pre-quantized params pass through — a
bank built over the same quantized base serves token-for-token
identically to per-tenant single-tenant engines (tested, dense + paged
+ sharded).

Quantized KV-cache blocks (``cfg.kv_quant="nf4" | "int8"``, engine
``kv_quant=`` cross-checks the knob): paged block pools store PACKED
codes (uint8 nibble pairs for nf4, int8 otherwise) plus a per-block
fp32 absmax-scale sibling leaf (``<key>_qscale``), blockwise along
head_dim (``cfg.quant_block_size`` — blocks never span tokens).
Prefill waves and chunked staging stay full precision; a stripe is
quantized exactly once, at block commit inside the ``insert_cache``
scatter, and each decode step quantizes the incoming token's K/V row
on append.  With ``cfg.attn_backend="pallas"`` the paged flash-decode
kernel gathers code+scale blocks through the block table and
dequantizes in VMEM (``kernels.flash_attention``); the reference path
dequantizes the gathered pools with the very same
``core.quantize.dequant_values``.  Because scale blocks are per-token,
paged-quantized decode is token-for-token IDENTICAL to the dense
engine serving the same model (whose stripes hold fake-quantized
values through the same helpers) — pinned dense == paged == sharded.
``cache_bytes_allocated`` bills the quantized pool bytes (a ~3.6x KV
cut for nf4 at block 64 over bf16; see ``serve_bench --smoke`` rows
``serve_kvquant_*`` and the roofline's ``quantized_kv_adjustment``).

Sharded serving (``mesh=...``, e.g. ``launch.mesh.make_host_mesh(2, 4)``):
the engine becomes mesh-aware end to end —

* **weights** are placed by the decode-time TP rules
  (``launch.shardings.param_shardings(decode=True)``: column/row-parallel
  projections over `model`, d_model-sharded embedding gathers) and PEFT
  adapters are replicated,
* **caches** are placed by ``launch.shardings.cache_shardings``: the slot
  (batch) axis shards over the DP axes, KV-heads or head_dim over
  `model`; paged block pools shard their block axis over DP with the
  allocator partitioned into per-shard arenas
  (``paging.PagedCacheView(data_shards=...)``) so block indices stay
  shard-local, and block tables stay replicated host-side,
* **every jitted entry point** (prefill wave, chunked prefill, fused
  decode, and the ``insert_cache`` scatter — jitted only under a mesh)
  carries explicit ``in_shardings``/``out_shardings``, so the cache
  stays resident in its partitioned layout across ticks and no implicit
  repartitioning happens at call boundaries,
* with ``cfg.attn_backend="pallas"`` the paged decode kernel runs under
  ``shard_map`` per data shard (``models.attention.paged_decode_attention``)
  — per-shard block-table entries are translated to arena-local pool rows,
* byte gauges report per-host (addressable) device memory
  (``paging.addressable_nbytes``): a `model`-replicated leaf bills every
  local copy, a DP-sharded pool bills only the local partition.

Sharded and single-device engines produce token-for-token identical
greedy outputs (pinned by ``tests/test_sharded_serve.py`` on 8 virtual
CPU devices, for dense AND paged caches across all three families).

Adapters (single-tenant vs multi-tenant):

* **merged weights** (default, paper §6): run ``core.peft.merge_all`` and
  serve the folded params — zero inference overhead.  This remains the
  single-tenant deployment fast path.
* **single adapter set** (``peft=``): serve the adapter-attached model
  (an ``AdapterSet`` from ``core.peft.attach``, or a legacy nested dict)
  — numerically identical to merged (tested).  ``cfg.peft_backend =
  "pallas"`` routes QuanTA application through the fused kernels.
* **multi-tenant bank** (``adapters=``, a ``core.bank.AdapterBank``): N
  trained adapter sets over ONE base-params tree.  ``submit(req,
  adapter="sst2")`` names the tenant; the engine tracks a per-slot
  ``adapter_id`` (0 = base model) and threads it as a traced ``(B,)``
  argument of the prefill-wave, chunked-prefill, and fused-decode jits,
  where each adapted linear gathers its row's adapter with ``jnp.take``
  along the bank axis — a batch mixing tenants stays ONE program with
  O(1) dispatch, and outputs are token-for-token identical to running
  each tenant on its own single-tenant engine (tested, dense + paged +
  sharded).  Under a mesh the bank is placed by
  ``launch.shardings.peft_shardings`` (replicated by default; the bank
  axis can be DP-split).
* **hot-swap adapter pool** (``adapters=``, a
  ``serve.adapter_pool.AdapterPool``): the lifecycle tier over the bank
  layout, for registries far larger than the device should hold.  A
  host-side ``AdapterStore`` keeps every registered tenant as raw
  factors; only a fixed-capacity resident bank lives on device, and —
  unlike the static bank, which the serving jits close over — it rides
  as a **traced argument** of prefill/chunk/decode, so loading or
  evicting a tenant between ticks recompiles nothing (the donated
  row-scatter ``swap`` entry point traces once per tenant structure
  profile).  Admission pins a request's tenant (``AdapterPool.acquire``,
  the LAST admission check — an unloadable tenant defers the request,
  and evicting a pinned tenant is refused); slot free and preemption
  unpin.  Requests carry stable global ids, so a preempted request
  survives its tenant being evicted and reloaded into a different bank
  row.  ``stats`` splits ``adapter_bytes_resident`` (device rows, fixed
  by capacity) from ``adapter_bytes_registry`` (host factors, grows
  with tenants); fold-free QuanTA tenants (``PeftConfig(fold=False)``)
  keep both figures factor-sized — no per-tenant dense base copies.

Async front end (``repro.serve.frontend.ServeFrontend``): this engine is
the **closed-loop core** — ``step()`` admits, dispatches one fused
decode, fetches tokens, and lands them, synchronously.  The front end
layers continuous batching with SLA latency classes on top through the
seams this module exposes:

* ``validate()`` + ``_admit(queue=...)`` — admission driven by the SLA
  scheduler's EDF-ordered class queues (``repro.serve.scheduler``)
  instead of the engine FIFO, with ``_admit(chunk=False)`` handing the
  chunked-prefill cadence to the front end's interleave policy,
* ``requeue_hook`` / ``victim_hook`` — preemption requeues into the
  scheduler's class queues and victim selection becomes SLA-aware
  (lowest-priority class, then latest arrival) while still flowing
  through the paged-arena machinery above,
* ``dispatch_decode()`` / ``_sample`` / ``_postprocess()`` —
  double-buffered ticks: the front end chains the device-resident
  sampled tokens of an un-landed tick straight into the next decode
  dispatch and only then fetches the older tick's tokens, overlapping
  host work (streaming, admission, block allocation) with the device
  step.  ``_fresh`` marks slots whose ``_last_token`` was written by
  admission after the last dispatch — their next token must come from
  the host, not the device chain.

Greedy per-request outputs are **scheduling-independent**: slots are
batch-independent and preemption resumes recompute-exact, so any
front-end admission order is token-for-token identical to this closed
loop (pinned by ``tests/test_frontend.py`` for all three families,
dense and paged, mixed adapter tenants).  ``Request.arrival_time`` /
``latency_class`` feed the per-class TTFT histograms and queue-depth
gauges (``stats["ttft_p50"]`` / ``["queue_depth"]`` / tick-latency
percentiles) surfaced in ``benchmarks/serve_bench.py --open-loop``.

Tracing: each layer boundary of a tick is a ``jax.profiler``
``TraceAnnotation`` (one inactive check when no profiler runs).  The
front end's ``serve.tick`` holds ``serve.chunk`` (uid, pos),
``serve.admit`` > ``serve.prefill`` (rows, bucket) > ``serve.insert``
(rows), ``serve.grow`` > ``serve.preempt`` (uid), ``serve.dispatch``
(chained), ``serve.land``, ``serve.stream``, ``serve.gauges`` and the
adapter pool's ``pool.load`` / ``pool.evict`` (tenant).  Every wait on
a device result is a ``serve.wait`` span.  The programs keep stable names
(``jit_decode_fn``, ``jit_prefill_fn``, ``jit_chunk_fn``,
``jit_insert_fn``, ``jit_sample_fn``, the pool's ``jit_swap_fn``, the
front end's ``jit_merge_tokens_fn``), and the adapter math inside them
runs under the ``adapter`` and ``quanta`` name scopes (``core.bank``,
``core.quanta``).

Correctness tooling (``repro.analysis``):

* every jitted entry point is registered on a
  ``repro.analysis.sanitize.CompileGuard`` (``engine.compile_guard``)
  with its documented compilation bound — see ``compilation_bounds()``:
  fused decode and chunked prefill compile exactly once (+1 jit
  signature-cache slack under a mesh for the first tick's freshly
  placed cache), the prefill wave compiles at most
  ``ceil(max_len / seq_bucket)`` token buckets, and the mesh-jitted
  insert scatter is bounded by the distinct ``(wave rows, token
  bucket)`` layouts it scatters.
* with ``REPRO_SANITIZE=1`` (see ``repro.analysis.sanitize.install``)
  the engine asserts those bounds every tick — a shape/dtype/static
  leaking into an entry point raises ``RetraceError`` at the tick that
  retraced, and ``jax_check_tracer_leaks`` catches traced values
  escaping their trace.  Tests can assert through the same API
  (``engine.compile_guard.counts()`` / ``assert_ok()``) instead of
  bespoke dispatch counters.
* the Pallas kernels the engine dispatches to are statically verified
  by ``python -m repro.analysis --check`` (grid/index-map/VMEM/dtype
  contracts; see ``repro.analysis.kernels`` for registering new ones).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import sanitize
from repro.models.common import (
    insert_cache_slots, merge_cache_slots, reset_cache_slots,
)
from repro.serve.adapter_pool import AdapterPool
from repro.serve.paging import PagedCacheView, addressable_nbytes
from repro.serve.scheduler import LatencyHistogram

__all__ = ["Request", "ServingEngine"]


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    # multi-tenant serving: which bank adapter to decode with (None = the
    # base model; only valid on engines built with ``adapters=``)
    adapter: Optional[str] = None
    # SLA scheduling (repro.serve.scheduler): arrival stamp (engine clock
    # at submit when None — an open-loop harness sets future arrivals
    # explicitly) and the latency class the SLA scheduler queues it
    # under.  Both survive preemption: requeue reuses this very object,
    # never a rebuilt copy (pinned by test).
    arrival_time: Optional[float] = None
    latency_class: str = "interactive"
    # filled by the engine:
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    first_token_time: Optional[float] = None


class ServingEngine:
    def __init__(
        self,
        model,
        params,
        peft=None,
        *,
        adapters=None,
        n_slots: int = 4,
        max_len: int = 256,
        admission: str = "auto",
        seq_bucket: int = 16,
        cache: str = "dense",
        block_size: int = 16,
        n_blocks: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        base_quant: Optional[str] = None,
        kv_quant: Optional[str] = None,
    ):
        self.model = model
        self.cfg = model.cfg
        # frozen-base weight quantization (the QLoRA serving pattern):
        # pack every peft_linear projection into QuantizedLinear before
        # placement; adapters stay full-precision and compose on top of
        # the dequant-matmul.  Idempotent for pre-quantized params.
        self.base_quant = base_quant
        if base_quant is not None:
            from repro.core.quantize import quantize_params

            params = quantize_params(
                params, base_quant,
                block_size=self.cfg.quant_block_size,
            )
        # quantized KV-cache blocks: the decode graph itself quantizes on
        # commit (models branch on cfg.kv_quant), so the engine knob only
        # cross-checks — it cannot enable quantization for a model built
        # without it.
        cfg_kv = getattr(self.cfg, "kv_quant", None)
        if kv_quant is not None:
            if kv_quant not in ("nf4", "int8"):
                raise ValueError(f"unknown kv_quant format {kv_quant!r}")
            if cfg_kv is None:
                raise ValueError(
                    "kv_quant= requires the model cfg to set kv_quant "
                    "(the decode graph quantizes KV at block commit)"
                )
            if kv_quant != cfg_kv:
                raise ValueError(
                    f"engine kv_quant={kv_quant!r} conflicts with model "
                    f"cfg.kv_quant={cfg_kv!r}"
                )
        self.kv_quant = cfg_kv
        self.n_slots = n_slots
        self.max_len = max_len
        self.seq_bucket = seq_bucket
        self.queue: deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * n_slots
        if cache not in ("dense", "paged"):
            raise ValueError(f"unknown cache mode {cache!r}")
        self.cache_mode = cache
        self.mesh = mesh
        self.spec = model.cache_spec()
        if adapters is not None and peft is not None:
            raise ValueError(
                "pass either peft= (one adapter set for every request) or "
                "adapters= (an AdapterBank with per-request selection)"
            )
        self.bank = adapters
        # hot-swap lifecycle mode (adapters= an AdapterPool): the resident
        # bank is a traced ARGUMENT of every serving jit — a static bank
        # is closed over instead, baked into the compiled programs
        self.pool = adapters if isinstance(adapters, AdapterPool) else None
        # what the model jits close over: the bank (selected per request
        # by adapter_ids) or the engine-wide single adapter set; pool mode
        # closes over nothing (the bank rides as an argument)
        served = (
            None if self.pool is not None
            else adapters if adapters is not None else peft
        )
        # per-slot tenant ids (0 = base model), threaded into every
        # serving jit when a bank is attached
        self._adapter_ids = np.zeros((n_slots,), np.int32)
        # ``True`` where admission wrote ``_last_token`` after the most
        # recent decode dispatch: the async front end must source those
        # slots' next tokens from the host, not its device-resident
        # sampled-token chain.  The closed loop never reads it.
        self._fresh = np.zeros((n_slots,), bool)
        # wall clock for arrival stamps / TTFT / tick latency; the front
        # end and tests may swap in a virtual clock.
        self.clock: Callable[[], float] = time.monotonic
        # front-end hooks: where a preempted request requeues (default:
        # the engine's own FIFO front) and how a preemption victim is
        # picked among the slots sharing an exhausted block arena
        # (default: the highest candidate slot — vLLM-style).
        self.requeue_hook: Optional[Callable[[Request], None]] = None
        self.victim_hook: Optional[
            Callable[[List[int], List[Optional[Request]]], int]
        ] = None
        # latency gauges: fused-tick wall time and per-class TTFT
        self.tick_hist = LatencyHistogram()
        self.ttft_hists: Dict[str, LatencyHistogram] = {}

        # --- mesh-aware layout: DP arena count for the paged allocator
        # (slot axis must divide over the DP axes, else slots replicate
        # and the pool stays a single global arena)
        data_shards = 1
        if mesh is not None:
            from repro.launch.mesh import dp_axes

            dp = dp_axes(mesh)
            dp_size = math.prod(dict(mesh.shape)[a] for a in dp) if dp else 1
            if dp_size > 1 and n_slots % dp_size:
                # an uneven split does NOT degrade gracefully: the slot
                # axis shards over the data axes and XLA pads the ragged
                # shard, silently generating wrong tokens (dense cache,
                # single-device-verified repro at n_slots=3 on a 2-dp
                # mesh) — fail loudly instead
                raise ValueError(
                    f"n_slots={n_slots} must be a multiple of the mesh "
                    f"data-parallel size {dp_size}: the slot axis shards "
                    "over the data axes and an uneven split mis-shards "
                    "the cache stripes"
                )
            if dp_size > 1:
                data_shards = dp_size

        if cache == "paged":
            self.pager = PagedCacheView(
                model, n_slots, max_len, block_size, n_blocks,
                data_shards=data_shards, kv_quant=kv_quant,
            )
        else:
            self.pager = None
        self._paged = self.pager is not None and self.pager.paged
        # spec of the SERVING cache: in paged-quant mode the pools hold
        # packed codes plus ``<key>_qscale`` scale leaves, so every
        # cache-surgery call on the serving cache (shardings, the merge,
        # the insert scatter) must use the view's augmented spec.  Waves
        # and chunked staging stay dense full-precision (base spec).
        self.serve_spec = (
            self.pager.serve_spec if self._paged else self.spec
        )

        # --- explicit shardings for every jitted entry point
        if mesh is not None:
            from repro.launch.shardings import (
                cache_shardings, param_shardings, peft_shardings,
            )

            struct = (
                self.pager.struct() if self.pager is not None
                else jax.eval_shape(lambda: model.init_cache(n_slots, max_len))
            )
            self._cache_sh = cache_shardings(
                self.cfg, mesh, struct, spec=self.serve_spec,
                paged=self._paged,
                pool_data_shards=(
                    self.pager.data_shards if self._paged else None
                ),
            )
            # prefill waves / chunked staging buffers are DENSE stripe
            # layouts even under the paged cache (pools only hold landed
            # tokens); shapes differ only along the unsharded token axis,
            # so one sharding tree per batch extent serves every bucket.
            self._wave_sh = cache_shardings(
                self.cfg, mesh,
                jax.eval_shape(lambda: model.init_cache(n_slots, seq_bucket)),
                spec=self.spec, paged=False,
            )
            # chunked staging buffers hold one slot: the batch axis cannot
            # split over DP, so only the model rule applies
            self._chunk_sh = cache_shardings(
                self.cfg, mesh,
                jax.eval_shape(lambda: model.init_cache(1, seq_bucket)),
                spec=self.spec, paged=False,
            )
            self._repl = NamedSharding(mesh, P())
            param_sh = param_shardings(self.cfg, mesh, params, decode=True)
            params = jax.device_put(params, param_sh)
            served_sh = None
            if served is not None:
                served_sh = peft_shardings(mesh, served)
                served = jax.device_put(served, served_sh)
                if adapters is not None:
                    self.bank = served
            self._weights_sh = (param_sh, served_sh)
            if self.pool is not None:
                # resident groups placed once (replicated, the adapter
                # rule); the bank's sharding tree feeds every serving
                # jit's in_shardings for the bank argument
                self.pool.place(mesh)
                self._bank_sh = peft_shardings(mesh, self.pool.device_bank())
            else:
                self._bank_sh = None
        else:
            self._cache_sh = self._wave_sh = self._chunk_sh = None
            self._repl = None
            self._bank_sh = None
            self._weights_sh = None
        self.params = params
        self.peft = served if adapters is None else None
        # the frozen base and the adapters enter every serving jit as its
        # first (traced) argument: a closed-over array would be baked
        # into the program as a literal
        self._weights = (params, served)
        self.cache = (
            self.pager.init_cache(shardings=self._cache_sh)
            if self.pager is not None
            else model.init_cache(n_slots, max_len, shardings=self._cache_sh)
        )
        self._lengths = np.zeros((n_slots,), np.int32)   # host-side per slot
        self._last_token = np.zeros((n_slots,), np.int32)
        # jitted-dispatch counters (benchmarks assert O(1) prefill admission)
        # + cache-memory gauges (refreshed by _update_gauges)
        # adapter byte gauges, split RESIDENT (device state the decode
        # ticks read: one AdapterSet, a whole static bank, or the pool's
        # fixed-capacity row bank) vs REGISTRY (host-side factor bytes of
        # every registered tenant — pool mode only; 0 elsewhere).
        # ``adapter_bytes`` stays the resident figure for back-compat.
        if self.pool is not None:
            resident_b = self.pool.resident_nbytes()
            registry_b = self.pool.store.nbytes
        else:
            resident_b = int(sum(
                addressable_nbytes(leaf)
                for leaf in jax.tree_util.tree_leaves(served)
            )) if served is not None else 0
            registry_b = 0
        self.stats: Dict[str, Any] = {
            "decode_calls": 0, "prefill_calls": 0, "chunk_calls": 0,
            "preemptions": 0,
            "queue_depth": {},
            "adapter_bytes": resident_b,
            "adapter_bytes_resident": resident_b,
            "adapter_bytes_registry": registry_b,
            "adapter_tenants": (
                self.bank.num_tenants if self.bank is not None else 0
            ),
            # per-host frozen-base weight bytes (a quantized base shows
            # its ~4x cut here; serve_bench reports it per row)
            "param_bytes": int(sum(
                addressable_nbytes(leaf)
                for leaf in jax.tree_util.tree_leaves(self.params)
            )),
            "base_quant": base_quant or "none",
            # KV-cache quantization format actually in effect (the paged
            # stats refresh keeps this in sync with the pool view)
            "kv_quant": self.kv_quant or "none",
        }

        can_prefill = (
            hasattr(model, "prefill") and self.cfg.frontend is None
        )
        if admission == "auto":
            admission = "prefill" if can_prefill else "replay"
        if admission not in ("prefill", "replay"):
            raise ValueError(f"unknown admission mode {admission!r}")
        if admission == "prefill" and not can_prefill:
            raise ValueError(
                f"model {self.cfg.name!r} cannot use prefill admission"
            )
        if cache == "paged" and admission == "replay" and self._paged:
            raise ValueError(
                "replay admission writes through dense slot stripes; "
                "use admission='prefill' with the paged cache"
            )
        self.admission = admission

        self.prefill_chunk = prefill_chunk
        self._can_chunk = (
            prefill_chunk is not None
            and admission == "prefill"
            and hasattr(model, "prefill_chunk")
        )
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be positive")
        # at most one in-flight chunked admission (req, slot, staged, pos)
        self._chunking: Optional[Dict[str, Any]] = None

        # the mesh reaches the model's paged attention only when the pool
        # arenas match the mesh's DP axes (shard-local block indices hold)
        decode_mesh = (
            mesh if self._paged and self.pager.data_shards > 1 else None
        )

        def _jit(fn, in_sh=None, out_sh=None):
            """jit with explicit in/out shardings under a mesh, plain jit
            otherwise — every device entry point goes through here."""
            if mesh is None:
                return jax.jit(fn)
            return jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)

        cache_sh, wave_sh, chunk_sh = (
            self._cache_sh, self._wave_sh, self._chunk_sh
        )
        repl = self._repl
        banked = self.bank is not None
        pooled = self.pool is not None
        w_sh = self._weights_sh

        def opt(flag, sh):
            """Sharding of an optional trailing argument (None if absent)."""
            return sh if flag else None

        # Every serving jit takes the weights first, then its per-call
        # arguments, then the optional trailing ones — block tables
        # (paged), the traced (B,) adapter_ids (bank attached) and the
        # resident bank itself (pool mode: hot-swapped rows must reach
        # already-compiled programs).  Absent ones are passed as None.
        # Per-request selection stays inside one fused program (O(1)
        # dispatch either way).
        def decode_fn(w, cache, toks, bt, aids, bank):
            return model.decode_step(
                w[0], bank if pooled else w[1], cache, {"tokens": toks},
                block_tables=bt, mesh=decode_mesh, adapter_ids=aids,
            )

        self._decode = _jit(
            decode_fn,
            in_sh=(w_sh, cache_sh, repl, opt(self._paged, repl),
                   opt(banked, repl), opt(pooled, self._bank_sh)),
            out_sh=(repl, cache_sh),
        )
        # greedy sampler over the fused decode's (B, 1, V) logits,
        # device-side: returns (B, 1) int32 next tokens WITHOUT a host
        # round-trip, so the async front end can chain them straight
        # into the next decode dispatch (double-buffered ticks) and the
        # closed loop fetches them with one D2H copy.
        vocab = self.cfg.vocab_size

        def sample_fn(logits):
            return jnp.argmax(logits[:, :, :vocab], -1).astype(jnp.int32)

        self._sample = _jit(sample_fn, in_sh=repl, out_sh=repl)
        if admission != "prefill":
            self._prefill = None
        else:
            def prefill_fn(w, toks, lens, aids, bank):
                return model.prefill(
                    w[0], bank if pooled else w[1], {"tokens": toks},
                    lengths=lens, adapter_ids=aids,
                )

            self._prefill = _jit(
                prefill_fn,
                in_sh=(w_sh, repl, repl, opt(banked, repl),
                       opt(pooled, self._bank_sh)),
                out_sh=(repl, wave_sh),
            )
        if not self._can_chunk:
            self._chunk_fn = None
        else:
            def chunk_fn(w, staged, toks, pos, n_valid, aids, bank):
                return model.prefill_chunk(
                    w[0], bank if pooled else w[1], {"tokens": toks}, staged,
                    pos, n_valid, adapter_ids=aids,
                )

            self._chunk_fn = _jit(
                chunk_fn,
                in_sh=(w_sh, chunk_sh, repl, repl, repl, opt(banked, repl),
                       opt(pooled, self._bank_sh)),
                out_sh=(repl, chunk_sh),
            )
        # the insert scatter is one jitted program (eagerly, quantizing a
        # full-width KV wave holds several wave-sized temporaries); under
        # a mesh it carries explicit shardings, so the wave lands in the
        # partitioned cache without a host gather.  `None` entries leave the wave/staging input as committed (wave
        # buffers arrive already sharded from the prefill/chunk jits; the
        # two layouts differ in batch extent, so one spec can't cover
        # both).  Compile count is bounded: wave sizes <= n_slots, token
        # extents bucketed.
        if self._paged and self.pager.kv_quant is not None:
            # quantized pools: the model's own insert_cache scatters with
            # its BASE cache_spec(), which has no ``_qscale`` leaves —
            # route through the shared body with the view's augmented
            # spec instead (the scatter pre-pass quantizes each wave
            # stripe into codes + scales at commit).
            serve_spec = self.serve_spec

            def insert_fn(cache, ids, wave, bt):
                return insert_cache_slots(
                    serve_spec, cache, ids, wave, block_tables=bt
                )
        else:
            def insert_fn(cache, ids, wave, bt):
                return model.insert_cache(cache, ids, wave, block_tables=bt)

        self._insert_fn = _jit(
            insert_fn, in_sh=(cache_sh, repl, None, None), out_sh=cache_sh
        )

        # Correctness tooling: every jitted entry point carries its
        # documented compilation bound (eager fns are skipped inside
        # register).  Asserted per tick under REPRO_SANITIZE=1; tests
        # assert through the same API.
        bounds = self.compilation_bounds()
        self.compile_guard = sanitize.CompileGuard("ServingEngine")
        self.compile_guard.register("decode", self._decode, bounds["decode"])
        self.compile_guard.register("prefill", self._prefill,
                                    bounds["prefill"])
        self.compile_guard.register("chunk", self._chunk_fn, bounds["chunk"])
        self.compile_guard.register("insert", self._insert_fn,
                                    bounds["insert"])
        self.compile_guard.register("sample", self._sample, bounds["sample"])
        if self.pool is not None:
            self.compile_guard.register("swap", self.pool.swap_fn,
                                        bounds["swap"])
        self._update_gauges()

    # ------------------------------------------------------ compile bounds
    def compilation_bounds(self) -> Dict[str, int]:
        """Documented compilation bound per jitted entry point.

        * ``decode`` — 1: every tick decodes the full fixed-shape slot
          batch (block tables are traced args of fixed shape, adapter
          ids a traced ``(B,)`` vector), so the fused decode step
          compiles exactly once.
        * ``prefill`` — ``ceil(max_len / seq_bucket)``: waves are padded
          to ``n_slots`` rows and the token axis is bucketed, so at most
          one compile per token bucket.
        * ``chunk`` — ``n_buckets + 2`` when chunking is enabled (else
          1): every chunk step feeds a fixed ``(1, prefill_chunk)``
          token block, but the staging buffer it updates is sized per
          request — chunk-aligned then bucketed, so one compile per
          distinct staging extent, which may exceed ``max_len`` by up
          to ``prefill_chunk + seq_bucket``.
        * ``insert`` — ``n_slots * (n_buckets + 2)``: the scatter sees
          one layout per distinct
          ``(wave rows, token bucket)`` pair; chunked staging adds
          single-row layouts whose token extent may exceed ``max_len``
          by up to ``prefill_chunk + seq_bucket``.
        * ``sample`` — 1: the greedy sampler only ever sees the fused
          decode's fixed ``(n_slots, 1, V)`` logits.
        * ``swap`` — pool mode only: the adapter pool's donated row
          scatter traces once per distinct tenant STRUCTURE profile
          (``AdapterPool.n_profiles``) — row indices and global ids are
          traced scalars, so residency churn itself never recompiles.

        Under a mesh, cache-carrying entry points get **+1 slack**: the
        first tick feeds the freshly ``device_put`` cache, whose
        argument-placement signature differs from the steady-state jit
        outputs — the jit signature cache gains one entry WITHOUT a
        second backend compile (verified via ``jax_log_compiles``), and
        ``_cache_size()`` counts signatures.

        ``compile_guard`` enforces these every tick when
        ``REPRO_SANITIZE=1`` (``repro.analysis.sanitize``).
        """
        n_buckets = -(-self.max_len // self.seq_bucket)
        slack = 1 if self.mesh is not None else 0
        chunked = getattr(self, "_can_chunk", False)
        bounds = {
            "decode": 1 + slack,
            "prefill": n_buckets,
            "chunk": (n_buckets + 2 if chunked else 1) + slack,
            "insert": self.n_slots * (n_buckets + 2),
            "sample": 1 + slack,
        }
        if getattr(self, "pool", None) is not None:
            bounds["swap"] = self.pool.n_profiles + slack
        return bounds

    # ------------------------------------------------------------- frontend
    def submit(self, req: Request, adapter: Optional[str] = None) -> None:
        """Queue a request.  ``adapter`` (or ``req.adapter``) names the bank
        tenant to decode with — engines built with ``adapters=`` only;
        ``None`` serves the base model (bank id 0)."""
        self.validate(req, adapter)
        self.queue.append(req)

    def validate(self, req: Request, adapter: Optional[str] = None) -> None:
        """Validate ``req`` against this engine and stamp it (adapter
        name, ``arrival_time`` when unset) WITHOUT queueing — the SLA
        front end routes validated requests into its own class queues."""
        name = adapter if adapter is not None else req.adapter
        if name is not None and self.bank is None:
            raise ValueError(
                f"request {req.uid} names adapter {name!r} but the "
                "engine has no AdapterBank (pass adapters= at construction)"
            )
        if self.bank is not None:
            self.bank.id_of(name)            # unknown tenants fail at submit
        if len(req.prompt) >= self.max_len:
            raise ValueError("prompt longer than engine max_len")
        if self._paged:
            # worst-case demand including generation: a request that could
            # never fit alone would livelock admission/preemption forever.
            worst = min(
                len(req.prompt) + req.max_new_tokens, self.max_len
            )
            need = self.pager.blocks_for(worst)
            usable = self.pager.max_request_blocks
            if need > usable:
                raise ValueError(
                    f"request needs up to {need} blocks but a pool arena "
                    f"only has {usable}; it could never be admitted"
                )
        if adapter is not None:
            req.adapter = adapter    # stamp only once fully validated
        if req.arrival_time is None:
            req.arrival_time = self.clock()

    def _req_adapter_id(self, req: Request) -> int:
        return self.bank.id_of(req.adapter) if self.bank is not None else 0

    def _decode_args(self, toks) -> List[Any]:
        """Positional args of the fused decode jit: weights, cache,
        tokens, block tables, adapter ids, resident bank (None where the
        engine has none)."""
        return [
            self._weights, self.cache, toks,
            self.pager.device_tables() if self._paged else None,
            jnp.asarray(self._adapter_ids) if self.bank is not None else None,
            self._bank_arg(),
        ]

    def _ids_arg(self, ids):
        """Adapter ids as a traced argument (None without a bank)."""
        return jnp.asarray(ids, jnp.int32) if self.bank is not None else None

    def _bank_arg(self):
        """The resident bank argument (pool mode), else None."""
        return self.pool.device_bank() if self.pool is not None else None

    def _acquire_adapter(self, req: Request) -> bool:
        """Pool mode: pin the request's tenant (loading it — possibly
        evicting an LRU idle tenant — if non-resident).  The LAST
        admission check: False defers the request without tearing
        anything down.  Static banks / single sets are always ready."""
        if self.pool is None:
            return True
        return self.pool.acquire(req.adapter)

    def _release_adapter(self, req: Request) -> None:
        """Pool mode: unpin when the request leaves its slot (completion
        or preemption) — the tenant stays resident until LRU-evicted."""
        if self.pool is not None:
            self.pool.release(req.adapter)

    @staticmethod
    def _tokens(req: Request) -> List[int]:
        """Admission token stream: a preempted request re-admits with its
        generated tokens as part of the prompt (recompute-style resume —
        prefill over the full prefix is numerically identical to having
        kept decoding, which is exactly the replay/prefill equivalence
        the engine tests pin down)."""
        return req.prompt + req.output if req.output else req.prompt

    def _free_slots(self) -> List[int]:
        reserved = (
            {self._chunking["slot"]} if self._chunking is not None else set()
        )
        return [
            i for i, r in enumerate(self.slots)
            if r is None and i not in reserved
        ]

    def _note_first_token(self, req: Request) -> None:
        """Stamp a request's time-to-first-token on its FIRST ever token
        (a preempted request keeps its original stamp) and record it in
        the per-class TTFT histogram."""
        if req.first_token_time is not None:
            return
        now = self.clock()
        req.first_token_time = now
        if req.arrival_time is not None:
            hist = self.ttft_hists.get(req.latency_class)
            if hist is None:
                hist = self.ttft_hists[req.latency_class] = LatencyHistogram()
            hist.record(max(now - req.arrival_time, 0.0))

    def ttft_all(self) -> LatencyHistogram:
        """TTFT across every latency class (merged counts)."""
        merged = LatencyHistogram()
        for hist in self.ttft_hists.values():
            merged.count += hist.count
            merged.total += hist.total
            merged.max = max(merged.max, hist.max)
            for i, c in enumerate(hist.counts):
                merged.counts[i] += c
        return merged

    def queue_depths(self) -> Dict[str, int]:
        """Queued (not yet admitted) requests per latency class in the
        engine's own FIFO: ``step`` writes it to ``stats["queue_depth"]``
        each tick (the SLA front end writes its class queues' depths
        there instead)."""
        depths: Dict[str, int] = {}
        for req in self.queue:
            depths[req.latency_class] = depths.get(req.latency_class, 0) + 1
        return depths

    def _fetch(self, x) -> np.ndarray:
        """Copy a device result to the host, the one place the serving
        loop waits on the device for tokens: a ``serve.wait`` span."""
        with jax.profiler.TraceAnnotation("serve.wait"):
            return np.asarray(x)

    @functools.partial(jax.profiler.annotate_function, name="serve.gauges")
    def _update_gauges(self) -> None:
        ttft = self.ttft_all()
        self.stats.update(
            ttft_p50=ttft.percentile(50),
            ttft_p99=ttft.percentile(99),
            tick_p50=self.tick_hist.percentile(50),
            tick_p99=self.tick_hist.percentile(99),
        )
        if self.pool is not None:
            pstats = self.pool.stats()
            pstats["adapter_bytes"] = pstats["adapter_bytes_resident"]
            self.stats.update(pstats)
        if self.pager is not None:
            self.stats.update(self.pager.stats())
            self.stats["kv_quant"] = self.stats.get("kv_quant") or "none"
        else:
            if "cache_bytes_allocated" not in self.stats:
                # per-host (addressable) bytes, not the logical global
                # size: a sharded cache bills only local partitions, a
                # model-replicated leaf bills every local copy.
                total = sum(
                    addressable_nbytes(leaf)
                    for leaf in jax.tree_util.tree_leaves(self.cache)
                )
                self.stats.update(
                    blocks_in_use=0, blocks_total=0, peak_blocks_in_use=0,
                    cache_bytes_allocated=int(total),
                    peak_block_utilization=0.0,
                )

    def _bucket(self, n: int) -> int:
        return min(-(-n // self.seq_bucket) * self.seq_bucket, self.max_len)

    # ------------------------------------------------------------ admission
    @functools.partial(jax.profiler.annotate_function, name="serve.admit")
    def _admit(self, queue=None, chunk: bool = True) -> None:
        """One admission pass.  ``queue`` substitutes any deque-protocol
        source (truthiness / ``[0]`` peek / ``popleft``) for the engine's
        FIFO — the SLA front end passes its EDF-ordered ready view;
        ``chunk=False`` skips the fixed one-chunk-per-tick advance so the
        front end's interleave policy can drive chunk bursts itself."""
        if chunk:
            self._step_chunked()
        q = self.queue if queue is None else queue
        free = self._free_slots()
        if not free or not q:
            return
        wave: List[Request] = []
        while q and len(wave) < len(free):
            nxt = q[0]
            n_tok = len(self._tokens(nxt))
            if self._paged:
                # pick a remaining free slot whose ARENA can hold the
                # request (under a mesh each data shard allocates from
                # its own arena): a full arena must not head-of-line
                # block admission into another shard's free slots.
                cand = next(
                    (j for j in range(len(wave), len(free))
                     if self.pager.can_admit(n_tok, free[j])),
                    None,
                )
                if cand is None:
                    break             # no arena has room: wait for frees
                free[len(wave)], free[cand] = free[cand], free[len(wave)]
            if self._can_chunk and n_tok > self.prefill_chunk:
                # long prompt: route through the chunked pipeline (one at
                # a time); shorter prompts behind it may still wave-admit
                # into the remaining free slots this tick.
                if self._chunking is None:
                    if not self._acquire_adapter(nxt):
                        break        # tenant unloadable: defer admission
                    self._start_chunked(
                        q.popleft(), free[len(wave)]
                    )
                    free = [
                        s for s in free if s != self._chunking["slot"]
                    ]
                    continue
                break
            if not self._acquire_adapter(nxt):
                break                # tenant unloadable: defer admission
            if self._paged:
                # reserve NOW (alloc at pop time): later wave members and
                # the mid-decode alloc-on-append see the reduced pool, so
                # admission can never tear mid-wave on a MemoryError.
                self.pager.ensure(free[len(wave)], n_tok)
            wave.append(q.popleft())
        if not wave:
            return
        if self.admission == "prefill":
            self._admit_prefill(free, wave)
        else:
            self._admit_replay(free, wave)

    def _admit_prefill(self, free: Sequence[int], wave: List[Request]) -> None:
        """Fast path: ONE jitted prefill over the right-padded wave, then
        scatter the resulting cache stripes into the free slots."""
        streams = [self._tokens(r) for r in wave]
        lengths = np.array([len(p) for p in streams], np.int32)
        s = self._bucket(int(lengths.max()))
        # fixed (n_slots, bucketed_s) shape: bounded compile count
        toks = np.zeros((self.n_slots, s), np.int32)
        lens = np.ones((self.n_slots,), np.int32)   # dummy rows: length 1
        wave_ids = np.zeros((self.n_slots,), np.int32)   # dummy rows: base
        for row, p in enumerate(streams):
            toks[row, : len(p)] = p
            lens[row] = len(p)
        for row, req in enumerate(wave):
            wave_ids[row] = self._req_adapter_id(req)
        with jax.profiler.TraceAnnotation("serve.prefill", rows=len(wave),
                                          bucket=s):
            logits, wave_cache = self._prefill(
                self._weights, jnp.asarray(toks), jnp.asarray(lens),
                self._ids_arg(wave_ids), self._bank_arg(),
            )
            self.stats["prefill_calls"] += 1
            slot_ids = np.asarray(free[: len(wave)], np.int32)
            self._insert_wave(slot_ids, wave_cache, lengths)
            first = self._fetch(
                jnp.argmax(logits[:, 0, : self.cfg.vocab_size], -1)  # repro: allow(host-jnp) greedy sampling: one argmax+D2H per tick is the sampler, not a leak
            ).astype(np.int32)
        for row, (slot, req) in enumerate(zip(free, wave)):
            self.slots[slot] = req
            self._lengths[slot] = lengths[row]
            self._adapter_ids[slot] = wave_ids[row]
            tok = int(first[row])
            self._last_token[slot] = tok
            self._fresh[slot] = True
            req.output.append(tok)
            self._note_first_token(req)
        self._update_gauges()

    def _insert_wave(self, slot_ids, wave_cache, lengths) -> None:
        """Land a prefill wave (or a finished chunked staging buffer) in
        the serving cache — dense slot scatter, or block-table scatter
        after allocating each row's blocks."""
        tables = None
        with jax.profiler.TraceAnnotation("serve.insert", rows=len(slot_ids)):
            if self._paged:
                for slot, n in zip(slot_ids, lengths):
                    self.pager.ensure(int(slot), int(n))
                ext = self.pager.wave_page_extent(wave_cache)
                nb = -(-ext // self.pager.block_size)
                tables = self.pager.wave_tables(slot_ids, nb)
            self.cache = self._insert_fn(
                self.cache, slot_ids, wave_cache, tables
            )

    # --------------------------------------------------- chunked admission
    def _start_chunked(self, req: Request, slot: int) -> None:
        # The staging buffer must be CHUNK-aligned, not just seq-bucketed:
        # every chunk writes a full (1, C) K/V slab at pos, and a buffer
        # shorter than ceil(len/C)*C would make the final slab's
        # dynamic_update_slice clamp its start and overwrite earlier rows.
        # It may exceed max_len by < C + seq_bucket; the insert scatter
        # slices oversized staging axes back down to the cache extent.
        c = self.prefill_chunk
        tokens = self._tokens(req)
        need = -(-len(tokens) // c) * c
        s_stage = -(-need // self.seq_bucket) * self.seq_bucket
        if self._paged:
            # reserve the whole prompt's blocks up front (the wave loop
            # checked can_admit): chunked admission can then never lose
            # the race against concurrent wave admissions or appends.
            self.pager.ensure(slot, len(tokens))
        self._chunking = {
            "req": req,
            "slot": slot,
            "tokens": tokens,
            "staged": self.model.init_cache(
                1, s_stage, shardings=self._chunk_sh
            ),
            "pos": 0,
            "aid": self._req_adapter_id(req),
        }

    def _step_chunked(self) -> None:
        """Advance the in-flight chunked admission by ONE chunk (called
        once per tick, so decode steps interleave between chunks)."""
        if self._chunking is None:
            return
        st = self._chunking
        req, c = st["req"], self.prefill_chunk
        tokens = st["tokens"]
        pos = st["pos"]
        with jax.profiler.TraceAnnotation("serve.chunk", uid=req.uid,
                                          pos=pos):
            n_valid = min(c, len(tokens) - pos)
            toks = np.zeros((1, c), np.int32)
            toks[0, :n_valid] = tokens[pos : pos + n_valid]
            logits, st["staged"] = self._chunk_fn(
                self._weights, st["staged"], jnp.asarray(toks), pos, n_valid,
                self._ids_arg([st["aid"]]), self._bank_arg(),
            )
            self.stats["chunk_calls"] += 1
            st["pos"] = pos + n_valid
            if st["pos"] < len(tokens):
                return
            # final chunk: first token + the SAME insert_cache scatter as
            # a wave
            slot = st["slot"]
            self._insert_wave(
                np.asarray([slot], np.int32), st["staged"],
                np.asarray([len(tokens)], np.int32),
            )
            tok = int(self._fetch(jnp.argmax(  # repro: allow(host-jnp) greedy sampling: one argmax+D2H per chunk is the sampler, not a leak
                logits[0, 0, : self.cfg.vocab_size]
            )))
            self.slots[slot] = req
            self._lengths[slot] = len(tokens)
            self._adapter_ids[slot] = st["aid"]
            self._last_token[slot] = tok
            self._fresh[slot] = True
            req.output.append(tok)
            self._note_first_token(req)
            self._chunking = None
            self._update_gauges()

    def _admit_replay(self, free: Sequence[int], wave: List[Request]) -> None:
        """Fallback: prompts replay token-by-token through ``decode_step``
        into the slot's cache stripe — O(max_prompt_len) jitted dispatches
        per wave, batched across the wave's slots."""
        max_p = max(len(r.prompt) for r in wave)
        slot_ids = np.asarray(free[: len(wave)], np.int32)
        self.cache = reset_cache_slots(self.spec, self.cache, slot_ids)
        for slot, req in zip(free, wave):
            self.slots[slot] = req
            self._lengths[slot] = len(req.prompt)
            self._adapter_ids[slot] = self._req_adapter_id(req)
        # replay: step all admitted slots together (inactive slots get pads
        # but their cache stripes are masked by the active-slot merge).
        for t in range(max_p):
            toks = np.zeros((self.n_slots, 1), np.int32)
            active = np.zeros((self.n_slots,), bool)
            for slot, req in zip(free, wave):
                if t < len(req.prompt):
                    toks[slot, 0] = req.prompt[t]
                    active[slot] = True
            logits, new_cache = self._decode(
                *self._decode_args(jnp.asarray(toks))
            )
            self.stats["decode_calls"] += 1
            self.cache = merge_cache_slots(
                self.spec, new_cache, self.cache, active
            )
            for slot, req in zip(free, wave):
                if t == len(req.prompt) - 1:
                    nxt = int(jnp.argmax(  # repro: allow(host-jnp) greedy sampling during replay, not a leak
                        logits[slot, 0, : self.cfg.vocab_size]
                    ))
                    self._last_token[slot] = nxt
                    self._fresh[slot] = True
                    req.output.append(nxt)
                    self._note_first_token(req)

    def _preempt(self, slot: int) -> None:
        """Recompute-style preemption (vLLM): free the slot's blocks and
        push the request back to the queue FRONT — it re-admits later
        with ``prompt + output`` as its prefill prefix, which continues
        the greedy stream exactly where it stopped.  ``requeue_hook``
        (the SLA front end) redirects the requeue into its class queues;
        either way the SAME ``Request`` object is reused, so
        ``arrival_time`` / ``latency_class`` / the generated prefix all
        survive preemption."""
        req = self.slots[slot]
        with jax.profiler.TraceAnnotation("serve.preempt", uid=req.uid):
            self.slots[slot] = None
            self._adapter_ids[slot] = 0
            self.pager.release(slot)
            # unpin the tenant: its rows may be reclaimed while the
            # request queues, and re-admission re-acquires (reloading
            # if evicted)
            self._release_adapter(req)
            (self.requeue_hook or self.queue.appendleft)(req)
        self.stats["preemptions"] = self.stats.get("preemptions", 0) + 1

    # ----------------------------------------------------------------- tick
    @functools.partial(jax.profiler.annotate_function, name="serve.grow")
    def _ensure_growth(self, active: np.ndarray) -> None:
        """Paged alloc-on-append: the incoming token may cross a block
        boundary, so every active slot's arena must hold one more token
        before the decode dispatch.  When an arena is exhausted, preempt
        a victim among the ACTIVE slots sharing it — ``victim_hook`` (the
        SLA scheduler's class/arrival-aware pick) or the highest such
        slot by default (vLLM-style).  Victims' blocks free immediately,
        the remaining slots keep decoding this tick, and the victim
        resumes by re-prefilling its prefix.  ``active`` is updated in
        place as victims are evicted."""
        for i in range(self.n_slots):
            if not active[i]:
                continue
            try:
                self.pager.ensure(i, int(self._lengths[i]) + 1)
            except MemoryError:
                # the victim must share slot i's block arena (under a
                # mesh each data shard allocates from its own arena)
                # and always frees >= 1 block there (an active slot
                # holds at least its prompt's first block), so the
                # retried ensure (one extra block) cannot fail —
                # worst case the victim is slot i itself.
                shard = self.pager.shard_of(i)
                cands = [
                    j for j in range(self.n_slots)
                    if active[j] and self.pager.shard_of(j) == shard
                ]
                victim = (
                    self.victim_hook(cands, self.slots)
                    if self.victim_hook is not None else max(cands)
                )
                self._preempt(victim)
                active[victim] = False
                if active[i]:                    # victim was not i
                    self.pager.ensure(i, int(self._lengths[i]) + 1)

    def dispatch_decode(self, toks, active: np.ndarray):
        """Dispatch ONE fused decode step for the full slot batch and
        return the (B, 1, V) logits (a device future — JAX async
        dispatch).  ``toks`` is the (B, 1) int32 token batch; ``active``
        masks which slots' cache stripes the eager merge keeps (paged
        pools skip the merge: inactive slots write the null block).  The
        caller overlaps host work with the device step — the async front
        end even dispatches the NEXT tick from device-resident sampled
        tokens before this one's logits land."""
        logits, new_cache = self._decode(*self._decode_args(toks))
        self.stats["decode_calls"] += 1
        self.cache = merge_cache_slots(
            self.serve_spec, new_cache, self.cache, active,
            skip_paged=self._paged,
        )
        # anything admission stamped before this dispatch is now on device
        self._fresh[:] = False
        return logits

    def _postprocess(self, nxt: np.ndarray, active: np.ndarray) -> None:
        """Land one tick's sampled tokens: append to outputs, advance
        lengths, complete/free slots on EOS / token budget / max_len.
        ``active`` is the DISPATCH-TIME mask of that tick — the front
        end lands a tick one dispatch late, after newer requests were
        admitted into slots the mask excludes."""
        for i, req in enumerate(self.slots):
            if req is None or not active[i]:
                continue
            tok = int(nxt[i])
            req.output.append(tok)
            self._last_token[i] = tok
            self._lengths[i] += 1
            if (req.eos_id is not None and tok == req.eos_id) or \
                    len(req.output) >= req.max_new_tokens or \
                    self._lengths[i] >= self.max_len - 1:
                req.done = True
                self.slots[i] = None
                self._adapter_ids[i] = 0     # freed slots decode as base
                self._release_adapter(req)   # unpin: evictable again
                if self._paged:
                    self.pager.release(i)   # free-on-eviction
        if self._paged:
            self._update_gauges()

    def step(self) -> None:
        t0 = self.clock()
        self._admit()
        self.stats["queue_depth"] = self.queue_depths()
        active = np.array([r is not None for r in self.slots])
        if not active.any():
            return
        if self._paged:
            self._ensure_growth(active)
            if not active.any():
                return
        with jax.profiler.TraceAnnotation("serve.dispatch", chained=0):
            toks = jnp.asarray(self._last_token.reshape(-1, 1))
            sampled = self._sample(self.dispatch_decode(toks, active))
        with jax.profiler.TraceAnnotation("serve.land"):
            self._postprocess(self._fetch(sampled)[:, 0], active)
        self.tick_hist.record(max(self.clock() - t0, 0.0))
        if sanitize.enabled():
            self.compile_guard.assert_ok()

    def run(self, max_ticks: int = 10_000) -> None:
        ticks = 0
        while (
            self.queue or any(self.slots) or self._chunking is not None
        ) and ticks < max_ticks:
            self.step()
            ticks += 1

    def teacher_forced_logits(self, prompt: Sequence[int],
                              continuation: Sequence[int],
                              adapter: Optional[str] = None) -> np.ndarray:
        """Logits of the serving path with the next tokens forced.

        Admits ``prompt`` (as ``adapter``'s tenant) through this engine's
        own prefill, cache and adapter residency, then feeds
        ``continuation`` one token per fused decode step instead of the
        sampled ones.  Returns ``(len(continuation), vocab)`` fp32 logits
        — row ``i`` is the prediction after ``continuation[i]`` — so two
        engines (kernel vs reference backends, sharded vs one device) can
        be compared logit for logit on the same token stream.  Needs an
        idle engine; the slot is freed again on return.
        """
        if self.queue or self._chunking is not None or any(
                r is not None for r in self.slots):
            raise RuntimeError("teacher_forced_logits needs an idle engine")
        req = Request(uid=-1, prompt=list(prompt),
                      max_new_tokens=len(continuation) + 1)
        self.validate(req, adapter)
        self._admit(queue=deque([req]), chunk=False)
        while self._chunking is not None:
            self._step_chunked()
        if req not in self.slots:
            raise RuntimeError("the prompt could not be admitted")
        slot = self.slots.index(req)
        active = np.arange(self.n_slots) == slot
        rows = []
        for tok in continuation:
            self._last_token[slot] = tok
            if self._paged:
                self._ensure_growth(active)
            logits = self.dispatch_decode(
                jnp.asarray(self._last_token.reshape(-1, 1)), active
            )
            rows.append(np.asarray(
                logits[slot, 0, : self.cfg.vocab_size], np.float32
            ))
            self._lengths[slot] += 1
        self.slots[slot] = None
        self._adapter_ids[slot] = 0
        self._release_adapter(req)
        if self._paged:
            self.pager.release(slot)
        self._update_gauges()
        return np.stack(rows)
