"""Static kernel-contract checker for the Pallas kernels.

Every Pallas kernel in this codebase rests on hand-maintained invariants:
its index maps must address blocks in-bounds for every grid step, every
output block must be written (and written uniformly — once per reduction
pass), one grid step's VMEM working set must fit the target budget, and
the dtype discipline (fp32 running statistics / accumulators, output in
the input dtype, int32 scalar operands) must hold.  Mosaic enforces none
of this at Python time; a violation surfaces as a miscompile or a
runtime fault on hardware the CI container doesn't have.

This module checks all of it **abstractly, with no device and no kernel
execution**:

* ``capture_pallas_calls`` monkeypatches ``pl.pallas_call`` with a
  recorder, so each registered kernel family's REAL entry point
  (``flash_attention``, ``paged_flash_decode_attention``,
  ``quanta_linear_fused``, ...) is invoked on representative shapes and
  its actual grid / BlockSpecs / scratch / operand shapes are captured
  exactly as production code builds them — the contract can never drift
  from the implementation,
* the checker then concretely enumerates the grid, evaluates every index
  map (scalar-prefetch operands included: the paged kernel's block
  tables are passed through to its gather maps), and verifies in-bounds
  block addressing, exactly-once (uniform-multiplicity) output-block
  coverage, the VMEM footprint against a per-target budget (the shared
  ``kernels.vmem.vmem_footprint`` arithmetic that ``ops.fused_vmem_ok``
  dispatches on), and the declared dtype contract.

Registering a new kernel (REQUIRED for new kernel families — see
ROADMAP "Correctness tooling")::

    @register_kernel("my_kernel")
    def _build_my_kernel():
        cases = []
        for name, args in representative_shapes:
            with capture_pallas_calls() as records:
                my_kernel_entry_point(*args, interpret=True)
            cases += [(f"{name}/{i}", r) for i, r in enumerate(records)]
        return cases

then ``python -m repro.analysis --check`` (CI's lint gate) covers it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.vmem import VMEM_TARGET_BYTES, vmem_footprint

__all__ = [
    "PallasCallRecord",
    "Finding",
    "capture_pallas_calls",
    "check_record",
    "register_kernel",
    "registered_kernels",
    "check_kernels",
]

# Cap on enumerated grid points per captured call: representative shapes
# must stay small enough to check exhaustively (a contract that can't be
# enumerated isn't a contract).
MAX_GRID_POINTS = 65_536


@dataclasses.dataclass
class PallasCallRecord:
    """One captured ``pl.pallas_call``: the kernel's static contract."""

    name: str
    grid: Tuple[int, ...]
    in_specs: List[Any]                  # pl.BlockSpec per non-scalar operand
    out_specs: List[Any]
    out_shapes: List[jax.ShapeDtypeStruct]
    scratch_shapes: List[Any]            # pltpu.VMEM / SMEM memory refs
    num_scalar_prefetch: int = 0
    scalar_prefetch: List[np.ndarray] = dataclasses.field(
        default_factory=list
    )
    operands: List[jax.ShapeDtypeStruct] = dataclasses.field(
        default_factory=list
    )

    @property
    def grid_points(self) -> int:
        return math.prod(self.grid) if self.grid else 1


@dataclasses.dataclass
class Finding:
    kernel: str
    case: str
    check: str        # "in-bounds" | "coverage" | "vmem" | "dtype" | "grid"
    message: str

    def __str__(self) -> str:
        return f"[{self.kernel}/{self.case}] {self.check}: {self.message}"


def _normalize_specs(specs) -> List[Any]:
    if specs is None:
        return []
    if isinstance(specs, (list, tuple)):
        return list(specs)
    return [specs]


@contextlib.contextmanager
def capture_pallas_calls(records: Optional[List[PallasCallRecord]] = None):
    """Patch ``pl.pallas_call`` with a recorder.

    Inside the context, any ``pallas_call`` builds a :class:`
    PallasCallRecord` instead of lowering a kernel; the returned callable
    captures operand shapes (and CONCRETE copies of scalar-prefetch
    operands, which index maps consume) and returns zeros of the declared
    output shape — so wrapper code (padding, reshapes, slicing) runs
    unmodified and no device is needed.
    """
    if records is None:
        records = []
    real = pl.pallas_call

    def fake_pallas_call(kernel, *, grid=None, in_specs=None, out_specs=None,
                         out_shape=None, scratch_shapes=(), grid_spec=None,
                         **kwargs):
        fn = getattr(kernel, "func", kernel)
        rec = PallasCallRecord(
            name=getattr(fn, "__name__", str(kernel)),
            grid=tuple(grid) if grid is not None else (),
            in_specs=_normalize_specs(in_specs),
            out_specs=_normalize_specs(out_specs),
            out_shapes=(
                list(out_shape) if isinstance(out_shape, (list, tuple))
                else [out_shape]
            ),
            scratch_shapes=list(scratch_shapes or ()),
        )
        if grid_spec is not None:      # e.g. pltpu.PrefetchScalarGridSpec
            rec.grid = tuple(grid_spec.grid)
            rec.in_specs = _normalize_specs(grid_spec.in_specs)
            rec.out_specs = _normalize_specs(grid_spec.out_specs)
            rec.scratch_shapes = list(grid_spec.scratch_shapes or ())
            rec.num_scalar_prefetch = int(
                getattr(grid_spec, "num_scalar_prefetch", 0)
            )

        def runner(*ops):
            nsp = rec.num_scalar_prefetch
            rec.scalar_prefetch = [np.asarray(o) for o in ops[:nsp]]
            rec.operands = [
                jax.ShapeDtypeStruct(o.shape, o.dtype) for o in ops[nsp:]
            ]
            records.append(rec)
            outs = [jnp.zeros(s.shape, s.dtype) for s in rec.out_shapes]
            if isinstance(out_shape, (list, tuple)):
                return outs
            return outs[0]

        return runner

    pl.pallas_call = fake_pallas_call
    try:
        yield records
    finally:
        pl.pallas_call = real


# ---------------------------------------------------------------------------
# Checks over one captured record
# ---------------------------------------------------------------------------

def _n_blocks(shape, block) -> Tuple[int, ...]:
    return tuple(-(-s // b) for s, b in zip(shape, block))


def _eval_index_map(spec, point, prefetch) -> Tuple[int, ...]:
    out = spec.index_map(*point, *prefetch)
    if not isinstance(out, tuple):
        out = (out,)
    return tuple(int(x) for x in out)


def check_record(
    kernel: str,
    case: str,
    rec: PallasCallRecord,
    *,
    vmem_budget: int,
    fp32_scratch: bool = True,
    out_dtype_like: Optional[int] = 0,
    int32_scalars: bool = True,
) -> List[Finding]:
    """All contract checks for one captured ``pallas_call``.

    ``out_dtype_like`` names the (non-scalar-prefetch) operand whose
    dtype every output must match (None skips the check);
    ``fp32_scratch`` requires float32 scratch accumulators (the online-
    softmax running-stats contract); ``int32_scalars`` requires int32
    scalar-prefetch operands (lengths, block tables).
    """
    findings: List[Finding] = []

    def add(check: str, message: str) -> None:
        findings.append(Finding(kernel, case, check, message))

    if rec.grid_points > MAX_GRID_POINTS:
        add("grid", f"grid {rec.grid} has {rec.grid_points} points, "
            f"over the {MAX_GRID_POINTS} enumeration cap — use a smaller "
            "representative shape")
        return findings
    if len(rec.in_specs) != len(rec.operands):
        add("grid", f"{len(rec.in_specs)} in_specs but "
            f"{len(rec.operands)} non-prefetch operands")
        return findings

    # --- in-bounds block addressing, every operand, every grid point
    named = [
        (f"in{i}", spec, op.shape)
        for i, (spec, op) in enumerate(zip(rec.in_specs, rec.operands))
    ] + [
        (f"out{i}", spec, out.shape)
        for i, (spec, out) in enumerate(zip(rec.out_specs, rec.out_shapes))
    ]
    out_multiplicity: List[Dict[Tuple[int, ...], int]] = [
        {} for _ in rec.out_specs
    ]
    for point in itertools.product(*(range(g) for g in rec.grid)):
        for name, spec, shape in named:
            block = tuple(spec.block_shape)
            if len(block) != len(shape):
                add("in-bounds", f"{name}: block rank {len(block)} != "
                    f"operand rank {len(shape)}")
                return findings
            nb = _n_blocks(shape, block)
            idx = _eval_index_map(spec, point, rec.scalar_prefetch)
            if len(idx) != len(shape):
                add("in-bounds", f"{name}: index map returned {len(idx)} "
                    f"coords for rank-{len(shape)} operand at grid {point}")
                return findings
            for d, (i_d, n_d) in enumerate(zip(idx, nb)):
                if not 0 <= i_d < n_d:
                    add("in-bounds",
                        f"{name}: block index {idx} out of bounds at grid "
                        f"{point} (dim {d}: {i_d} not in [0, {n_d}) for "
                        f"shape {shape} / block {block})")
                    return findings
            if name.startswith("out"):
                mult = out_multiplicity[int(name[3:])]
                mult[idx] = mult.get(idx, 0) + 1

    # --- exactly-once output coverage (uniform multiplicity: each output
    # block revisited the same number of times — its reduction depth)
    for i, (spec, out) in enumerate(zip(rec.out_specs, rec.out_shapes)):
        nb = _n_blocks(out.shape, tuple(spec.block_shape))
        want = set(itertools.product(*(range(n) for n in nb)))
        got = out_multiplicity[i]
        missing = want - set(got)
        if missing:
            add("coverage", f"out{i}: {len(missing)} of "
                f"{len(want)} output blocks never written "
                f"(e.g. {sorted(missing)[0]})")
            continue
        counts = set(got.values())
        if len(counts) != 1:
            add("coverage", f"out{i}: non-uniform write multiplicity "
                f"{sorted(counts)} across output blocks — some blocks see "
                "a different number of reduction steps")

    # --- VMEM footprint of one grid step vs the target budget
    blocks = [
        (tuple(spec.block_shape), op.dtype)
        for spec, op in zip(rec.in_specs, rec.operands)
    ] + [
        (tuple(spec.block_shape), out.dtype)
        for spec, out in zip(rec.out_specs, rec.out_shapes)
    ] + [
        (tuple(s.shape), s.dtype) for s in rec.scratch_shapes
    ]
    footprint = vmem_footprint(blocks)
    if footprint > vmem_budget:
        add("vmem", f"one grid step holds {footprint} bytes in VMEM, over "
            f"the {vmem_budget}-byte budget")

    # --- dtype contract
    if fp32_scratch:
        for i, s in enumerate(rec.scratch_shapes):
            dt = jnp.dtype(s.dtype)
            if dt != jnp.dtype(jnp.float32):
                add("dtype", f"scratch {i} is {dt}, not float32 — running "
                    "stats / accumulators must be fp32")
    if out_dtype_like is not None and rec.operands:
        ref = rec.operands[out_dtype_like].dtype
        for i, out in enumerate(rec.out_shapes):
            if jnp.dtype(out.dtype) != jnp.dtype(ref):
                add("dtype", f"out{i} dtype {jnp.dtype(out.dtype)} != "
                    f"operand {out_dtype_like} dtype {jnp.dtype(ref)}")
    if int32_scalars:
        for i, arr in enumerate(rec.scalar_prefetch):
            if arr.dtype != np.int32:
                add("dtype", f"scalar-prefetch operand {i} is {arr.dtype}, "
                    "not int32")
    return findings


# ---------------------------------------------------------------------------
# Registry: each kernel family declares its construction on representative
# shapes by invoking its real entry point under capture.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class KernelContract:
    name: str
    build: Callable[[], List[Tuple[str, PallasCallRecord]]]
    fp32_scratch: bool = True
    out_dtype_like: Optional[int] = 0


_REGISTRY: Dict[str, KernelContract] = {}


def register_kernel(name: str, **contract_kwargs):
    """Decorator: register a builder returning ``[(case_name, record)]``."""
    def deco(build):
        _REGISTRY[name] = KernelContract(
            name=name, build=build, **contract_kwargs
        )
        return build
    return deco


def registered_kernels() -> List[str]:
    return sorted(_REGISTRY)


def check_kernels(
    names: Optional[Sequence[str]] = None,
    *,
    target: str = "v5e",
    budget: Optional[int] = None,
) -> List[Finding]:
    """Run every registered contract; returns all findings (empty = pass)."""
    if budget is None:
        budget = VMEM_TARGET_BYTES[target]
    findings: List[Finding] = []
    for name in (names if names is not None else registered_kernels()):
        contract = _REGISTRY[name]
        try:
            cases = contract.build()
        except Exception as e:  # repro: allow(broad-except) a builder crash of ANY kind is reported as a contract failure, not swallowed
            findings.append(Finding(name, "<build>", "grid",
                                    f"builder raised {e!r}"))
            continue
        if not cases:
            findings.append(Finding(name, "<build>", "grid",
                                    "builder captured no pallas_call"))
        for case, rec in cases:
            findings += check_record(
                name, case, rec,
                vmem_budget=budget,
                fp32_scratch=contract.fp32_scratch,
                out_dtype_like=contract.out_dtype_like,
            )
    return findings


# ---------------------------------------------------------------------------
# Registered kernel families (the five production Pallas kernels).
# Representative shapes mirror the serving/training configs: GQA head
# layouts from the smoke/proxy configs, the default 512 blocking at a
# 1k-token extent, non-divisible extents to exercise the pad+slice paths,
# and sliding-window variants.
# ---------------------------------------------------------------------------

def _capture_cases(invocations) -> List[Tuple[str, PallasCallRecord]]:
    cases = []
    for case_name, thunk in invocations:
        with capture_pallas_calls() as records:
            thunk()
        for i, rec in enumerate(records):
            suffix = f"/{i}" if len(records) > 1 else ""
            cases.append((case_name + suffix, rec))
    return cases


@register_kernel("flash_fwd")
def _build_flash_fwd():
    from repro.kernels.flash_attention import flash_attention

    def run(b, s, h, kv, hd, bq, bk, window, dtype=jnp.bfloat16):
        q = jnp.zeros((b, s, h, hd), dtype)
        k = jnp.zeros((b, s, kv, hd), dtype)
        v = jnp.zeros((b, s, kv, hd), dtype)
        return lambda: flash_attention(
            q, k, v, window=window, block_q=bq, block_k=bk, interpret=True
        )

    return _capture_cases([
        # qwen2-0.5b GQA layout (14 heads / 2 KV) at the default blocking
        ("gqa_s1024_b512", run(1, 1024, 14, 2, 64, 512, 512, None)),
        # llama-7b-proxy MHA heads, prime-ish length -> pad+slice path
        ("mha_s130_pad", run(1, 130, 8, 8, 128, 64, 64, None)),
        # sliding-window (griffin local-attention layers)
        ("window_s512", run(1, 512, 4, 2, 64, 128, 128, 96)),
    ])


# the int32 per-slot lengths ride as scalar prefetch; outputs match q
@register_kernel("flash_decode")
def _build_flash_decode():
    from repro.kernels.flash_attention import flash_decode_attention

    def run(b, s_max, h, kv, hd, bk, window, dtype=jnp.bfloat16):
        q = jnp.zeros((b, 1, h, hd), dtype)
        kc = jnp.zeros((b, s_max, kv, hd), dtype)
        vc = jnp.zeros((b, s_max, kv, hd), dtype)
        lens = jnp.arange(1, b + 1, dtype=jnp.int32) * (s_max // (b + 1) + 1)
        return lambda: flash_decode_attention(
            q, kc, vc, jnp.minimum(lens, s_max), window=window,
            block_k=bk, interpret=True,
        )

    return _capture_cases([
        # serving decode over the engine's bucketed dense cache
        ("gqa_cache256", run(4, 256, 14, 2, 64, 64, None)),
        # odd (non-block-divisible) cache extent -> pad path
        ("odd_cache100", run(2, 100, 8, 8, 128, 64, None)),
        ("window_cache512", run(2, 512, 4, 2, 64, 128, 96)),
    ])


@register_kernel("paged_decode")
def _build_paged_decode():
    from repro.kernels.flash_attention import paged_flash_decode_attention

    def run(b, n_pool, bs, kv, hd, h, alloc, dtype=jnp.bfloat16):
        # tables mirror paging.PagedCacheView.device_tables: allocated
        # rows first, entries past a slot's count repeat its LAST
        # allocated row; lens place each slot mid-way into its blocks.
        max_b = max(alloc)
        tables = np.zeros((b, max_b), np.int32)
        nxt = 1                                  # row 0 = the null block
        lens = np.zeros((b,), np.int32)
        for slot, n in enumerate(alloc):
            rows = list(range(nxt, nxt + n))
            nxt += n
            tables[slot, :n] = rows
            tables[slot, n:] = rows[-1] if rows else 0
            lens[slot] = max(1, n * bs - bs // 2)
        q = jnp.zeros((b, 1, h, hd), dtype)
        kp = jnp.zeros((n_pool, bs, kv, hd), dtype)
        vp = jnp.zeros((n_pool, bs, kv, hd), dtype)
        return lambda: paged_flash_decode_attention(
            q, kp, vp, jnp.asarray(tables), jnp.asarray(lens),
            interpret=True,
        )

    return _capture_cases([
        # mixed allocation: full, partial, and single-block slots
        ("gqa_pool32", run(4, 32, 16, 2, 64, 14, (6, 3, 1, 6))),
        # serving default block_size=16 with a fully-allocated slot
        ("bs16_full", run(2, 16, 16, 8, 128, 8, (7, 2))),
    ])


# Dequant-in-VMEM paged decode (kv_quant): operands after the two int32
# scalar-prefetch arrays are the query part(s) (nf4: its two head_dim
# halves), packed K codes, K scales, packed V codes, V scales — outputs
# match q, the code+scale pools gather through the SAME table index maps
# as the fp kernel, and the online-softmax scratch contract is unchanged.
@register_kernel("paged_decode_quant")
def _build_paged_decode_quant():
    from repro.core.quantize import quantize_kv
    from repro.kernels.flash_attention import paged_flash_decode_attention

    def run(b, n_pool, bs, kv, hd, h, alloc, fmt, qb, dtype=jnp.bfloat16):
        max_b = max(alloc)
        tables = np.zeros((b, max_b), np.int32)
        nxt = 1                                  # row 0 = the null block
        lens = np.zeros((b,), np.int32)
        for slot, n in enumerate(alloc):
            rows = list(range(nxt, nxt + n))
            nxt += n
            tables[slot, :n] = rows
            tables[slot, n:] = rows[-1] if rows else 0
            lens[slot] = max(1, n * bs - bs // 2)
        q = jnp.zeros((b, 1, h, hd), dtype)
        kc, ks = quantize_kv(jnp.zeros((n_pool, bs, kv, hd)), fmt,
                             block_size=qb)
        vc, vs = quantize_kv(jnp.zeros((n_pool, bs, kv, hd)), fmt,
                             block_size=qb)
        return lambda: paged_flash_decode_attention(
            q, kc, vc, jnp.asarray(tables), jnp.asarray(lens),
            kv_quant=fmt, k_scales=ks, v_scales=vs, quant_block=qb,
            value_dtype=dtype, interpret=True,
        )

    return _capture_cases([
        # nf4 at the default block 64 (one scale block per row)
        ("nf4_gqa_pool32", run(4, 32, 16, 2, 64, 14, (6, 3, 1, 6),
                               "nf4", 64)),
        # remainder scale block: hd=80 with quant_block=64 -> 2 blocks,
        # the second covering only 16 of 64 elements
        ("nf4_hd80_remainder", run(2, 16, 16, 4, 80, 8, (7, 2),
                                   "nf4", 64)),
        # int8 keeps head_dim at int8 dtype; small quant_block remainder
        ("int8_bs16", run(2, 16, 16, 8, 24, 8, (7, 2), "int8", 16)),
    ])


def _demo_adapter(d: int, dims, dtype):
    from repro.core.quanta import QuantaAdapter

    return QuantaAdapter.create(
        jax.random.PRNGKey(0), d, d, dims_in=dims, dtype=dtype,
    )


@register_kernel("quanta_apply")
def _build_quanta_apply():
    from repro.kernels.ops import quanta_apply_fused

    def run(rows, d, dims, block_rows, dtype=jnp.bfloat16):
        ad = _demo_adapter(d, dims, jnp.float32)
        x = jnp.zeros((rows, d), dtype)
        return lambda: quanta_apply_fused(
            x, ad, block_rows=block_rows, interpret=True
        )

    return _capture_cases([
        # qwen2 hidden (896 = 16*8*7) at the default row blocking
        ("qwen2_d896", run(512, 896, (16, 8, 7), 256)),
        # 4-axis scheme (paper N=4), rows needing pad
        ("n4_d256_pad", run(100, 256, (4, 4, 4, 4), 64)),
    ])


@register_kernel("quanta_linear")
def _build_quanta_linear():
    from repro.kernels.ops import quanta_linear_fused

    def run(rows, d, dims, block_rows, block_cols, dtype=jnp.bfloat16):
        ad = _demo_adapter(d, dims, jnp.float32)
        x = jnp.zeros((rows, d), dtype)
        w = jnp.zeros((d, d), dtype)
        return lambda: quanta_linear_fused(
            x, w, ad, block_rows=block_rows, block_cols=block_cols,
            interpret=True,
        )

    return _capture_cases([
        ("qwen2_d896", run(256, 896, (16, 8, 7), 128, 448)),
        ("d512_cols256", run(128, 512, (8, 8, 8), 128, 256)),
    ])


# operands: x (0), packed codes (uint8/int8), per-block scales, then the
# normalizers — outputs match x, accumulation is fp32 inside the dot.
# The one scratch buffer is the dequantized weight tile, held in the
# activation dtype (the dot's operand), not an accumulator.
@register_kernel("quantized_matmul", fp32_scratch=False)
def _build_quantized_matmul():
    from repro.core.quantize import quantize_linear
    from repro.kernels.quantized_matmul import quantized_matmul

    def run(rows, d_in, d_out, fmt, block_size, block_rows, block_cols,
            normalize=None, dtype=jnp.bfloat16):
        w = jnp.asarray(
            np.linspace(-1, 1, d_in * d_out, dtype=np.float32).reshape(
                d_in, d_out
            )
        )
        qw = quantize_linear(w, fmt, block_size=block_size,
                             normalize=normalize)
        x = jnp.zeros((rows, d_in), dtype)
        return lambda: quantized_matmul(
            x, qw, block_rows=block_rows, block_cols=block_cols,
            interpret=True,
        )

    return _capture_cases([
        # qwen2 hidden at the default nf4 blocking; grid (2, 2)
        ("nf4_d896", run(256, 896, 896, "nf4", 64, 128, 448)),
        # block-remainder everywhere: 100 rows pad to 128, d_in=200 leaves
        # a ragged final scale block (200 % 64 != 0), d_out=136 under-fills
        # the column block
        ("int8_remainder", run(100, 200, 136, "int8", 64, 128, 512,
                               dtype=jnp.float32)),
        # column padding path (640 % 512 != 0 -> packed/scales zero-pad)
        # with row/col normalizers as extra operands
        ("nf4_colpad_norms", run(64, 256, 640, "nf4", 64, 64, 512,
                                 normalize="rowcol")),
    ])


# Banked-gather LoRA (multi-tenant serving): operand 0 is the int32
# scalar-prefetch adapter_ids; x / bank-stacked A / bank-stacked B
# [/ shared W for the fused variant] follow.  The A/B index maps address
# bank rows through the prefetched ids (the Punica-style gather) — the
# checker walks them with a synthetic prefetch vector.
@register_kernel("banked_gather")
def _build_banked_gather():
    from repro.kernels.banked_gather import (
        banked_lora_delta,
        banked_lora_linear,
    )

    def run(n_slots, seq, d_in, d_out, g, rank, block_cols, fuse,
            dtype=jnp.float32):
        x = jnp.zeros((n_slots, seq, d_in), dtype)
        a = jnp.zeros((g + 1, d_in, rank), dtype)
        b = jnp.zeros((g + 1, rank, d_out), dtype)
        ids = jnp.asarray(np.arange(n_slots) % (g + 1), jnp.int32)
        if fuse:
            w = jnp.zeros((d_in, d_out), dtype)
            return lambda: banked_lora_linear(
                x, w, a, b, ids, scale=2.0, block_cols=block_cols,
                interpret=True,
            )
        return lambda: banked_lora_delta(
            x, a, b, ids, scale=2.0, block_cols=block_cols, interpret=True,
        )

    return _capture_cases([
        # decode tick at qwen2-0.5b hidden, fused base+gather; grid (8, 2)
        ("fused_decode_d896", run(8, 1, 896, 896, 4, 8, 448, True)),
        # prefill wave, delta-only (quantized base keeps its own kernel)
        ("delta_prefill_s64", run(4, 64, 896, 896, 4, 8, 448, False)),
        # column remainder: d_out=136 pads to 3 blocks of 48 and slices
        ("fused_remainder", run(4, 1, 200, 136, 2, 4, 48, True)),
    ])
