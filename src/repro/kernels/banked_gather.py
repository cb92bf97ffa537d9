"""Fused banked-gather LoRA kernel: per-slot adapter row gather + matmul.

Multi-tenant serving applies, per batch slot, the adapter row named by
that slot's ``adapter_ids`` entry (``repro.core.bank``).  The reference
path gathers each group's factors with ``jnp.take`` and runs the delta
under ``vmap`` — which materializes a per-slot copy of the gathered
factors in HBM before the matmuls see them.  This kernel fuses the gather
into the adapted matmul instead (the Punica/SGMV grouped-LoRA trick):
``adapter_ids`` ride as a scalar-prefetch operand, and each grid step's
BlockSpec index map addresses the bank row directly —

    y[s] = x[s] @ W + scale * ((x[s] @ A[ids[s]]) @ B[ids[s]])

so a slot's factors are DMA'd from their resident bank row straight into
VMEM, once, with no gathered intermediate.  Row 0 of the bank is the
neutral (all-zeros) entry, so base-model slots (id 0) add an exact zero
delta — the same contract the reference vmap path honors.

Scope: the LoRA factor form (the family this fusion pays for — tiny
``(d_in, r)`` / ``(r, d_out)`` tiles amortized over the base GEMM).
Other families (QuanTA chains, DoTA) keep the reference gather; routing
is per-group via the ``Adapter.banked_delta`` / ``Adapter.banked_linear``
protocol hooks, so the bank never dispatches on adapter classes.

Grid ``(B, d_out // block_cols)``; per step VMEM holds the slot's
``x (S, d_in)`` tile, its gathered ``A (d_in, r)`` / ``B (r, Bc)`` rows,
a ``W (d_in, Bc)`` column tile (fused variant), and the ``(S, Bc)``
output: full-K f32 dots, bitwise-aligned with the monolithic reference
matmuls (pinned by ``tests/test_banked_gather.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import resolve_interpret
from repro.kernels.vmem import VMEM_BUDGET_BYTES, vmem_footprint

__all__ = [
    "banked_lora_delta",
    "banked_lora_linear",
    "banked_vmem_ok",
]


def _kernel(ids_ref, x_ref, a_ref, b_ref, *rest, scale: float,
            fuse_base: bool):
    del ids_ref  # consumed by the BlockSpec index maps
    if fuse_base:
        w_ref, o_ref = rest
    else:
        (o_ref,) = rest
    h = x_ref[0]                                   # (S, d_in), x dtype
    # mirror LoraAdapter.delta's numerics: the factored matmuls run in
    # the adapter dtype, scale multiplies the product, and the delta is
    # cast back to the activation dtype before the base add.  Every dot
    # accumulates in fp32 (Mosaic has no sub-32-bit accumulator) and is
    # rounded to its operand dtype, as XLA rounds ``a @ b``.
    za = _dot(h.astype(a_ref.dtype), a_ref[0])     # (S, r)
    d = (scale * _dot(za, b_ref[0])).astype(h.dtype)
    if fuse_base:
        d = _dot(h, w_ref[...]) + d
    o_ref[0] = d


def _dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    ).astype(a.dtype)


def banked_vmem_ok(seq: int, d_in: int, d_out: int, rank: int,
                   block_cols: int, *, fuse_base: bool,
                   dtype_bytes: int = 4) -> bool:
    """One grid step's VMEM working set fits the budget?  Same arithmetic
    the contract checker verifies (``repro.analysis.kernels``)."""
    bc = min(block_cols, d_out)
    blocks = [
        ((seq, d_in), dtype_bytes),       # x tile
        ((d_in, rank), dtype_bytes),      # gathered A row
        ((rank, bc), dtype_bytes),        # gathered B row tile
        ((seq, bc), dtype_bytes),         # output tile
    ]
    if fuse_base:
        blocks.append(((d_in, bc), dtype_bytes))   # W column tile
    return vmem_footprint(blocks) <= VMEM_BUDGET_BYTES


def _call(x, a, b, ids, w, *, scale: float, block_cols: int,
          interpret: Optional[bool]):
    interpret = resolve_interpret(interpret)
    n_slots, seq, d_in = x.shape
    rank, d_out = b.shape[1], b.shape[2]
    fuse_base = w is not None

    bc = min(block_cols, d_out)
    pad = (-d_out) % bc
    if pad:
        b = jnp.pad(b, ((0, 0), (0, 0), (0, pad)))
        if fuse_base:
            w = jnp.pad(w, ((0, 0), (0, pad)))
    n_cb = (d_out + pad) // bc

    ids = jnp.asarray(ids, jnp.int32)
    in_specs = [
        pl.BlockSpec((1, seq, d_in), lambda i, j, ids_: (i, 0, 0)),
        pl.BlockSpec((1, d_in, rank), lambda i, j, ids_: (ids_[i], 0, 0)),
        pl.BlockSpec((1, rank, bc), lambda i, j, ids_: (ids_[i], 0, j)),
    ]
    operands = [ids, x, a, b]
    if fuse_base:
        in_specs.append(pl.BlockSpec((d_in, bc), lambda i, j, ids_: (0, j)))
        operands.append(w)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,            # per-slot local bank rows
        grid=(n_slots, n_cb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, seq, bc), lambda i, j, ids_: (i, 0, j)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, fuse_base=fuse_base),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots, seq, d_out + pad), x.dtype),
        interpret=interpret,
        name="banked_lora_linear" if fuse_base else "banked_lora_delta",
    )(*operands)
    return out[:, :, :d_out] if pad else out


def _norm_x(x: jnp.ndarray):
    """(B, d) -> (B, 1, d); (B, S, d) passes through."""
    if x.ndim == 2:
        return x[:, None, :], True
    if x.ndim == 3:
        return x, False
    raise ValueError(f"banked gather expects (B, d) or (B, S, d), got {x.shape}")


def banked_lora_delta(
    x: jnp.ndarray,               # (B, S, d_in) or (B, d_in)
    a: jnp.ndarray,               # (G+1, d_in, r) bank-stacked A
    b: jnp.ndarray,               # (G+1, r, d_out) bank-stacked B
    ids: jnp.ndarray,             # (B,) local bank rows, 0 = neutral
    *,
    scale: float,
    block_cols: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Gathered per-slot LoRA delta (no base): drop-in for the reference
    ``jnp.take`` + vmap ``delta`` path."""
    xn, squeezed = _norm_x(x)
    out = _call(xn, a, b, ids, None, scale=scale, block_cols=block_cols,
                interpret=interpret)
    return out[:, 0, :] if squeezed else out


def banked_lora_linear(
    x: jnp.ndarray,               # (B, S, d_in) or (B, d_in)
    w: jnp.ndarray,               # (d_in, d_out) shared dense base
    a: jnp.ndarray,
    b: jnp.ndarray,
    ids: jnp.ndarray,
    *,
    scale: float,
    block_cols: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused ``x @ W + gathered LoRA delta`` — base matmul and gather in
    one kernel pass over the slot's VMEM-resident ``x`` tile."""
    xn, squeezed = _norm_x(x)
    if w.shape != (xn.shape[-1], b.shape[2]):
        raise ValueError(f"w {w.shape} incompatible with x/b")
    out = _call(xn, a, b, ids, w, scale=scale, block_cols=block_cols,
                interpret=interpret)
    return out[:, 0, :] if squeezed else out
