"""Fused dequant-matmul Pallas kernel for blockwise-quantized weights.

``y = x @ dequant(Wq)`` with the dense weight never materialized in HBM:
the grid walks output column tiles (outer) and activation row blocks
(inner).  At the first row block of a column tile the kernel dequantizes
the PACKED tile (uint8 NF4 codes or int8, plus per-block scales) into a
VMEM scratch in the activation dtype, one scale block of rows at a time;
every row block of that column then runs one full-K matmul against the
resident tile with fp32 accumulation on the MXU.  The HBM weight stream
drops from ``d_in * d_out * itemsize`` to the quantized bytes (~4x for
NF4 of bf16), and the dequantization runs once per tile, not once per
row block.

Numerics contract: the kernel equals ``core.quantize.matmul_ref`` —
dequantize-then-matmul in the activation dtype.  Every weight element
goes through the same fp32 operations (``code_planes`` then
``scale_values``) in both paths, and the contraction axis is never
split: each output element is ONE ``dot_general`` over the full ``d_in``
with ``preferred_element_type=f32``.

The wrapper pads rows and output columns to the block grid (zero-padded
weight columns cannot perturb kept columns) and picks the widest column
tile whose grid step fits the VMEM budget.  It never falls back to the
reference: a weight no tile fits raises.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import (
    QuantizedLinear,
    code_planes,
    matmul_ref,
    scale_values,
)
from repro.kernels.dispatch import resolve_interpret
from repro.kernels.vmem import VMEM_BUDGET_BYTES, vmem_footprint

__all__ = [
    "quantized_matmul",
    "quantized_matmul_kernel_call",
    "quantized_matmul_ref",
    "quantized_tile_cols",
]

# Re-exported so kernel-vs-reference callers (tests, the analysis
# registry) name both paths from one module.
quantized_matmul_ref = matmul_ref

# Column tiles tried widest first (lane multiples of 128); a tile that
# divides d_out is preferred, since padding the columns copies the weight.
_COL_TILES = (512, 384, 256, 128)


def _kernel(x_ref, q_ref, s_ref, *refs, fmt, block_size, d_in, strip,
            has_row, has_col):
    row_ref = refs[0] if has_row else None
    col_ref = refs[int(has_row)] if has_col else None
    o_ref, w_ref = refs[int(has_row) + int(has_col):]
    kp = q_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _dequant():
        # The packed rows hold one plane (int8) or two nibble planes
        # (nf4: natural rows [0, d_in/2) high, [d_in/2, d_in) low).  A
        # strip never crosses a scale block or a plane, so it takes one
        # scale row.
        col = col_ref[...] if has_col else None

        def body(k, carry):
            r = pl.multiple_of(k * strip, strip)
            planes = code_planes(q_ref[pl.ds(r, strip), :], fmt)
            for p, vals in enumerate(planes):
                row0 = p * kp + r                      # natural row
                s = s_ref[pl.ds(row0 // block_size, 1), :]
                rn = row_ref[pl.ds(row0, strip), :] if has_row else None
                w = scale_values(vals, s, rn, col)
                w_ref[pl.ds(row0, strip), :] = w.astype(w_ref.dtype)
            return carry

        jax.lax.fori_loop(0, kp // strip, body, 0)

    o_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def _strip_rows(kp: int, block_size: int) -> int:
    """Rows dequantized per loop step: a divisor of both the packed rows
    and the scale block, so no strip straddles a plane or a block."""
    return math.gcd(kp, block_size)


def quantized_matmul_kernel_call(
    x: jnp.ndarray,                       # (rows, d_in)
    packed: jnp.ndarray,                  # (d_in//2 | d_in, d_out)
    scales: jnp.ndarray,                  # (nb, d_out)
    row_norm: Optional[jnp.ndarray],      # (d_in, 1) or None
    col_norm: Optional[jnp.ndarray],      # (1, d_out) or None
    *,
    fmt: str,
    block_size: int,
    block_rows: int = 128,
    block_cols: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    # interpret=None auto-detects via dispatch.on_cpu (TPU callers
    # bypassing the quantized_matmul wrapper must not silently run
    # interpret mode)
    interpret = resolve_interpret(interpret)
    rows, d_in = x.shape
    d_out = packed.shape[1]
    kp = packed.shape[0]
    if d_in != kp * (2 if fmt == "nf4" else 1):
        raise ValueError(f"packed rows {kp} do not match d_in={d_in}")
    nb = scales.shape[0]
    block_cols = min(block_cols, d_out)
    if rows % block_rows or d_out % block_cols:
        raise ValueError("rows/cols not divisible by block sizes")
    grid = (d_out // block_cols, rows // block_rows)

    in_specs = [
        pl.BlockSpec((block_rows, d_in), lambda j, i: (i, 0)),
        pl.BlockSpec((kp, block_cols), lambda j, i: (0, j)),
        pl.BlockSpec((nb, block_cols), lambda j, i: (0, j)),
    ]
    operands = [x, packed, scales]
    if row_norm is not None:
        in_specs.append(pl.BlockSpec((d_in, 1), lambda j, i: (0, 0)))
        operands.append(row_norm)
    if col_norm is not None:
        in_specs.append(pl.BlockSpec((1, block_cols), lambda j, i: (0, j)))
        operands.append(col_norm)

    kernel = functools.partial(
        _kernel, fmt=fmt, block_size=block_size, d_in=d_in,
        strip=_strip_rows(kp, block_size),
        has_row=row_norm is not None, has_col=col_norm is not None,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, block_cols), lambda j, i: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, d_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((d_in, block_cols), x.dtype)],
        # the row axis reuses the column tile dequantized at its first
        # step, so it must run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="quantized_matmul",
    )(*operands)


def _footprint(qw: QuantizedLinear, block_rows: int, bc: int,
               dtype_bytes: int) -> int:
    """VMEM bytes of one grid step: double-buffered x / packed / scale /
    norm / output blocks, the dequantized-tile scratch, and one fp32
    strip of dequant temporaries."""
    d_in = qw.shape[-2]
    kp = qw.packed.shape[-2]
    nb = qw.scales.shape[-2]
    strip = _strip_rows(kp, qw.block_size)
    piped = [
        ((block_rows, d_in), dtype_bytes),       # x tile
        ((kp, bc), 1),                           # packed tile
        ((nb, bc), jnp.dtype(qw.scales.dtype).itemsize),
        ((block_rows, bc), dtype_bytes),         # output tile
    ]
    if qw.row_norm is not None:
        piped.append(((d_in, 1), 4))
    if qw.col_norm is not None:
        piped.append(((1, bc), 4))
    return 2 * vmem_footprint(piped) + vmem_footprint([
        ((d_in, bc), dtype_bytes),               # dequantized tile
        ((strip, bc), 4 * 4),                    # fp32 strip temporaries
    ])


def quantized_tile_cols(qw: QuantizedLinear, block_rows: int,
                        dtype_bytes: int = 2) -> int:
    """Widest column tile whose grid step fits the VMEM budget (the
    contract checker's arithmetic, ``kernels.vmem.vmem_footprint``).
    Raises when even the narrowest tile does not fit."""
    d_out = qw.shape[-1]
    fits = [
        bc for bc in sorted({min(t, d_out) for t in _COL_TILES}, reverse=True)
        if _footprint(qw, block_rows, bc, dtype_bytes) <= VMEM_BUDGET_BYTES
    ]
    for bc in fits:
        if d_out % bc == 0:
            return bc
    if fits:
        return fits[0]
    raise ValueError(
        f"no column tile of a {qw.shape} {qw.fmt} weight fits the "
        f"{VMEM_BUDGET_BYTES}-byte VMEM budget at {block_rows} rows"
    )


def quantized_matmul(
    x: jnp.ndarray,
    qw: QuantizedLinear,
    *,
    block_rows: int = 128,
    block_cols: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused dequant-matmul ``x @ dequant(qw)`` for a 2-D quantized
    weight, equal to :func:`quantized_matmul_ref`.  ``block_rows`` caps
    the row block (small batches use one block of their own padded
    height); ``block_cols=None`` picks the widest tile that fits VMEM.
    ``interpret=None`` resolves inside the kernel call (interpret on
    CPU, Mosaic on TPU)."""
    if qw.ndim != 2:
        raise ValueError(f"quantized_matmul needs a 2-D weight, got "
                         f"{qw.shape}")
    d_in, d_out = qw.shape
    batch = x.shape[:-1]
    xf = x.reshape(-1, d_in)
    rows = xf.shape[0]
    # a decode batch of a few rows runs as one block of its own
    # (sublane-padded) height rather than padding to ``block_rows``
    block_rows = min(block_rows, -(-rows // 16) * 16)
    dtype_bytes = jnp.dtype(x.dtype).itemsize
    bc = (quantized_tile_cols(qw, block_rows, dtype_bytes)
          if block_cols is None else min(block_cols, d_out))
    pad_r = (-rows) % block_rows
    if pad_r:
        xf = jnp.pad(xf, ((0, pad_r), (0, 0)))
    pad_c = (-d_out) % bc
    packed, scales = qw.packed, qw.scales
    col = qw.col_norm
    if pad_c:
        packed = jnp.pad(packed, ((0, 0), (0, pad_c)))
        scales = jnp.pad(scales, ((0, 0), (0, pad_c)))
        if col is not None:
            col = jnp.pad(col, ((0, pad_c),))
    row = qw.row_norm
    out = quantized_matmul_kernel_call(
        xf, packed, scales,
        row.reshape(d_in, 1) if row is not None else None,
        col.reshape(1, d_out + pad_c) if col is not None else None,
        fmt=qw.fmt, block_size=qw.block_size,
        block_rows=block_rows, block_cols=bc, interpret=interpret,
    )
    return out[:rows, :d_out].reshape(*batch, d_out)
