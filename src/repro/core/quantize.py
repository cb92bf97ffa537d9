"""Blockwise frozen-base weight quantization (NF4 / int8) for serving.

Decode is bandwidth-bound (ROADMAP §Perf log B4/B5): after paging cut the
KV bytes, the frozen base's weight stream is the dominant HBM term per
decode tick.  QuanTA's core selling point — adaptation that leaves the
base *frozen* — composes directly with a quantized base, the standard
production PEFT deployment (the QLoRA pattern: 4-bit frozen weights +
full-precision adapters).  This module provides:

* :class:`QuantizedLinear` — the packed storage format for one frozen
  linear weight: blockwise NF4 (4-bit normal-float codebook, two codes
  per byte) or int8, per-block fp16/fp32 absmax scales along ``d_in``,
  and optional NoWag-style row/column normalizers.  A registered
  dataclass pytree, so it stacks along a leading layer axis and slices
  under ``jax.lax.scan`` exactly like the dense ``(L, d_in, d_out)``
  weights it replaces.
* :func:`quantize_linear` / :func:`dequantize` — the lossy encode and
  the exact decode.  :func:`code_planes` and :func:`scale_values` are
  THE elementwise dequantization: ``dequant_values`` (the reference)
  and the Pallas kernels build on them, so the kernel-vs-reference
  equality gates (tests/test_quantize.py) have one implementation to
  agree with.
* :func:`quantize_params` — quantize every projection leaf a model
  applies through ``peft_linear`` (``QUANT_TARGETS``); embeddings, the
  LM head, norms, biases, convs, and raw-matmul projections (Mamba2's
  ``bc_proj``/``dt_proj``, Griffin's ``w_a``/``w_x``) stay dense.
* :func:`base_matmul` — the base-weight matmul every adapter ``apply``
  routes through: plain arrays keep the exact ``x @ w`` the models
  always ran; ``QuantizedLinear`` dispatches to the fused dequant-matmul
  kernel (``backend="pallas"``) or the dequantize-then-matmul reference.
* blockwise scale/round helpers shared with the int8 gradient
  compressor (``optim.compress``) — one scale/round implementation for
  both wire-format gradients and frozen weights.

Quantization itself is lossy; everything downstream of the stored codes
is exact: kernel == reference bitwise, and the quantized base + fp
adapter composition is the same contract as ``quanta_linear_fused``
(adapter delta applied on top of the base matmul).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "NF4_CODEBOOK",
    "QUANT_TARGETS",
    "QuantizedLinear",
    "base_matmul",
    "blockwise_absmax",
    "blockwise_round",
    "blockwise_scales",
    "code_planes",
    "dequant_values",
    "dequantize",
    "ensure_dense",
    "expand_scales",
    "fake_quantize_kv",
    "kv_dequant_values",
    "matmul_ref",
    "quantize_kv",
    "quantize_linear",
    "quantize_params",
    "quantized_nbytes",
    "scale_values",
]

# The 16-level NF4 codebook (QLoRA, Dettmers et al. 2023): quantiles of a
# standard normal rescaled to span exactly [-1, 1], with 0.0 exactly
# representable (code 7).  Block absmax scaling maps each weight block
# into this range.
NF4_CODEBOOK = np.array(
    [
        -1.0, -0.6961928009986877, -0.5250730514526367,
        -0.39491748809814453, -0.28444138169288635, -0.18477343022823334,
        -0.09105003625154495, 0.0, 0.07958029955625534,
        0.16093020141124725, 0.24611230194568634, 0.33791524171829224,
        0.44070982933044434, 0.5626170039176941, 0.7229568362236023, 1.0,
    ],
    np.float32,
)
# Decision boundaries for nearest-code assignment: midpoints between
# adjacent codebook entries.
_NF4_BOUNDS = (NF4_CODEBOOK[:-1] + NF4_CODEBOOK[1:]) / 2.0

# Projection leaves applied through peft_linear/base_matmul in all three
# model families (transformer/griffin/mamba2).  NOT quantizable: embed /
# lm_head (gather + transpose-reuse), norms/biases/convs, MoE expert
# stacks (ndim 4, applied via einsum), and the raw-matmul projections
# (mamba2 bc_proj/dt_proj, griffin w_a/w_x).
QUANT_TARGETS = (
    "q_proj", "k_proj", "v_proj", "o_proj",
    "gate_proj", "up_proj", "down_proj",
    "rec_proj", "z_proj", "x_proj", "out_proj",
)


# ---------------------------------------------------------------------------
# Shared blockwise scale/round helpers (also used by optim.compress)
# ---------------------------------------------------------------------------

def _norm_axis(ndim: int, axis: int) -> int:
    return axis % ndim


def blockwise_absmax(x: jnp.ndarray, block_size: Optional[int],
                     axis: int = 0) -> jnp.ndarray:
    """Per-block absmax along ``axis``.

    ``block_size=None`` treats the whole axis as one block (the
    per-tensor case, after flattening).  A remainder block (axis extent
    not divisible by ``block_size``) is zero-padded — absmax is
    unaffected and the pad rows are never dequantized.
    """
    axis = _norm_axis(x.ndim, axis)
    n = x.shape[axis]
    bs = n if block_size is None else block_size
    nb = -(-n // bs)
    pad = nb * bs - n
    if pad:
        cfg = [(0, 0)] * x.ndim
        cfg[axis] = (0, pad)
        x = jnp.pad(x, cfg)
    shp = x.shape
    x = x.reshape(shp[:axis] + (nb, bs) + shp[axis + 1:])
    return jnp.max(jnp.abs(x), axis=axis + 1)


def blockwise_scales(x: jnp.ndarray, block_size: Optional[int],
                     axis: int = 0, levels: float = 127.0,
                     eps: float = 1e-12) -> jnp.ndarray:
    """Per-block positive scales: ``max(absmax, eps) / levels``.

    ``levels=127`` for symmetric int8, ``levels=1`` for codebooks that
    span ``[-1, 1]`` (NF4).  The eps clamp keeps all-zero blocks from
    producing a 0 (or NaN-generating) scale — scale positivity is a
    pinned property (tests/test_quantize.py).
    """
    return jnp.maximum(blockwise_absmax(x, block_size, axis), eps) / levels


def expand_scales(scales: jnp.ndarray, block_size: int, n: int,
                  axis: int = 0) -> jnp.ndarray:
    """Broadcast per-block scales back to ``n`` per-element rows along
    ``axis`` (remainder block: the repeat overshoots, then slices)."""
    axis = _norm_axis(scales.ndim, axis)
    s = jnp.repeat(scales, block_size, axis=axis)
    return jax.lax.slice_in_dim(s, 0, n, axis=axis)


def blockwise_round(x: jnp.ndarray, scales: jnp.ndarray, block_size: int,
                    axis: int = 0, levels: int = 127) -> jnp.ndarray:
    """Symmetric round-to-nearest against expanded per-block scales:
    ``clip(round(x / scale), -levels, levels)`` — the one rounding rule
    shared by gradient compression and int8 weight quantization."""
    axis = _norm_axis(x.ndim, axis)
    s = expand_scales(scales, block_size, x.shape[axis], axis)
    return jnp.clip(jnp.round(x / s), -levels, levels)


# ---------------------------------------------------------------------------
# The packed weight format
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantizedLinear:
    """One frozen linear weight in blockwise-quantized storage.

    Array children (stack/scan/vmap along a leading layer axis like the
    dense weight they replace):

    * ``packed`` — NF4: ``uint8 (..., d_in//2, d_out)``, two 4-bit codes
      per byte along ``d_in`` (high nibble = row ``i``, low nibble = row
      ``i + d_in // 2``);
      int8: ``int8 (..., d_in, d_out)``.
    * ``scales`` — ``(..., ceil(d_in/block_size), d_out)`` per-block
      absmax scales (fp32 or fp16).
    * ``row_norm`` / ``col_norm`` — optional ``(..., d_in)`` /
      ``(..., d_out)`` NoWag-style normalizers divided out before
      blockwise quantization and multiplied back at dequant (``None``
      children are skipped by every pytree transform).

    Static fields: ``fmt`` ("nf4" | "int8"), ``block_size``, and the
    original weight's dtype name (what ``dequantize`` restores and what
    ``.shape``/``.ndim`` describe).
    """

    packed: jnp.ndarray
    scales: jnp.ndarray
    fmt: str = dataclasses.field(metadata=dict(static=True))
    block_size: int = dataclasses.field(metadata=dict(static=True))
    dtype: str = dataclasses.field(metadata=dict(static=True))
    row_norm: Optional[jnp.ndarray] = None
    col_norm: Optional[jnp.ndarray] = None

    @property
    def d_in(self) -> int:
        return self.packed.shape[-2] * (2 if self.fmt == "nf4" else 1)

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.packed.shape[:-2] + (self.d_in, self.packed.shape[-1])

    @property
    def ndim(self) -> int:
        return self.packed.ndim


def quantized_nbytes(qw: QuantizedLinear) -> int:
    """Stored bytes of one quantized weight (packed + scales + norms)."""
    return sum(
        int(leaf.size * jnp.dtype(leaf.dtype).itemsize)
        for leaf in jax.tree_util.tree_leaves(qw)
    )


# ---------------------------------------------------------------------------
# Encode (lossy) / decode (exact)
# ---------------------------------------------------------------------------

def quantize_linear(
    w: jnp.ndarray,
    fmt: str = "nf4",
    *,
    block_size: int = 64,
    normalize: Optional[str] = None,
    scale_dtype: Any = jnp.float32,
) -> QuantizedLinear:
    """Blockwise-quantize a ``(d_in, d_out)`` (or layer-stacked
    ``(L, d_in, d_out)``) weight.  Blocks run along ``d_in`` — the
    contraction axis — so a column tile of the matmul only ever needs
    its own columns' scales.  ``normalize`` in {None, "row", "col",
    "rowcol"} divides out RMS row/column normalizers first.
    """
    if w.ndim not in (2, 3):
        raise ValueError(f"expected a 2-D or layer-stacked 3-D weight, "
                         f"got ndim={w.ndim}")
    if fmt not in ("nf4", "int8"):
        raise ValueError(f"unknown quantization format {fmt!r}")
    if normalize not in (None, "row", "col", "rowcol"):
        raise ValueError(f"unknown normalize mode {normalize!r}")
    d_in = w.shape[-2]
    dtype_name = str(jnp.dtype(w.dtype).name)
    w32 = jnp.asarray(w, jnp.float32)
    row_norm = col_norm = None
    if normalize in ("row", "rowcol"):
        row_norm = jnp.maximum(
            jnp.sqrt(jnp.mean(w32 * w32, axis=-1)), 1e-12
        )
        w32 = w32 / row_norm[..., :, None]
    if normalize in ("col", "rowcol"):
        col_norm = jnp.maximum(
            jnp.sqrt(jnp.mean(w32 * w32, axis=-2)), 1e-12
        )
        w32 = w32 / col_norm[..., None, :]

    if fmt == "nf4":
        if d_in % 2:
            raise ValueError(
                f"NF4 packs two codes per byte along d_in; d_in={d_in} "
                "must be even"
            )
        scales = blockwise_scales(w32, block_size, axis=-2, levels=1.0)
        v = w32 / expand_scales(scales, block_size, d_in, axis=-2)
        codes = _nf4_encode(v)
        half = d_in // 2
        packed = (
            (codes[..., :half, :] << 4) | codes[..., half:, :]
        ).astype(jnp.uint8)
    else:
        scales = blockwise_scales(w32, block_size, axis=-2, levels=127.0)
        packed = blockwise_round(
            w32, scales, block_size, axis=-2, levels=127
        ).astype(jnp.int8)
    return QuantizedLinear(
        packed=packed, scales=scales.astype(scale_dtype), fmt=fmt,
        block_size=block_size, dtype=dtype_name,
        row_norm=row_norm, col_norm=col_norm,
    )


def _nf4_encode(v: jnp.ndarray) -> jnp.ndarray:
    """Nearest NF4 code (uint8) of each value in ``[-1, 1]``-scaled
    units: the number of decision bounds at or below it, which is what
    ``searchsorted(_NF4_BOUNDS, v, side="right")`` returns.  Summed
    elementwise, so no temporary is larger than ``v`` — a searchsorted
    over a layer-stacked KV wave or weight needs several times its size
    in device memory."""
    v = jnp.clip(v, -1.0, 1.0)
    codes = jnp.zeros(v.shape, jnp.uint8)
    for b in _NF4_BOUNDS.tolist():
        codes = codes + (v >= b).astype(jnp.uint8)
    return codes


def _nf4_lookup(codes: jnp.ndarray) -> jnp.ndarray:
    """fp32 :data:`NF4_CODEBOOK` values of integer NF4 codes.

    A 16-way select instead of a gather: the same values bit for bit,
    and it lowers inside a TPU kernel, where Mosaic supports no general
    gather and a kernel body cannot capture an array constant."""
    vals = jnp.zeros(codes.shape, jnp.float32)
    for c, v in enumerate(NF4_CODEBOOK.tolist()):
        vals = jnp.where(codes == c, jnp.float32(v), vals)
    return vals


def code_planes(packed: jnp.ndarray, fmt: str) -> List[jnp.ndarray]:
    """fp32 values, before scaling, of the code planes of a packed
    array: NF4 gives two, the high nibbles (the first half of the packed
    axis' elements) then the low nibbles (the second half); int8 gives
    the codes themselves.  Concatenating the planes along the packed
    axis restores the natural element order."""
    codes = packed.astype(jnp.int32)
    if fmt == "nf4":
        return [_nf4_lookup(codes >> 4), _nf4_lookup(codes & 0xF)]
    if fmt == "int8":
        return [codes.astype(jnp.float32)]
    raise ValueError(f"unknown quantization format {fmt!r}")


def scale_values(vals: jnp.ndarray, scales: jnp.ndarray,
                 row_norm: Optional[jnp.ndarray] = None,
                 col_norm: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``vals * scales [* row_norm] [* col_norm]`` in fp32, operands
    already broadcast to ``vals``' rows — the elementwise tail of every
    dequantization, shared by the reference and the kernel bodies."""
    w = vals * scales.astype(jnp.float32)
    if row_norm is not None:
        w = w * row_norm.astype(jnp.float32)
    if col_norm is not None:
        w = w * col_norm.astype(jnp.float32)
    return w


def dequant_values(
    packed: jnp.ndarray,
    scales: jnp.ndarray,
    row_norm: Optional[jnp.ndarray],
    col_norm: Optional[jnp.ndarray],
    *,
    fmt: str,
    block_size: int,
    d_in: int,
) -> jnp.ndarray:
    """Elementwise fp32 dequantization of a quantized weight.

    The reference the Pallas kernels are gated against: they dequantize
    row strips in VMEM through the same :func:`code_planes` /
    :func:`scale_values`, so every element is computed by the same fp32
    operations in the same order.
    """
    vals = jnp.concatenate(code_planes(packed, fmt), axis=-2)
    s = expand_scales(scales, block_size, d_in, axis=-2)
    return scale_values(
        vals, s,
        None if row_norm is None else row_norm[..., :, None],
        None if col_norm is None else col_norm[..., None, :],
    )


def dequantize(qw: QuantizedLinear, dtype: Any = None) -> jnp.ndarray:
    """Materialize the full dense weight (fp32 internally, cast to the
    stored dtype by default)."""
    w = dequant_values(
        qw.packed, qw.scales, qw.row_norm, qw.col_norm,
        fmt=qw.fmt, block_size=qw.block_size, d_in=qw.d_in,
    )
    return w.astype(qw.dtype if dtype is None else dtype)


def ensure_dense(w, dtype: Any = None):
    """Dense view of a maybe-quantized weight: pass-through for arrays,
    :func:`dequantize` for :class:`QuantizedLinear` (weight-coupled
    adapters like DoRA need the dense matrix)."""
    if isinstance(w, QuantizedLinear):
        return dequantize(w, dtype)
    return w


# ---------------------------------------------------------------------------
# KV-cache row quantization (serve.paging quantized pools)
# ---------------------------------------------------------------------------

def quantize_kv(
    x: jnp.ndarray, fmt: str, *, block_size: int = 64
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Blockwise-quantize KV rows along the LAST axis (head_dim).

    Per-token-row granularity: absmax blocks of ``block_size`` elements
    run along ``head_dim`` only, never spanning tokens — so quantizing a
    committed pool stripe, a single decode token, and a dense cache row
    all produce identical codes for identical rows (the property the
    paged-vs-dense-fake-quantized equality gate rests on).

    Returns ``(codes, scales)``: NF4 packs two 4-bit codes per byte
    along the last axis (``uint8 (..., d//2)``, high nibble = element
    ``i``, low nibble = element ``i + d // 2`` — the same nibble
    convention as :class:`QuantizedLinear`);
    int8 keeps ``int8 (..., d)``.  Scales are fp32
    ``(..., ceil(d/block_size))``.
    """
    if fmt not in ("nf4", "int8"):
        raise ValueError(f"unknown quantization format {fmt!r}")
    d = x.shape[-1]
    x32 = jnp.asarray(x, jnp.float32)
    if fmt == "nf4":
        if d % 2:
            raise ValueError(
                f"NF4 packs two codes per byte along head_dim; d={d} "
                "must be even"
            )
        scales = blockwise_scales(x32, block_size, axis=-1, levels=1.0)
        v = x32 / expand_scales(scales, block_size, d, axis=-1)
        codes = _nf4_encode(v)
        half = d // 2
        packed = ((codes[..., :half] << 4) | codes[..., half:]).astype(
            jnp.uint8
        )
    else:
        scales = blockwise_scales(x32, block_size, axis=-1, levels=127.0)
        packed = blockwise_round(
            x32, scales, block_size, axis=-1, levels=127
        ).astype(jnp.int8)
    return packed, scales.astype(jnp.float32)


def kv_dequant_values(
    codes: jnp.ndarray,
    scales: jnp.ndarray,
    *,
    fmt: str,
    block_size: int,
    d: int,
) -> jnp.ndarray:
    """Elementwise fp32 dequantization of KV rows quantized along the
    last axis — :func:`dequant_values` applied through a trailing
    singleton axis, so the reference gather path and the Pallas decode
    kernel share the ONE dequant implementation with the weight paths
    (the ISSUE's "same ``dequant_values`` feeds both paths" gate)."""
    return dequant_values(
        codes[..., None], scales[..., None], None, None,
        fmt=fmt, block_size=block_size, d_in=d,
    )[..., 0]


def fake_quantize_kv(
    x: jnp.ndarray, fmt: str, *, block_size: int = 64
) -> jnp.ndarray:
    """Quantize-dequantize round trip at the input dtype: the dense
    reference cache writes THIS, making dense decode token-for-token
    comparable to the paged quantized pools (which store the same codes
    and dequantize with the same :func:`dequant_values`)."""
    codes, scales = quantize_kv(x, fmt, block_size=block_size)
    return kv_dequant_values(
        codes, scales, fmt=fmt, block_size=block_size, d=x.shape[-1]
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# The base matmul every adapter apply routes through
# ---------------------------------------------------------------------------

def matmul_ref(x: jnp.ndarray, qw: QuantizedLinear) -> jnp.ndarray:
    """Dequantize-then-matmul reference: fp32 dequant, cast to the
    activation dtype, one monolithic dot with fp32 accumulation.

    This is the numerics contract the Pallas kernel is gated against
    bitwise — the kernel wrapper falls back to this exact function when
    a tile would overflow the VMEM budget, so dispatch never changes
    results.
    """
    if qw.ndim != 2:
        raise ValueError(f"matmul_ref needs a 2-D weight, got {qw.shape}")
    w = dequantize(qw, jnp.float32).astype(x.dtype)
    batch = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    out = jax.lax.dot_general(
        xf, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    return out.reshape(*batch, w.shape[-1])


def base_matmul(x: jnp.ndarray, w, backend: str = "reference") -> jnp.ndarray:
    """The frozen-base linear under every adapter: ``x @ w`` verbatim for
    dense weights (bit-identical to what the models always ran), fused
    dequant-matmul for :class:`QuantizedLinear`: ``backend="pallas"``
    runs a 2-D weight through the Pallas kernel (which raises rather
    than fall back where no tile fits VMEM); the reference backend and
    layer-stacked (3-D) weights, which no kernel serves, take
    :func:`matmul_ref`."""
    if isinstance(w, QuantizedLinear):
        if backend == "pallas" and w.ndim == 2:
            # deferred import: kernels.quantized_matmul imports the
            # dequant helpers from this module
            from repro.kernels.quantized_matmul import quantized_matmul

            return quantized_matmul(x, w)
        return matmul_ref(x, w)
    return x @ w


# ---------------------------------------------------------------------------
# Whole-tree quantization
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("fmt", "block_size", "normalize", "scale_dtype")
)
def _quantize_leaf(w, fmt, block_size, normalize, scale_dtype):
    # One layer at a time (a 2-D weight is a stack of one): quantizing a
    # whole (L, d_in, d_out) stack at once holds full-stack fp32
    # temporaries — 2.1 GB each for minicpm-2b's MLP stacks, beside the
    # bf16 model on one device.
    quant = functools.partial(quantize_linear, fmt=fmt,
                              block_size=block_size, normalize=normalize,
                              scale_dtype=scale_dtype)
    q = jax.lax.map(quant, w.reshape((-1,) + w.shape[-2:]))
    return jax.tree_util.tree_map(
        lambda a: a.reshape(w.shape[:-2] + a.shape[1:]), q
    )


def quantize_params(
    params: Dict[str, Any],
    fmt: str,
    *,
    block_size: int = 64,
    targets: Tuple[str, ...] = QUANT_TARGETS,
    normalize: Optional[str] = None,
    scale_dtype: Any = jnp.float32,
) -> Dict[str, Any]:
    """Quantize every targeted projection leaf of a model's parameter
    tree; all other leaves (embeddings, LM head, norms, biases, convs,
    MoE expert stacks) pass through untouched.  Idempotent: already
    quantized leaves are kept as-is, so an engine can accept
    pre-quantized params.
    """

    def walk(node):
        if not isinstance(node, dict):
            return node
        out = {}
        for key, val in node.items():
            if isinstance(val, dict):
                out[key] = walk(val)
            elif isinstance(val, QuantizedLinear):
                out[key] = val
            elif key in targets and getattr(val, "ndim", 0) in (2, 3):
                out[key] = _quantize_leaf(
                    val, fmt=fmt, block_size=block_size, normalize=normalize,
                    scale_dtype=scale_dtype,
                )
            else:
                out[key] = val
        return out

    return walk(params)
