"""Tiled Pallas flash-attention kernel with masked-block skipping.

The hottest op of every QuanTA fine-tuning and serving step is causal
attention.  The pure-JAX reference path (``models/attention.py``) computes
the full score row per query block and masks, so the compiled FLOPs
include the whole masked upper triangle and the fp32 score tensor is the
dominant HBM-traffic term of the roofline.  This kernel fuses the row
into VMEM and *skips* KV blocks that the causal (and sliding-window) mask
fully hides:

* grid ``(B, H, n_q_blocks, n_kv_blocks)`` with the KV dimension minor —
  the fp32 running max / denominator / output accumulator live in VMEM
  scratch that persists across the KV steps of one ``(b, h, i)`` row,
* online softmax: fp32 row statistics, probabilities cast to the value
  dtype for the PV matmul (the ``fast_softmax`` trade made structural —
  the score tensor never exists in HBM at all),
* **masked-block skipping**: for query block ``i`` only KV blocks in
  ``[j_lo(i), j_hi(i)]`` are computed — ``j_hi`` from causality, ``j_lo``
  from the sliding window.  Out-of-range grid steps predicate off all
  compute (``pl.when``) and their index maps clamp into the visible range
  so no new block is fetched: compiled FLOPs and HBM reads drop by the
  masked-block fraction (~2x for causal self-attention, ``window/S`` for
  windowed layers),
* GQA layout: ``q (B, S, H, hd)`` with ``k/v (B, S, KV, hd)`` shared via
  the index map (``h // group``) — no KV duplication in HBM or VMEM.

Differentiation: the fused forward is wrapped in ``jax.custom_vjp``; the
backward recomputes attention blockwise in pure JAX (flash-style
recompute, numerically identical to the reference path) so training can
route through the kernel without a hand-written backward kernel.  A
Mosaic backward kernel is a recorded follow-up.

The decode variant (``flash_decode_attention``) handles ``q_len == 1``
over a per-slot ``cache_len``-masked KV cache: the length is dynamic, so
blocks past ``cache_len`` (and, with a window, before the window start)
are predicated off rather than grid-skipped; the dense per-slot cache
keeps the index maps static, and non-block-divisible cache lengths are
padded up (pad rows sit past every ``cache_len``) so the kernel stays
engaged at odd ``max_len``.  The paged variant
(``paged_flash_decode_attention``) consumes the serving engine's block
tables as a scalar-prefetch operand: its index maps gather KV blocks
through the table and unallocated blocks are true grid-level skips (no
DMA, predicated compute) — per-slot decode reads scale with allocated
blocks, not ``max_len``.

Interpret-on-CPU / Mosaic-on-TPU dispatch matches ``kernels/ops.py``
(``interpret=None`` auto-detects via ``dispatch.on_cpu``).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import code_planes
from repro.kernels.dispatch import MASK_VALUE, masked_softmax, resolve_interpret

__all__ = [
    "flash_attention",
    "flash_decode_attention",
    "paged_flash_decode_attention",
    "blockwise_reference_attention",
    "pad_to_q_block",
    "visible_block_fraction",
    "decode_visible_blocks",
]

# Running-statistic scratch is kept (bq, _STATS_LANES) and broadcast on
# store: TPU vector lanes are 128 wide, a (bq, 1) buffer would not tile.
_STATS_LANES = 128


# ---------------------------------------------------------------------------
# Block-visibility accounting (shared by the kernel grid and the roofline)
# ---------------------------------------------------------------------------

def _visible_j_range(q_lo, bq: int, bk: int, n_k: int,
                     window: Optional[int]):
    """Inclusive KV-block range ``[j_lo, j_hi]`` visible to the query
    block starting at ``q_lo``.  Works on Python ints (accounting) and
    traced scalars (kernel body / index maps) alike."""
    lo, hi = (max, min) if isinstance(q_lo, int) else (
        jnp.maximum, jnp.minimum
    )
    j_hi = hi((q_lo + bq - 1) // bk, n_k - 1)
    j_lo = 0 if window is None else lo(0, (q_lo - window + 1) // bk)
    return j_lo, j_hi


def visible_block_fraction(s: int, block_q: int, block_k: int,
                           window: Optional[int] = None) -> float:
    """Fraction of the ``n_q x n_k`` KV-block grid the kernel computes.

    This is the exact FLOPs ratio flash/reference for one forward pass
    (the reference path computes every block and masks); it feeds the
    roofline's analytic attention accounting.
    """
    bq = min(block_q, s)
    bk = min(block_k, s)
    n_q = -(-s // bq)
    n_k = -(-s // bk)
    visible = 0
    for i in range(n_q):
        j_lo, j_hi = _visible_j_range(i * bq, bq, bk, n_k, window)
        visible += max(0, j_hi - j_lo + 1)
    return visible / float(n_q * n_k)


def decode_visible_blocks(s_max: int, block_k: int,
                          window: Optional[int] = None) -> int:
    """Upper bound on KV blocks one decode step computes (full cache when
    dense; the window span + one boundary block when windowed)."""
    bk = min(block_k, s_max)
    n_k = -(-s_max // bk)
    if window is None:
        return n_k
    return min(n_k, -(-window // bk) + 1)


# ---------------------------------------------------------------------------
# Forward kernel (train / prefill): q_len == kv_len, causal (+ window)
# ---------------------------------------------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, n_k: int, scale: float,
                  window: Optional[int]):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = i * bq
    j_lo, j_hi = _visible_j_range(q_lo, bq, bk, n_k, window)

    @pl.when((j >= j_lo) & (j <= j_hi))
    def _step():
        q = q_ref[0, 0]                                    # (bq, hd)
        k = k_ref[0, 0]                                    # (bk, hd)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                          # (bq, bk) fp32
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kv_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = q_pos >= kv_pos
        if window is not None:
            mask &= (q_pos - kv_pos) < window
        s = jnp.where(mask, s, MASK_VALUE)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                             # fp32 in VMEM
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == n_k - 1)
    def _finalize():
        denom = l_ref[:, :1]
        o_ref[0, 0] = (
            acc_ref[...] / jnp.where(denom == 0.0, 1.0, denom)
        ).astype(o_ref.dtype)


def _flash_forward(q, k, v, *, window, scale, block_q, block_k, interpret):
    b, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    bq = min(block_q, s)
    bk = min(block_k, s)
    pad_q = (-s) % bq
    pad_k = (-s) % bk
    # Head-major views: a block then spans (rows, hd), the two tiled
    # dims Mosaic lays out in (8, 128) tiles — a size-1 block over the
    # head axis of the (B, S, H, hd) layout is not a legal TPU block.
    # Padded KV positions sit above every real query position, so the
    # causal mask hides them; padded query rows are sliced off below.
    qt = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    kt = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vt = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    n_q = (s + pad_q) // bq
    n_k = (s + pad_k) // bk

    def q_map(b_, h_, i, j):
        return (b_, h_, i, 0)

    def kv_map(b_, h_, i, j):
        # Clamp out-of-range steps onto the visible span: the revisited
        # block index issues no new fetch, so skipped steps cost neither
        # DMA nor (predicated off) compute.
        j_lo, j_hi = _visible_j_range(i * bq, bq, bk, n_k, window)
        return (b_, h_ // g, jnp.clip(j, j_lo, j_hi), 0)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, bq=bq, bk=bk, n_k=n_k, scale=scale, window=window
        ),
        grid=(b, h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), q_map),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), q_map),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _STATS_LANES), jnp.float32),   # running max
            pltpu.VMEM((bq, _STATS_LANES), jnp.float32),   # running denom
            pltpu.VMEM((bq, hd), jnp.float32),             # out accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)[:, :s]


# ---------------------------------------------------------------------------
# Blockwise pure-JAX reference — the "reference" backend of
# models/attention.py AND the kernel backward's recompute target (one
# implementation, so the VJP cannot drift from the parity oracle)
# ---------------------------------------------------------------------------

def pad_to_q_block(s: int, q_block: int) -> tuple:
    """Effective ``(q_block, padded_s)`` for a sequence of length ``s``.

    The query axis is padded up to a multiple of ``q_block`` (output rows
    are sliced off) instead of shrinking ``q_block`` to a divisor of
    ``s`` — the old divisor fallback degraded to ``q_block=1`` (an
    ``S``-step scan) for prime ``s``.
    """
    bq = min(q_block, s)
    return bq, s + ((-s) % bq)


def _block_attend(
    q: jnp.ndarray,          # (B, Bq, KV, G, hd)
    k: jnp.ndarray,          # (B, S, KV, hd)
    v: jnp.ndarray,          # (B, S, KV, hd)
    q_pos: jnp.ndarray,      # (Bq,) absolute positions of this query block
    kv_pos: jnp.ndarray,     # (S,)  absolute positions of keys
    window: Optional[int],
    softmax_scale: float,
    fast_softmax: bool,
) -> jnp.ndarray:
    scores = jnp.einsum(
        "bqkgh,bskh->bkgqs", q, k, preferred_element_type=jnp.float32
    ) * softmax_scale                                   # (B, KV, G, Bq, S)
    causal = q_pos[:, None] >= kv_pos[None, :]           # (Bq, S)
    if window is not None:
        causal &= q_pos[:, None] - kv_pos[None, :] < window
    scores = jnp.where(causal[None, None, None], scores, MASK_VALUE)
    probs = masked_softmax(scores, v.dtype, fast_softmax)
    return jnp.einsum("bkgqs,bskh->bqkgh", probs, v)     # (B, Bq, KV, G, hd)


def blockwise_reference_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_block: int = 512,
    window: Optional[int] = None,
    pos_offset: int = 0,
    fast_softmax: bool = False,
    softmax_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Pure-JAX causal attention scanned over query blocks.

    Full score rows are computed and masked (the flash kernel's FLOPs
    baseline); peak memory is O(q_block * S).  Returns ``(B, S, H, hd)``.
    """
    b, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(b, s, kv, g, hd)
    kv_pos = pos_offset + jnp.arange(s)

    bq, s_pad = pad_to_q_block(s, q_block)
    if s_pad != s:
        qg = jnp.pad(qg, ((0, 0), (0, s_pad - s), (0, 0), (0, 0), (0, 0)))
    n_blocks = s_pad // bq

    if n_blocks == 1:
        out = _block_attend(qg, k, v, kv_pos, kv_pos, window, scale,
                            fast_softmax)
        return out.reshape(b, s, h, hd)

    qb = qg.reshape(b, n_blocks, bq, kv, g, hd).transpose(1, 0, 2, 3, 4, 5)
    # Padded query rows get positions >= every kv position: fully causal-
    # visible garbage rows, sliced off after the scan.
    pos_b = (pos_offset + jnp.arange(s_pad)).reshape(n_blocks, bq)

    def body(_, inputs):
        q_i, pos_i = inputs
        out_i = _block_attend(q_i, k, v, pos_i, kv_pos, window, scale,
                              fast_softmax)
        return None, out_i

    _, out = jax.lax.scan(body, None, (qb, pos_b))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, s_pad, h, hd)
    return out[:, :s]


class _FlashSpec(NamedTuple):
    window: Optional[int]
    scale: float
    block_q: int
    block_k: int
    interpret: bool


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_attention(spec: _FlashSpec, q, k, v):
    return _flash_forward(
        q, k, v, window=spec.window, scale=spec.scale,
        block_q=spec.block_q, block_k=spec.block_k,
        interpret=spec.interpret,
    )


def _flash_fwd(spec, q, k, v):
    return _flash_attention(spec, q, k, v), (q, k, v)


def _banded_recompute(q, k, v, *, block_q, window, scale):
    """Backward recompute restricted to the visible KV band.

    Like the kernel, each query block only touches KV positions in
    ``[q_lo - window + 1, q_hi]`` — the masked upper triangle (and the
    region left of the window) is never recomputed, so the backward's
    FLOPs and score traffic shrink by the same visible fraction as the
    forward's.  Query blocks are unrolled (band extents are static per
    block); fine for the production ``S / q_block <= 8-64`` range.
    Output is identical to ``blockwise_reference_attention`` — excluded
    columns have exactly-zero probabilities and zero gradients.
    """
    b, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, s, kv, g, hd)
    bq, s_pad = pad_to_q_block(s, block_q)
    if s_pad != s:
        qg = jnp.pad(qg, ((0, 0), (0, s_pad - s), (0, 0), (0, 0), (0, 0)))
    outs = []
    for i in range(s_pad // bq):
        q_lo = i * bq
        kv_hi = min(s, q_lo + bq)
        kv_lo = 0 if window is None else max(0, q_lo - window + 1)
        out_i = _block_attend(
            qg[:, q_lo:q_lo + bq],
            k[:, kv_lo:kv_hi], v[:, kv_lo:kv_hi],
            q_lo + jnp.arange(bq), kv_lo + jnp.arange(kv_hi - kv_lo),
            window, scale, False,
        )
        outs.append(out_i.reshape(b, bq, h, hd))
    return jnp.concatenate(outs, axis=1)[:, :s]


def _flash_bwd(spec, residuals, g):
    # Flash-style recompute: no score tensor is saved between forward
    # and backward; gradients are the VJP of a banded blockwise
    # recompute that, like the kernel, skips fully-masked KV regions —
    # so the training backward shares the forward's FLOPs/traffic
    # savings (numerics identical to the reference backward).
    q, k, v = residuals
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _banded_recompute(
            q_, k_, v_, block_q=spec.block_q, window=spec.window,
            scale=spec.scale,
        ),
        q, k, v,
    )
    return vjp(g)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,               # (B, S, H, hd)
    k: jnp.ndarray,               # (B, S, KV, hd)
    v: jnp.ndarray,               # (B, S, KV, hd)
    *,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    pos_offset: int = 0,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Causal (optionally sliding-window) flash attention.

    Drop-in for the reference ``blockwise_causal_attention`` (same GQA
    layout, same masking semantics); differentiable via a blockwise
    recompute VJP.  ``pos_offset`` shifts queries and keys equally, so the
    relative mask is unchanged — accepted for API parity.
    Returns ``(B, S, H, hd)``.
    """
    del pos_offset
    b, s, h, hd = q.shape
    kv = k.shape[2]
    if h % kv:
        raise ValueError(f"n_heads {h} must be a multiple of n_kv_heads {kv}")
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    spec = _FlashSpec(
        window=window, scale=scale, block_q=block_q, block_k=block_k,
        interpret=resolve_interpret(interpret),
    )
    return _flash_attention(spec, q, k, v)


# ---------------------------------------------------------------------------
# Decode kernels: q_len == 1 over a per-slot length-masked KV cache, dense
# (``flash_decode_attention``) or paged (``paged_flash_decode_attention``).
#
# Both share one kernel body.  A grid step holds ONE KV block of ALL heads:
# the cache is viewed as ``(rows, tokens, KV * hd)`` (a free reshape of the
# ``(.., KV, hd)`` layout), so the block's last two dims are
# ``(block tokens, KV * hd)`` — the full lane extent, legal for Mosaic for
# any head count.  Per-slot lengths (and block tables) ride as scalar-
# prefetch operands.  The body takes one of two forms (``_all_heads_body``
# picks by shape and format):
#
# * per head (GQA, quantized pools): the kernel walks the heads with
#   static lane slices; per head it runs the same fp32 online softmax as
#   the forward kernel, on ``(g, hd) . (hd, bk)`` dots.
# * all heads (MHA, fp pools): one query row per KV head makes each head's
#   dots ``(1, hd) . (hd, bk)`` — dozens of dependent chains of tiny ops a
#   block, bound by latency.  Instead the query is laid out block-
#   diagonally, ``Qsel[h, h*hd + d] = q[h, d]`` (built once per slot), so
#   ONE ``(KV, KV*hd) . (KV*hd, bk)`` contraction gives every head's scores
#   (heads on sublanes, the off-diagonal products exact zeros), the online
#   softmax runs on all heads' rows at once, and ``p (KV, bk) . V (bk,
#   KV*hd)`` accumulates into a ``(KV, KV*hd)`` fp32 scratch whose diagonal
#   blocks are each head's output.  The products and fp32 accumulations are
#   those of the per-head dots; finalize keeps the diagonal (a select and a
#   sum of one value with zeros: exact).
#
# Quantized pools (nf4 / int8 codes + per-block fp32 scales) dequantize
# each head's tile in VMEM.  NF4 packs element ``i`` in the high nibble
# and element ``i + hd/2`` in the low nibble of byte ``i``; rather than
# re-joining the nibble planes (a lane shuffle), the kernel contracts each
# plane with its half of the query — ``q.k = q[:hd/2].k_hi + q[hd/2:].k_lo``
# — and accumulates the two output halves separately.
# ---------------------------------------------------------------------------

def _plane_scales(scales: jnp.ndarray, width: int, offset: int,
                  block: int) -> jnp.ndarray:
    """``(rows, nsb)`` per-block scales -> ``(rows, width)`` per lane for
    the plane whose lane ``i`` is head_dim element ``offset + i`` (block
    ``(offset + i) // block``) — broadcasts and selects only, no
    in-kernel repeat or reshape."""
    rows, nsb = scales.shape
    if nsb == 1:
        return jnp.broadcast_to(scales, (rows, width))
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    blk = (lane + offset) // block
    out = jnp.zeros((rows, width), jnp.float32)
    for b_ in range(nsb):
        out = jnp.where(blk == b_, scales[:, b_:b_ + 1], out)
    return out


# Cap on the all-heads body's fp32 working set, the ``(KV rows, KV * hd)``
# accumulator and ``(KV rows, bk)`` scores: it grows with the square of
# the head count, and so do the wasted off-diagonal products of its two
# contractions.  Every MHA configuration here fits (32 heads of 128 at
# bk = 512: 0.56 MiB).
_ALL_HEADS_ACC_BYTES = 1 << 20


def _head_rows(kv: int) -> int:
    """KV heads padded to whole sublane tiles (8 rows)."""
    return -(-kv // 8) * 8


def _all_heads_body(kv: int, g: int, fmt: Optional[str], bk: int,
                    width: int) -> bool:
    """Whether the decode kernel attends all heads of a KV block in one
    pass (see the section comment above) rather than head by head.

    Only for one query row per KV head (``g == 1``: MHA) over an fp cache
    (``fmt is None``; nf4 and int8 pools dequantize nibble planes per
    head), and while the all-heads fp32 working set — the ``(rows,
    width)`` accumulator and ``(rows, bk)`` scores — stays within
    ``_ALL_HEADS_ACC_BYTES``.  A pure function of shapes and format, so
    the serving engine can report the choice the kernel makes."""
    if g != 1 or fmt is not None:
        return False
    return _head_rows(kv) * (width + bk) * 4 <= _ALL_HEADS_ACC_BYTES


def _decode_kernel(*refs, n_prefetch: int, bk: int, n_k: int,
                   kv: int, hd: int, scale: float, window: Optional[int],
                   fmt: Optional[str], quant_block: int, value_dtype,
                   all_heads: bool):
    """One KV block of every head for one slot.  Operand order:
    ``prefetch..., q parts..., K refs..., V refs..., out parts...,
    m, l, acc parts...`` (+ ``qsel`` for the all-heads body) — a "part"
    is the whole head_dim (fp, int8) or one nibble plane's half of it
    (nf4)."""
    n_parts = 2 if fmt == "nf4" else 1
    n_kv_refs = 1 if fmt is None else 2          # codes (+ scales)
    len_ref = refs[n_prefetch - 1]
    pos = n_prefetch
    q_refs = refs[pos:pos + n_parts]
    pos += n_parts
    k_refs = refs[pos:pos + n_kv_refs]
    pos += n_kv_refs
    v_refs = refs[pos:pos + n_kv_refs]
    pos += n_kv_refs
    o_refs = refs[pos:pos + n_parts]
    pos += n_parts
    m_ref, l_ref = refs[pos], refs[pos + 1]
    acc_refs = refs[pos + 2:pos + 2 + n_parts]

    b = pl.program_id(0)
    j = pl.program_id(1)                                   # logical block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        for a in acc_refs:
            a[...] = jnp.zeros_like(a)
        if all_heads:
            qsel_ref = refs[-1]
            qsel_ref[...] = jnp.where(
                _diagonal(qsel_ref.shape, hd),
                jnp.broadcast_to(q_refs[0][0].astype(jnp.float32),
                                 qsel_ref.shape), 0.0,
            )

    length = len_ref[b]                                    # this slot's len
    q_pos = length - 1
    # Blocks past the slot's length (and, windowed, before the window)
    # predicate off all compute.  Paged: their index maps revisit the
    # slot's last allocated pool row, so they cost no DMA either.
    should = j * bk < length
    if window is not None:
        should &= (j + 1) * bk > q_pos - window + 1

    def token_mask(shape):
        """Keys of this block the query sees (length and window)."""
        kv_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = kv_pos < length
        if window is not None:
            mask &= (q_pos - kv_pos) < window
        return mask

    def online_softmax(s, m_prev, l_prev):
        """-> (p, alpha, m_new, l_new) for rows of scores ``s``."""
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        return p, alpha, m_new, l_new

    if all_heads:
        (q_ref,), (k_ref,), (v_ref,) = q_refs, k_refs, v_refs
        (o_ref,), (acc_ref,), qsel_ref = o_refs, acc_refs, refs[-1]

        @pl.when(should)
        def _step_all():
            s = jax.lax.dot_general(
                qsel_ref[...].astype(q_ref.dtype), k_ref[0],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                      # (rows, bk)
            s = jnp.where(token_mask(s.shape), s, MASK_VALUE)
            p, alpha, m_new, l_new = online_softmax(
                s, m_ref[:, :1], l_ref[:, :1]
            )
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
            v = v_ref[0]
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                              # (rows, width)

        @pl.when(j == n_k - 1)
        def _finalize_all():
            denom = l_ref[:, :1]
            denom = jnp.where(denom == 0.0, 1.0, denom)
            out = jnp.where(_diagonal(acc_ref.shape, hd),
                            acc_ref[...] / denom, 0.0)
            o_ref[0] = jnp.sum(out, axis=0, keepdims=True).astype(o_ref.dtype)

        return

    def head_parts(refs_, h):
        """The ``n_parts`` (bk, part width) value tiles of head ``h``."""
        if fmt is None:
            return [refs_[0][0, :, h * hd:(h + 1) * hd]]
        codes_ref, scale_ref = refs_
        width = hd // n_parts
        nsb = scale_ref.shape[-1] // kv
        codes = codes_ref[0, :, h * width:(h + 1) * width]
        scales = scale_ref[0, :, h * nsb:(h + 1) * nsb]
        return [
            (p * _plane_scales(scales, width, i * width, quant_block)
             ).astype(value_dtype)
            for i, p in enumerate(code_planes(codes, fmt))
        ]

    @pl.when(should)
    def _step():
        mask = None
        for h in range(kv):
            ks = head_parts(k_refs, h)
            s = None
            for q_ref, k in zip(q_refs, ks):
                part = jax.lax.dot_general(
                    q_ref[0, h], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )                                          # (G, bk)
                s = part if s is None else s + part
            if mask is None:
                mask = token_mask(s.shape)
            s = jnp.where(mask, s * scale, MASK_VALUE)
            p, alpha, m_new, l_new = online_softmax(
                s, m_ref[h][:, :1], l_ref[h][:, :1]
            )
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])
            for acc_ref, v in zip(acc_refs, head_parts(v_refs, h)):
                acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )

    @pl.when(j == n_k - 1)
    def _finalize():
        for h in range(kv):
            denom = l_ref[h][:, :1]
            denom = jnp.where(denom == 0.0, 1.0, denom)
            for o_ref, acc_ref in zip(o_refs, acc_refs):
                o_ref[0, h] = (acc_ref[h] / denom).astype(o_ref.dtype)


def _diagonal(shape, hd: int) -> jnp.ndarray:
    """``(rows, rows_kv * hd)`` mask: lane ``i`` of row ``h`` is head
    ``h``'s (``h * hd <= i < (h + 1) * hd``)."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) * hd
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= row) & (lane < row + hd)


def _decode_call(q, k_tiles, v_tiles, prefetch, *, block_map, bk, n_k,
                 kv, hd, scale, window, fmt, quant_block, value_dtype,
                 interpret, name):
    """Shared ``pallas_call`` of the dense and paged decode kernels.

    ``k_tiles`` / ``v_tiles``: the flattened ``(rows, tokens, lanes)``
    operands (fp values, or codes + scales); ``block_map(b, j, *prefetch)``
    gives the (row, token block) coordinates of slot ``b``'s ``j``-th KV
    block."""
    b, _, h, _ = q.shape
    g = h // kv
    width = k_tiles[0].shape[2]
    all_heads = _all_heads_body(kv, g, fmt, bk, width)
    if all_heads:
        # one (1, KV * hd) lane row a slot: the all-heads body's layout
        q_parts = [q.reshape(b, 1, width)]
    elif fmt == "nf4":
        qg = q.reshape(b, kv, g, hd)
        q_parts = [qg[..., :hd // 2], qg[..., hd // 2:]]
    else:
        q_parts = [q.reshape(b, kv, g, hd)]
    n_pre = len(prefetch)

    def q_map(b_, j, *pre):
        return (b_,) + (0,) * (q_parts[0].ndim - 1)

    def kv_map(b_, j, *pre):
        return (*block_map(b_, j, *pre), 0)

    in_specs = [pl.BlockSpec((1,) + p.shape[1:], q_map) for p in q_parts]
    for t in list(k_tiles) + list(v_tiles):
        in_specs.append(pl.BlockSpec((1, bk, t.shape[2]), kv_map))
    if all_heads:
        rows = _head_rows(kv)
        out_shapes = [(b, 1, width)]
        scratch = [
            pltpu.VMEM((rows, _STATS_LANES), jnp.float32),   # running max
            pltpu.VMEM((rows, _STATS_LANES), jnp.float32),   # running denom
            pltpu.VMEM((rows, width), jnp.float32),           # acc
            # Qsel: q values, exact in fp32 (every scratch is fp32)
            pltpu.VMEM((rows, width), jnp.float32),
        ]
    else:
        out_shapes = [(b, kv, g, p.shape[-1]) for p in q_parts]
        scratch = [
            pltpu.VMEM((kv, g, _STATS_LANES), jnp.float32),  # running max
            pltpu.VMEM((kv, g, _STATS_LANES), jnp.float32),  # running denom
        ] + [pltpu.VMEM((kv, g, p.shape[-1]), jnp.float32) for p in q_parts]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pre,
        grid=(b, n_k),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1,) + o[1:], q_map) for o in out_shapes],
        scratch_shapes=scratch,
    )
    outs = pl.pallas_call(
        functools.partial(
            _decode_kernel, n_prefetch=n_pre, bk=bk, n_k=n_k,
            kv=kv, hd=hd, scale=scale, window=window, fmt=fmt,
            quant_block=quant_block, value_dtype=value_dtype,
            all_heads=all_heads,
        ),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(o, q.dtype) for o in out_shapes],
        interpret=resolve_interpret(interpret),
        name=name,
    )(*prefetch, *q_parts, *k_tiles, *v_tiles)
    out = jnp.concatenate(outs, axis=-1) if fmt == "nf4" else outs[0]
    return out.reshape(b, 1, h, hd)


def _lanes(x: jnp.ndarray) -> jnp.ndarray:
    """``(rows, tokens, KV, w)`` -> ``(rows, tokens, KV * w)`` (free)."""
    return x.reshape(x.shape[0], x.shape[1], -1)


def flash_decode_attention(
    q: jnp.ndarray,               # (B, 1, H, hd)
    k_cache: jnp.ndarray,         # (B, S_max, KV, hd)
    v_cache: jnp.ndarray,
    cache_len: jnp.ndarray,       # (B,) valid entries (incl. the new token)
    *,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Single-step flash attention over a dense KV cache.

    A cache length the KV block doesn't divide is padded up to the next
    block multiple (the q_block pad+slice convention of the forward
    kernel): padded rows sit past every ``cache_len`` so the per-slot
    length mask hides them, and the Pallas path stays engaged at odd
    ``max_len`` instead of silently falling back to the reference path.
    The pad is a whole-cache copy inside the jitted step, so callers
    should still prefer block-aligned cache extents (the serving
    engine's bucketed shapes are; the pad only covers the odd-shape
    tail, where the old behavior was a silent O(S^2)-flops fallback).
    Returns ``(B, 1, H, hd)``.
    """
    b, q_len, h, hd = q.shape
    if q_len != 1:
        raise ValueError(f"decode kernel expects q_len == 1, got {q_len}")
    s_max = k_cache.shape[1]
    kv = k_cache.shape[2]
    bk = min(block_k, s_max)
    pad_k = (-s_max) % bk
    if pad_k:
        widths = ((0, 0), (0, pad_k), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, widths)
        v_cache = jnp.pad(v_cache, widths)
        s_max += pad_k
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    lens = cache_len.reshape(b).astype(jnp.int32)
    return _decode_call(
        q, [_lanes(k_cache)], [_lanes(v_cache)], [lens],
        block_map=lambda b_, j, ln: (b_, j), bk=bk, n_k=s_max // bk,
        kv=kv, hd=hd, scale=scale, window=window, fmt=None, quant_block=0,
        value_dtype=q.dtype, interpret=interpret, name="flash_decode",
    )


# ---------------------------------------------------------------------------
# Paged decode: the KV cache is a block pool, per-slot block tables
# (repro.serve.paging) map logical KV blocks to pool rows.  The table is a
# scalar-prefetch operand, so the grid index maps GATHER blocks through it
# — the grid-level decode skipping the dense kernel could not do: a slot
# with 3 allocated blocks fetches exactly 3 blocks from HBM, not
# max_len/block_size.  Unallocated trailing steps revisit the slot's last
# allocated pool row (tables are exported with that clamp) so they issue
# no DMA, and their compute is predicated off by the length test.
# ---------------------------------------------------------------------------

def paged_flash_decode_attention(
    q: jnp.ndarray,               # (B, 1, H, hd)
    k_pool: jnp.ndarray,          # (n_blocks, block_size, KV, hd)
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,    # (B, max_blocks) physical pool rows
    cache_len: jnp.ndarray,       # (B,) valid tokens (incl. the new one)
    *,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    kv_quant: Optional[str] = None,
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
    quant_block: int = 64,
    value_dtype=None,
) -> jnp.ndarray:
    """Single-step flash attention over a paged KV pool.

    ``block_tables[b, j]`` is the pool row holding slot ``b``'s logical
    block ``j``; entries past the slot's allocated count must repeat its
    last allocated row (``paging.PagedCacheView.device_tables`` exports
    that layout) so skipped grid steps re-address a resident block.  The
    block size is the pool's — no ``block_k`` knob; serving picks it at
    cache construction.  Returns ``(B, 1, H, hd)``.

    ``kv_quant`` ("nf4" | "int8") switches to the dequant-in-VMEM
    variant: ``k_pool``/``v_pool`` hold packed codes
    (``core.quantize.quantize_kv`` layout — uint8 with head_dim halved
    for nf4, int8 otherwise), ``k_scales``/``v_scales`` the per-block
    fp32 absmax scale pools; both gather through the same table index
    maps and each visited block dequantizes in VMEM, cast to
    ``value_dtype`` (default: the query dtype).
    """
    b, q_len, h, hd = q.shape
    if q_len != 1:
        raise ValueError(f"decode kernel expects q_len == 1, got {q_len}")
    bs, kv = k_pool.shape[1], k_pool.shape[2]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    lens = cache_len.reshape(b).astype(jnp.int32)
    tables = block_tables.astype(jnp.int32)
    if kv_quant is not None:
        if k_scales is None or v_scales is None:
            raise ValueError("kv_quant needs k_scales and v_scales")
        k_tiles = [_lanes(k_pool), _lanes(k_scales)]
        v_tiles = [_lanes(v_pool), _lanes(v_scales)]
    else:
        k_tiles, v_tiles = [_lanes(k_pool)], [_lanes(v_pool)]
    return _decode_call(
        q, k_tiles, v_tiles, [tables, lens],
        block_map=lambda b_, j, bt, ln: (bt[b_, j], 0), bk=bs,
        n_k=tables.shape[1], kv=kv, hd=hd, scale=scale, window=window,
        fmt=kv_quant, quant_block=quant_block,
        value_dtype=value_dtype or q.dtype, interpret=interpret,
        name="paged_decode" if kv_quant is None else f"paged_decode_{kv_quant}",
    )
