"""QuanTA: Quantum-informed Tensor Adaptation (paper §5, App. B, App. G).

The QuanTA operator is a product of "two-axis tensors" applied to a hidden
vector reshaped into an N-axis tensor (a "qudit register"):

    (T^(a) x)_{i_1..i_N} = sum_{j_m, j_n} T^(a)_{i_m, i_n; j_m, j_n}
                           x_{i_1, .., j_m, .., j_n, .., i_N}
    T x = prod_a T^(a) x                                       (Eq. 4, 5)

Conventions used throughout this repository
-------------------------------------------
* Activations are row vectors: a linear layer is ``y = x @ W`` with
  ``W.shape == (d_in, d_out)``.  The materialized QuanTA operator is
  returned in the same convention, i.e. ``materialize(...)`` has shape
  ``(d_in, d_out)`` and ``apply(x) == x @ materialize(...)``.
* Each two-axis tensor is stored with shape ``(out_m, out_n, in_m, in_n)``
  for the axis pair ``(m, n)``, ``m < n`` — matching the paper's
  ``T_{i_m, i_n; j_m, j_n}`` index order.
* The tensor list order equals the sequential application order (first
  tensor in the list is applied to ``x`` first), which reproduces the
  App. G generator exactly (verified against the N=3 example in §5:
  ``einsum("...abc,efbc,diaf,ghde->...ghi", x, T_3, T_2, T_1)``).

Rectangular layers (App. B): for ``W0 \\in R^{d_in x d_out}`` with a simple
ratio, the *first* tensor in the schedule that touches axis 0 is rectangular
(``out_0 != in_0``); all other axes keep their dimensions.

Zero initialization (Eq. 8/9): the adapted layer starts as
``y = W0 x + T_theta x - S x`` with ``S`` a frozen copy of the initialized
tensors.  Two equivalent realizations are supported:

* **folded** (the paper's deployment form): ``S`` is folded into the base
  weight at attach time; note the paper's Eq. 9 writes ``W0' = W0 + S``
  but Eq. 8 requires ``W0' = W0 - S`` — we implement the mathematically
  consistent sign (``fold_frozen_copy`` subtracts).
* **fold-free** (``PeftConfig(fold=False)``): the base stays untouched and
  the adapter carries ``S`` as frozen factor tensors
  (:attr:`QuantaAdapter.frozen`), computing Eq. 8 directly as
  ``delta(x) = T_theta x - S x``.  The adapter stays delta-form against
  the *shared* ``W0``, which is what lets a multi-tenant bank serve a
  QuanTA tenant as just its factors (no per-tenant dense folded base —
  see ``repro.core.bank`` / ``repro.serve.adapter_pool``).
"""

from __future__ import annotations

import dataclasses
import math
import string
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.adapters import Adapter
from repro.core.factorize import factorize, pair_schedule, param_count

__all__ = [
    "QuantaAdapter",
    "get_symbol",
    "apply_einsum_expr",
    "operator_einsum_expr",
    "tensor_shapes",
    "init_tensors",
    "apply_sequential",
    "apply_einsum",
    "materialize",
    "materialize_einsum",
    "fold_frozen_copy",
    "merge",
]

_SYMBOLS = string.ascii_lowercase + string.ascii_uppercase


def get_symbol(i: int) -> str:
    """Einsum subscript symbol #i (the paper uses ``opt_einsum.get_symbol``)."""
    if i >= len(_SYMBOLS):
        raise ValueError(f"einsum expression needs too many symbols ({i})")
    return _SYMBOLS[i]


# ---------------------------------------------------------------------------
# Schedules and shapes
# ---------------------------------------------------------------------------

def tensor_shapes(
    dims_in: Sequence[int],
    pairs: Sequence[Tuple[int, int]],
    dims_out: Sequence[int] | None = None,
) -> Tuple[Tuple[int, int, int, int], ...]:
    """Shape ``(out_m, out_n, in_m, in_n)`` of every tensor in the schedule.

    Tracks the evolving per-axis dimensions: the first tensor touching axis 0
    maps ``dims_in[0] -> dims_out[0]`` (rectangular case of App. B); all
    other applications are square.
    """
    dims_out = tuple(dims_out) if dims_out is not None else tuple(dims_in)
    if len(dims_out) != len(dims_in):
        raise ValueError("dims_in and dims_out must have equal length")
    for ax, (di, do) in enumerate(zip(dims_in, dims_out)):
        if ax != 0 and di != do:
            raise ValueError(
                "rectangular QuanTA may only change axis 0 "
                f"(axis {ax}: {di} -> {do})"
            )
    cur = list(dims_in)
    shapes = []
    for (m, n) in pairs:
        if not (0 <= m < n < len(cur)):
            raise ValueError(f"bad axis pair {(m, n)} for N={len(cur)}")
        om = dims_out[m] if m == 0 else cur[m]
        on = dims_out[n] if n == 0 else cur[n]
        shapes.append((om, on, cur[m], cur[n]))
        cur[m], cur[n] = om, on
    if tuple(cur) != dims_out:
        raise ValueError(
            f"schedule {tuple(pairs)} never maps dims_in[0] {dims_in[0]} to "
            f"dims_out[0] {dims_out[0]} (no tensor touches axis 0)"
        )
    return tuple(shapes)


# ---------------------------------------------------------------------------
# App. G einsum-expression generators
# ---------------------------------------------------------------------------

def apply_einsum_expr(
    n_axes: int, pairs: Sequence[Tuple[int, int]] | None = None
) -> str:
    """Einsum expression applying the full chain to ``x`` (App. G, verbatim
    port with positive-axis pairs).

    >>> apply_einsum_expr(3)
    '...abc,efbc,diaf,ghde->...ghi'
    """
    pairs = tuple(pairs) if pairs is not None else pair_schedule(n_axes)
    cur = list(range(n_axes))
    expr = "..." + "".join(get_symbol(i) for i in cur)
    for (m, n) in pairs:
        sm, sn = cur[m], cur[n]
        om, on = sm + n_axes, sn + n_axes  # App. G: new symbol = old + N
        expr += "," + get_symbol(om) + get_symbol(on) + get_symbol(sm) + get_symbol(sn)
        cur[m], cur[n] = om, on
    expr += "->..." + "".join(get_symbol(i) for i in cur)
    return expr


def operator_einsum_expr(
    n_axes: int, pairs: Sequence[Tuple[int, int]] | None = None
) -> str:
    """Einsum expression materializing the full operator as ``(in; out)``.

    Output subscripts are ``j_1..j_N i_1..i_N`` so the reshaped result is a
    ``(d_in, d_out)`` matrix in the ``y = x @ M`` convention.
    (The paper's App. G builds the ``(out; in)`` variant; ours is its
    transpose to match the row-vector convention used by the models.)
    """
    pairs = tuple(pairs) if pairs is not None else pair_schedule(n_axes)
    cur = list(range(n_axes))
    operands = []
    for (m, n) in pairs:
        sm, sn = cur[m], cur[n]
        om, on = sm + n_axes, sn + n_axes  # App. G: new symbol = old + N
        operands.append(
            get_symbol(om) + get_symbol(on) + get_symbol(sm) + get_symbol(sn)
        )
        cur[m], cur[n] = om, on
    out = "".join(get_symbol(i) for i in range(n_axes)) + "".join(
        get_symbol(i) for i in cur
    )
    return ",".join(operands) + "->" + out


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def _identity_like(om: int, on: int, im: int, in_: int, dtype) -> jnp.ndarray:
    """(Truncated/padded) identity for a tensor of shape (om,on,im,in)."""
    eye = jnp.zeros((om * on, im * in_), dtype=dtype)
    k = min(om * on, im * in_)
    eye = eye.at[jnp.arange(k), jnp.arange(k)].set(1.0)
    return eye.reshape(om, on, im, in_)


def init_tensors(
    key: jax.Array,
    dims_in: Sequence[int],
    dims_out: Sequence[int] | None = None,
    pairs: Sequence[Tuple[int, int]] | None = None,
    *,
    init: str = "identity_noise",
    noise_scale: float = 0.02,
    dtype=jnp.float32,
) -> Tuple[jnp.ndarray, ...]:
    """Initialize the QuanTA tensor chain.

    ``identity_noise`` (default): each tensor is (truncated) identity plus
    small Gaussian noise — the chain starts full-rank and near-identity,
    which keeps the operator well conditioned for the frozen-copy
    cancellation trick (Eq. 8).  ``normal``: i.i.d. Gaussian with
    1/sqrt(fan_in) scaling (ablation).
    """
    pairs = tuple(pairs) if pairs is not None else pair_schedule(len(dims_in))
    shapes = tensor_shapes(dims_in, pairs, dims_out)
    keys = jax.random.split(key, len(shapes))
    tensors = []
    for k, (om, on, im, in_) in zip(keys, shapes):
        if init == "identity_noise":
            base = _identity_like(om, on, im, in_, dtype)
            t = base + noise_scale * jax.random.normal(
                k, (om, on, im, in_), dtype
            )
        elif init == "normal":
            t = jax.random.normal(k, (om, on, im, in_), dtype) / math.sqrt(
                im * in_
            )
        else:
            raise ValueError(f"unknown init {init!r}")
        tensors.append(t)
    return tuple(tensors)


# ---------------------------------------------------------------------------
# Application paths
# ---------------------------------------------------------------------------

def apply_sequential(
    x: jnp.ndarray,
    tensors: Sequence[jnp.ndarray],
    dims_in: Sequence[int],
    pairs: Sequence[Tuple[int, int]],
    dims_out: Sequence[int] | None = None,
) -> jnp.ndarray:
    """Memory-light sequential path (paper §6 complexity analysis).

    Each tensor application is a batched matmul: the pair axes are moved to
    the minor positions, flattened, and contracted with the tensor reshaped
    to ``(out_m*out_n, in_m*in_n)``.  This is also the schedule the Pallas
    kernel fuses (see ``repro/kernels``).
    """
    dims_in = tuple(dims_in)
    batch_shape = x.shape[:-1]
    if x.shape[-1] != math.prod(dims_in):
        raise ValueError(f"x last dim {x.shape[-1]} != prod{dims_in}")
    nb = len(batch_shape)
    h = x.reshape(*batch_shape, *dims_in)
    for t, (m, n) in zip(tensors, pairs):
        om, on, im, in_ = t.shape
        h = jnp.moveaxis(h, (nb + m, nb + n), (-2, -1))
        lead = h.shape[:-2]
        h2 = h.reshape(*lead, im * in_)
        y2 = h2 @ t.reshape(om * on, im * in_).T
        h = y2.reshape(*lead, om, on)
        h = jnp.moveaxis(h, (-2, -1), (nb + m, nb + n))
    return h.reshape(*batch_shape, -1)


def apply_einsum(
    x: jnp.ndarray,
    tensors: Sequence[jnp.ndarray],
    dims_in: Sequence[int],
    pairs: Sequence[Tuple[int, int]],
    dims_out: Sequence[int] | None = None,
) -> jnp.ndarray:
    """Single-einsum path (App. G) — joint contraction, optimized order."""
    dims_in = tuple(dims_in)
    batch_shape = x.shape[:-1]
    h = x.reshape(*batch_shape, *dims_in)
    expr = apply_einsum_expr(len(dims_in), pairs)
    out = jnp.einsum(expr, h, *tensors, optimize=True)
    return out.reshape(*batch_shape, -1)


def materialize(
    tensors: Sequence[jnp.ndarray],
    dims_in: Sequence[int],
    pairs: Sequence[Tuple[int, int]],
    dims_out: Sequence[int] | None = None,
) -> jnp.ndarray:
    """Materialize the full operator as a ``(d_in, d_out)`` matrix.

    Built by applying the chain to the identity basis — numerically identical
    to :func:`materialize_einsum` (tested) and cheaper for large N.
    """
    d_in = math.prod(dims_in)
    eye = jnp.eye(d_in, dtype=tensors[0].dtype)
    return apply_sequential(eye, tensors, dims_in, pairs, dims_out)


def materialize_einsum(
    tensors: Sequence[jnp.ndarray],
    dims_in: Sequence[int],
    pairs: Sequence[Tuple[int, int]],
    dims_out: Sequence[int] | None = None,
) -> jnp.ndarray:
    """Materialize via the App. G operator einsum expression."""
    expr = operator_einsum_expr(len(dims_in), pairs)
    full = jnp.einsum(expr, *tensors, optimize=True)
    d_in = math.prod(dims_in)
    return full.reshape(d_in, -1)


# ---------------------------------------------------------------------------
# Adapter pytree
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantaAdapter(Adapter):
    """Trainable QuanTA state for one linear layer.

    Folded mode (``frozen is None``, the default): after
    :func:`fold_frozen_copy` the adapted layer is (Eq. 9)::

        y = x @ w0_folded + adapter.delta(x)

    Fold-free mode (``frozen`` holds the initialization copy ``S`` as
    factor tensors): the base weight is untouched and Eq. 8 is computed
    directly::

        y = x @ w0 + (T_theta x - S x)        # delta(x) subtracts S

    At initialization ``T_theta == S`` bitwise, so the delta is exactly
    zero — the adapted model IS the base model at step 0, same as the
    folded form, without a per-layer dense ``W0 - S`` copy.  ``S`` rides
    in the trainable pytree but is excluded from gradients
    (``stop_gradient``) and from ``num_params``; train with
    ``weight_decay=0`` (the repo default) or a decay mask so the frozen
    copy is not silently decayed.

    Implements the :class:`repro.core.adapters.Adapter` protocol;
    ``apply`` additionally routes through the fused Pallas kernels
    (``repro.kernels.ops``) when called with ``backend="pallas"``.
    """

    tensors: Tuple[jnp.ndarray, ...]
    dims_in: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))
    dims_out: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))
    pairs: Tuple[Tuple[int, int], ...] = dataclasses.field(
        metadata=dict(static=True)
    )
    # fold-free mode: frozen copy S of the initialized tensors (Eq. 8).
    # None (default, flattens to an empty subtree) = folded mode.
    frozen: Tuple[jnp.ndarray, ...] | None = None

    @staticmethod
    def create(
        key: jax.Array,
        d_in: int,
        d_out: int | None = None,
        *,
        n_axes: int = 4,
        dims_in: Sequence[int] | None = None,
        dims_out: Sequence[int] | None = None,
        pairs: Sequence[Tuple[int, int]] | None = None,
        init: str = "identity_noise",
        noise_scale: float = 0.02,
        dtype=jnp.float32,
    ) -> "QuantaAdapter":
        d_out = d_out if d_out is not None else d_in
        if dims_in is None:
            dims_in = factorize(d_in, n_axes)
        dims_in = tuple(dims_in)
        if math.prod(dims_in) != d_in:
            raise ValueError(f"prod{dims_in} != d_in={d_in}")
        if dims_out is None:
            if d_out == d_in:
                dims_out = dims_in
            else:
                # App. B: only axis 0 is rectangular; requires a simple ratio.
                if d_out % (d_in // dims_in[0]) != 0:
                    raise ValueError(
                        f"d_out={d_out} not reachable from dims_in={dims_in} "
                        "by changing axis 0 only"
                    )
                dims_out = (d_out * dims_in[0] // d_in,) + dims_in[1:]
        dims_out = tuple(dims_out)
        if math.prod(dims_out) != d_out:
            raise ValueError(f"prod{dims_out} != d_out={d_out}")
        pairs = tuple(pairs) if pairs is not None else pair_schedule(len(dims_in))
        tensors = init_tensors(
            key, dims_in, dims_out, pairs,
            init=init, noise_scale=noise_scale, dtype=dtype,
        )
        return QuantaAdapter(tensors, dims_in, dims_out, pairs)

    @property
    def d_in(self) -> int:
        return math.prod(self.dims_in)

    @property
    def d_out(self) -> int:
        return math.prod(self.dims_out)

    @property
    def num_params(self) -> int:
        return param_count(self.dims_in, self.pairs, self.dims_out)

    @property
    def fold_free(self) -> bool:
        """True when this adapter carries the frozen copy S (Eq. 8 mode)."""
        return self.frozen is not None

    def unfrozen(self, tensors: Tuple[jnp.ndarray, ...] | None = None
                 ) -> "QuantaAdapter":
        """A plain (folded-mode) view over ``tensors`` (default: the
        trainable chain) — used to route each chain of the fold-free pair
        through the single-chain fused kernels."""
        t = tensors if tensors is not None else self.tensors
        return QuantaAdapter(t, self.dims_in, self.dims_out, self.pairs)

    def delta(self, x: jnp.ndarray) -> jnp.ndarray:
        """``T_theta x`` (folded) or ``T_theta x - S x`` (fold-free) for
        batched ``x (..., d_in) -> (..., d_out)``.  Runs under the
        ``quanta`` name scope, by which a profile attributes the chain's
        device time."""
        with jax.named_scope("quanta"):
            h = x.astype(self.tensors[0].dtype)
            y = apply_sequential(
                h, self.tensors, self.dims_in, self.pairs, self.dims_out
            )
            if self.frozen is not None:
                # stop_gradient on S only — the S chain is linear in x,
                # so gradients still flow through x to upstream layers
                y = y - apply_sequential(
                    h, jax.lax.stop_gradient(self.frozen),
                    self.dims_in, self.pairs, self.dims_out,
                )
            return y.astype(x.dtype)

    def matrix(self) -> jnp.ndarray:
        """Full ``(d_in, d_out)`` update matrix (fold-free subtracts S)."""
        m = materialize(self.tensors, self.dims_in, self.pairs, self.dims_out)
        if self.frozen is not None:
            m = m - materialize(
                jax.lax.stop_gradient(self.frozen),
                self.dims_in, self.pairs, self.dims_out,
            )
        return m

    def apply(self, x: jnp.ndarray, w: jnp.ndarray,
              backend: str = "reference") -> jnp.ndarray:
        """Adapted linear ``x @ w + delta(x)``.

        ``backend="pallas"`` fuses base matmul and chain in one kernel
        (``kernels.ops.quanta_linear_fused``) when the working set fits
        the VMEM budget, else XLA matmul + the fused-chain kernel —
        interpret-mode on CPU, Mosaic on TPU (``kernels.dispatch``).
        Forward-only today: training keeps ``backend="reference"`` (the
        raw kernels carry no custom VJP).
        """
        # deferred import: kernels.ops imports QuantaAdapter from here
        from repro.core.quantize import QuantizedLinear, base_matmul

        if backend == "pallas" and w.ndim == 2:
            from repro.kernels.ops import quanta_apply_fused

            if self.frozen is not None:
                # fold-free: base matmul (fused-dequant for quantized
                # bases) + each chain of the T - S pair through the
                # fused-chain kernel
                s_view = self.unfrozen(jax.lax.stop_gradient(self.frozen))
                y = base_matmul(x, w, backend)
                with jax.named_scope("quanta"):
                    d = (quanta_apply_fused(x, self.unfrozen())
                         - quanta_apply_fused(x, s_view)).astype(x.dtype)
                return y + d
            if isinstance(w, QuantizedLinear):
                # quantized frozen base: fused dequant-matmul for the
                # base + the fused chain kernel for the delta (the dense
                # weight is never materialized in HBM)
                y = base_matmul(x, w, backend)
                with jax.named_scope("quanta"):
                    d = quanta_apply_fused(x, self).astype(x.dtype)
                return y + d
            from repro.kernels.ops import quanta_linear_fused

            return quanta_linear_fused(x, w, self)
        return base_matmul(x, w, backend) + self.delta(x)

    def merge(self, w: jnp.ndarray) -> jnp.ndarray:
        """Merge the trained operator into the (folded) base weight
        (paper §6, no inference overhead): ``W = W0' + T_theta``."""
        return merge(w, self)


def fold_frozen_copy(w0: jnp.ndarray, adapter: QuantaAdapter) -> jnp.ndarray:
    """Fold the frozen initialization copy ``S`` into the base weight.

    Implements Eq. 8 -> Eq. 9: ``y = W0 x + T x - S x`` becomes
    ``y = (W0 - S) x + T x`` where at call time ``S == T`` (``adapter`` holds
    the freshly initialized tensors).  The returned weight keeps ``w0``'s
    dtype; the subtraction happens in the adapter's (higher) precision.
    """
    s_mat = adapter.matrix()
    return (w0.astype(s_mat.dtype) - s_mat).astype(w0.dtype)


def merge(w0_folded: jnp.ndarray, adapter: QuantaAdapter) -> jnp.ndarray:
    """Merge the trained operator into the base weight (no inference
    overhead, paper §6): ``W = W0' + T_theta``."""
    t_mat = adapter.matrix()
    return (w0_folded.astype(t_mat.dtype) + t_mat).astype(w0_folded.dtype)
