"""PEFT attachment layer: wires adapters (QuanTA / LoRA / DoRA / KronA)
onto a model's parameter pytree.

Models in ``repro.models`` store every adaptable linear as a 2-D weight
``(d_in, d_out)`` or, for scan-over-layers stacks, ``(L, d_in, d_out)``.
Every adapter implements the uniform :class:`repro.core.adapters.Adapter`
protocol (``apply(x, w) / delta(x) / merge(w) / neutral(w) / num_params``),
so this module contains **no per-method dispatch** — the concrete class is
the dispatch.

:func:`attach` returns a structured :class:`AdapterSet`: a pytree whose
``tree`` mirrors the parameter key paths (adapters stacked along the layer
axis for scanned stacks, so ``jax.lax.scan`` slices them in lockstep with
the weights) plus static per-path metadata — path, method, and
stacked-vs-flat layout — that downstream consumers (``merge_all``, the
serving :class:`repro.core.bank.AdapterBank`, sharding rules) read instead
of re-deriving it from array shapes.  ``AdapterSet`` is a drop-in
trainable pytree: ``jax.grad``, optimizers, and checkpointing treat it as
its nested adapter dict with metadata riding along statically.

The public API:

* :func:`attach` — create an :class:`AdapterSet` for every target path;
  for QuanTA this also folds the frozen initialization copy into the base
  weights (Eq. 9), returning ``(folded_base_params, adapter_set)``.
* :func:`merge_all` — merge trained adapters into the base weights for
  deployment (no inference overhead, paper §6).
* :func:`peft_linear` — the adapted linear used by all models; pure
  protocol dispatch (``adapter.apply``), with ``backend="pallas"`` routing
  QuanTA through the fused kernels (``cfg.peft_backend``).
* :func:`adapter_subtree` — normalize ``None`` / legacy dict /
  ``AdapterSet`` / ``AdapterBank`` (+ per-request ``adapter_ids``) into
  the nested adapter tree a model's layer scan consumes.
* :func:`count_params` / :func:`trainable_fraction` — paper-style
  "# Params (%)".
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import quanta as Q
from repro.core.baselines import (
    DoraAdapter,
    DotaAdapter,
    KronaAdapter,
    LoraAdapter,
)
from repro.core.factorize import factorize, parse_scheme
from repro.core.quantize import base_matmul

__all__ = [
    "PeftConfig",
    "AdapterLeafSpec",
    "AdapterSet",
    "attach",
    "merge_all",
    "peft_linear",
    "adapter_subtree",
    "get_adapter",
    "count_params",
    "trainable_fraction",
    "flatten_paths",
]

# Default target modules per the paper (Table E.2-E.4): q_proj and v_proj.
DEFAULT_TARGETS = (r".*/(q_proj|v_proj)$",)


@dataclasses.dataclass(frozen=True)
class PeftConfig:
    """Which method to attach, where, and with what hyperparameters."""

    method: str = "quanta"  # quanta | lora | dora | dota | krona | ft | none
    targets: Tuple[str, ...] = DEFAULT_TARGETS
    # QuanTA
    n_axes: int = 4
    scheme: Optional[str] = None          # e.g. "16-8-8-4" (paper notation)
    rounds: int = 1                       # repetitions of the pairwise
    #                                       schedule (paper E.1 uses 1; more
    #                                       rounds enlarge the chain manifold
    #                                       toward universality, App. C)
    init: str = "identity_noise"
    noise_scale: float = 0.02
    # fold=True (paper Eq. 9): attach folds the frozen copy S into the
    # base weights.  fold=False: base stays untouched; the adapter carries
    # S as factors and computes Eq. 8 directly (delta-form against the
    # shared W0) — required for factor-only multi-tenant serving
    # (repro.serve.adapter_pool).
    fold: bool = True
    # LoRA / DoRA
    rank: int = 8
    alpha: float = 16.0
    # KronA
    krona_a: int = 64
    # numerics
    dtype: Any = jnp.float32

    def replace(self, **kw) -> "PeftConfig":
        return dataclasses.replace(self, **kw)


def flatten_paths(tree: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    """Flatten a nested dict into ``{"a/b/c": leaf}`` (adapter objects are
    leaves, sub-dicts are structure)."""
    out: Dict[str, Any] = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten_paths(v, path))
        else:
            out[path] = v
    return out


def _set_path(tree: Dict[str, Any], path: str, value: Any) -> None:
    keys = path.split("/")
    node = tree
    for k in keys[:-1]:
        node = node.setdefault(k, {})
    node[keys[-1]] = value


def _match(path: str, patterns: Tuple[str, ...]) -> bool:
    return any(re.fullmatch(p, path) for p in patterns)


# ---------------------------------------------------------------------------
# AdapterSet: the structured result of attach()
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AdapterLeafSpec:
    """Static per-path record of what ``attach`` created."""

    path: str           # parameter key path, e.g. "layers/attn/q_proj"
    method: str         # quanta | lora | dora | dota | krona
    stacked: bool       # True: leading layer axis, sliced by lax.scan
    d_in: int
    d_out: int
    fold: bool = True   # quanta only: False = fold-free (Eq. 8) attach


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AdapterSet:
    """Adapters for one model, plus static layout metadata.

    ``tree`` is the nested adapter dict mirroring the parameter paths (the
    trainable pytree); ``specs`` records, per adapted path, the method and
    the stacked-vs-flat layout.  Dict-style read access (``set["layers"]``)
    is kept for callers that navigate the tree directly.
    """

    tree: Dict[str, Any]
    specs: Tuple[AdapterLeafSpec, ...] = dataclasses.field(
        default=(), metadata=dict(static=True)
    )

    # ---- tree navigation
    def subtree(self, key: str, adapter_ids=None) -> Dict[str, Any]:
        """The nested adapter dict under ``key`` (a model scan group, e.g.
        ``"layers"``).  ``adapter_ids`` is accepted for signature
        uniformity with ``AdapterBank.subtree`` and ignored — a single
        adapter set serves every request."""
        del adapter_ids
        return self.tree.get(key, {})

    def __getitem__(self, key: str):
        return self.tree[key]

    def __contains__(self, key: str) -> bool:
        return key in self.tree

    def flat(self) -> Dict[str, Any]:
        """``{path: adapter}`` over every adapted path."""
        return flatten_paths(self.tree)

    @property
    def paths(self) -> Tuple[str, ...]:
        return tuple(s.path for s in self.specs)

    def spec(self, path: str) -> AdapterLeafSpec:
        for s in self.specs:
            if s.path == path:
                return s
        raise KeyError(path)

    @property
    def num_params(self) -> int:
        return count_params(self.tree)


def choose_dims(
    d_in: int, d_out: int, n_axes: int, scheme: Optional[str] = None
) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Pick QuanTA axis factorizations for a (possibly rectangular) weight.

    Square: the config's paper-style scheme (e.g. ``"16-8-8-5"``) or a
    balanced auto-factorization.  Rectangular (App. B): the simple ratio
    ``d_in/d_out = p/q`` is carried entirely by axis 0, so
    ``dims_in = (p*a, rest)`` and ``dims_out = (q*a, rest)`` with
    ``(a, *rest) = factorize(d_in / p)``.
    """
    if d_in == d_out:
        dims = parse_scheme(scheme) if scheme else factorize(d_in, n_axes)
        if math.prod(dims) != d_in:
            raise ValueError(f"scheme {scheme} does not factor d={d_in}")
        return dims, dims
    g = math.gcd(d_in, d_out)
    p, q = d_in // g, d_out // g
    if d_in % p:
        raise ValueError(f"no simple-ratio factorization for {d_in}->{d_out}")
    base = factorize(d_in // p, n_axes)
    return (p * base[0],) + base[1:], (q * base[0],) + base[1:]


def _krona_dims(cfg: PeftConfig, d_in: int, d_out: int) -> Tuple[int, int]:
    """Validated KronA factor dims.

    The old silent fallback ``a_in = gcd(krona_a, d_in);
    a_out = gcd(a_in, d_out)`` could collapse to 1 (e.g. ``krona_a=7``
    against even dims), leaving a near-empty ``1 x 1 (x) d_in x d_out``
    adapter that trains but learns almost nothing.  Degenerate picks now
    raise instead of degrading.
    """
    a_in = math.gcd(cfg.krona_a, d_in)
    a_out = math.gcd(a_in, d_out)
    if a_in < 2 or a_out < 2:
        raise ValueError(
            f"krona_a={cfg.krona_a} is incompatible with a "
            f"({d_in}, {d_out}) weight: the usable factor collapses to "
            f"(a_in={a_in}, a_out={a_out}), a near-empty adapter. Pick a "
            f"krona_a sharing a common divisor >= 2 with both dims "
            f"(e.g. a divisor of gcd={math.gcd(d_in, d_out)})."
        )
    return a_in, a_out


def _make_adapter(key, w: jnp.ndarray, cfg: PeftConfig):
    """Build one adapter (possibly layer-stacked) for weight ``w``."""
    stacked = w.ndim == 3
    d_in, d_out = (w.shape[1], w.shape[2]) if stacked else (w.shape[0], w.shape[1])

    def make_one(k, w_layer):
        if cfg.method == "quanta":
            dims_in, dims_out = choose_dims(
                d_in, d_out, cfg.n_axes, cfg.scheme
            )
            pairs = None
            if cfg.rounds > 1:
                from repro.core.factorize import pair_schedule
                base_sched = pair_schedule(len(dims_in))
                # rectangular first round maps axis 0; later rounds square
                pairs = base_sched * cfg.rounds
            return Q.QuantaAdapter.create(
                k, d_in, d_out, n_axes=cfg.n_axes, dims_in=dims_in,
                dims_out=dims_out, pairs=pairs,
                init=cfg.init, noise_scale=cfg.noise_scale, dtype=cfg.dtype,
            )
        if cfg.method == "lora":
            return LoraAdapter.create(
                k, d_in, d_out, rank=cfg.rank, alpha=cfg.alpha, dtype=cfg.dtype
            )
        if cfg.method == "dora":
            # per-layer magnitude init: each layer starts EXACTLY at the
            # base model (the old layer-0 template broke the stacked
            # attach->merge_all identity at init)
            return DoraAdapter.create(
                k, w_layer.astype(cfg.dtype), rank=cfg.rank, alpha=cfg.alpha,
                dtype=cfg.dtype,
            )
        if cfg.method == "dota":
            # weight-decomposed like DoRA (per-layer magnitude init) with
            # a tensor-train delta over QuanTA's axis factorization
            return DotaAdapter.create(
                k, w_layer.astype(cfg.dtype), rank=cfg.rank,
                n_axes=cfg.n_axes, dtype=cfg.dtype,
            )
        if cfg.method == "krona":
            a_in, a_out = _krona_dims(cfg, d_in, d_out)
            return KronaAdapter.create(
                k, d_in, d_out, a_in=a_in, a_out=a_out, dtype=cfg.dtype
            )
        raise ValueError(f"unknown PEFT method {cfg.method!r}")

    if not stacked:
        return make_one(key, w)
    n_layers = w.shape[0]
    keys = jax.random.split(key, n_layers)
    return jax.vmap(make_one)(keys, w)


def _fold_quanta(w: jnp.ndarray, adapter) -> jnp.ndarray:
    """Fold the frozen copy S into (possibly stacked) base weights."""
    if w.ndim == 3:
        return jax.vmap(Q.fold_frozen_copy)(w, adapter)
    return Q.fold_frozen_copy(w, adapter)


def attach(
    key: jax.Array, params: Dict[str, Any], cfg: PeftConfig
) -> Tuple[Dict[str, Any], Any]:
    """Create adapters for every parameter path matching ``cfg.targets``.

    Returns ``(base_params, adapter_set)`` with ``adapter_set`` an
    :class:`AdapterSet` (``{}`` for the full-FT / no-PEFT methods, so the
    trainable tree stays empty).  For QuanTA with ``cfg.fold=True`` (the
    default), ``base_params`` has the frozen initialization copy folded in
    (``W0' = W0 - S``, Eq. 8/9) so the adapted model is exactly the base
    model at step 0.  With ``cfg.fold=False`` the base weights are
    returned unchanged and the adapter carries ``S`` as frozen factors
    (Eq. 8 computed directly) — same step-0 exactness, delta-form against
    the shared base.  For the other methods the adapters are
    zero-initialized by construction and the base weights are returned
    unchanged.
    """
    if cfg.method in ("ft", "none"):
        return params, {}
    flat = flatten_paths(params)
    targets = {p: w for p, w in flat.items() if _match(p, cfg.targets)}
    if not targets:
        raise ValueError(
            f"no parameter matched targets {cfg.targets}; available paths: "
            f"{sorted(flat)[:20]}..."
        )
    peft: Dict[str, Any] = {}
    specs = []
    new_params = jax.tree_util.tree_map(lambda x: x, params)  # shallow copy tree
    keys = jax.random.split(key, len(targets))
    for k, (path, w) in zip(keys, sorted(targets.items())):
        if w.ndim not in (2, 3):
            raise ValueError(f"target {path} has ndim={w.ndim}; expected 2 or 3")
        adapter = _make_adapter(k, w, cfg)
        if cfg.method == "quanta" and not cfg.fold:
            # fold-free (Eq. 8): stamp the frozen copy S onto the adapter
            # instead of folding it into the base weight.  A copy, not the
            # same buffers: a train step that donates its state would
            # otherwise donate each of them twice.
            adapter = dataclasses.replace(
                adapter,
                frozen=jax.tree_util.tree_map(jnp.copy, adapter.tensors),
            )
        _set_path(peft, path, adapter)
        stacked = w.ndim == 3
        d_in, d_out = w.shape[-2], w.shape[-1]
        specs.append(AdapterLeafSpec(
            path, cfg.method, stacked, d_in, d_out, fold=cfg.fold
        ))
        if cfg.method == "quanta" and cfg.fold:
            _set_path(new_params, path, _fold_quanta(w, adapter))
    return new_params, AdapterSet(tree=peft, specs=tuple(specs))


def adapter_subtree(peft, key: str, adapter_ids=None) -> Dict[str, Any]:
    """The nested adapter tree a model scan group consumes.

    Accepts ``None`` (no PEFT), a legacy bare nested dict, an
    :class:`AdapterSet`, or an ``AdapterBank`` — anything exposing
    ``.subtree(key, adapter_ids)``.  ``adapter_ids`` (a traced ``(B,)``
    int32 array of per-request tenant ids, 0 = base model) only matters
    for banks, where it selects each request's adapter inside the jitted
    program.
    """
    if peft is None:
        return {}
    sub = getattr(peft, "subtree", None)
    if sub is not None:
        return sub(key, adapter_ids)
    return peft.get(key, {})


def get_adapter(peft: Optional[Dict[str, Any]], *keys: str):
    """Walk the adapter tree; returns None when the path is not adapted."""
    node = peft
    for k in keys:
        if not isinstance(node, dict) or k not in node:
            return None
        node = node[k]
    return node if not isinstance(node, dict) else None


def peft_linear(
    x: jnp.ndarray,
    w: jnp.ndarray,
    adapter=None,
    bias: Optional[jnp.ndarray] = None,
    backend: str = "reference",
) -> jnp.ndarray:
    """The adapted linear layer used by every model in ``repro.models``.

    Pure protocol dispatch: the adapter's ``apply`` defines its own
    application (delta form, DoRA's weight rescaling, the bank's gathered
    per-request form, ...).  ``backend`` is the model's
    ``cfg.peft_backend``; adapters without a fused kernel ignore it.

    ``w`` may be a blockwise-quantized frozen base
    (``core.quantize.QuantizedLinear``) — ``base_matmul`` and every
    adapter's ``apply`` run the dequant-matmul (fused under
    ``backend="pallas"``) with the fp adapter update on top; dense
    weights keep the exact ``x @ w`` the models always ran.
    """
    if adapter is None:
        y = base_matmul(x, w, backend)
    else:
        y = adapter.apply(x, w, backend)
    if bias is not None:
        y = y + bias
    return y


def _merge_one(w: jnp.ndarray, adapter) -> jnp.ndarray:
    fn = lambda w0, a: a.merge(w0)  # noqa: E731 — protocol, not dispatch
    if w.ndim == 3:
        return jax.vmap(fn)(w, adapter)
    return fn(w, adapter)


def merge_all(params: Dict[str, Any], peft) -> Dict[str, Any]:
    """Merge every adapter into the base weights (deployment form, §6:
    the zero-inference-overhead single-tenant fast path).

    ``peft`` may be an :class:`AdapterSet` or a legacy nested dict.
    """
    flat_adapters = flatten_paths(getattr(peft, "tree", peft) or {})
    flat_params = flatten_paths(params)
    merged = jax.tree_util.tree_map(lambda x: x, params)
    for path, adapter in flat_adapters.items():
        _set_path(merged, path, _merge_one(flat_params[path], adapter))
    return merged


def count_params(tree: Any) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(tree))


def trainable_fraction(base_params: Any, peft: Any) -> float:
    """Paper-style ``# Params (%)``: trainable / base totals."""
    base = count_params(base_params)
    trainable = count_params(peft)
    return 100.0 * trainable / max(base, 1)
