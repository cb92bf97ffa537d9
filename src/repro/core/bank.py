"""Multi-tenant adapter bank: N trained adapter sets over ONE base model.

Production PEFT serving is multi-tenant — many task adapters (sst2, mnli,
...) share a frozen base model, and each request names the adapter it
wants.  ``AdapterBank`` packs the tenants' :class:`~repro.core.peft.
AdapterSet`s into bank-stacked pytrees so a batch mixing tenants stays ONE
jitted program: per-request ``adapter_ids`` (0 = base model, ``1 + i`` =
``names[i]``) are a traced ``(B,)`` argument, each adapted linear gathers
its row's adapter parameters with ``jnp.take`` along the bank axis, and
application runs ``vmap``-per-row — O(1) dispatch regardless of how many
tenants the batch touches (the punica / multi-LoRA serving pattern).
``BankedAdapter.apply`` additionally routes delta-form LoRA groups through
the fused Pallas banked-gather kernel under ``backend="pallas"``
(``repro.kernels.banked_gather``); the vmap gather is the pinned
reference.

This module is the *device layout*; the adapter **lifecycle** lives one
level up in ``repro.serve.adapter_pool``, which splits tenancy into a
host-side ``AdapterStore`` registry (tenants as raw factors, unbounded)
and a fixed-capacity **resident bank** using exactly this ``_BankPath``
layout — rows are hot-swapped in place between serving ticks while the
jitted programs see one static pytree shape.  A static ``AdapterBank``
built here is the degenerate always-resident case.

Layout
------
Tenants may use different PEFT methods (and different ranks/schemes), so
adapters cannot stack into one array family.  The bank groups members by
*structure signature* (pytree structure + leaf shapes); per adapted path it
stores, per group:

* a stacked adapter pytree whose leaves carry a bank axis of extent
  ``G + 1`` — entry 0 is the group's **neutral** element
  (``Adapter.neutral``: ``apply(x, w) == x @ w`` exactly), used for id 0
  and for requests belonging to other groups,
* an ``id_map`` ``(n_tenants + 1,)`` from global adapter id to the local
  bank row (0 when the tenant is not in this group).  The id_map is the
  indirection that makes residency dynamic: requests carry stable global
  ids, and a row swap only rewrites two id_map entries.

For scan-stacked paths the bank axis sits at axis 1 — ``(L, G+1, ...)`` —
so ``jax.lax.scan`` slices the layer axis first and the per-layer gather
stays a leading-axis ``jnp.take``; per-request ids are broadcast to
``(L, B)`` so the scan slices them in lockstep.

Exactness
---------
The equivalence bar is token-for-token agreement with per-tenant
single-tenant engines, so banked application avoids re-associating
floating-point sums:

* delta-form groups (LoRA / KronA / QuanTA, including fold-free QuanTA)
  add their gathered ``delta(x)`` to the shared base matmul — neutral
  rows add exact zeros,
* non-delta groups (DoRA's weight rescale, DoTA, ``RebasedAdapter``-
  wrapped folded QuanTA) compute the member rows' full ``apply`` and
  ``jnp.where``-select them over the base result — no add-then-subtract
  of the base matmul.

QuanTA tenants come in two forms.  **Folded** tenants (the default
``attach``) had the frozen copy folded into their base (``W0' = W0 - S``),
so their trained delta is only correct against that tenant-specific base:
``build`` takes them as the ``(folded_params, adapter_set)`` pair and
wraps them in :class:`~repro.core.adapters.RebasedAdapter` — one dense
``(d_in, d_out)`` copy per tenant per path.  **Fold-free** tenants
(``PeftConfig(fold=False)``) carry ``S`` as frozen factors and stay
delta-form against the shared base, so they bank bare — their residency
cost is just their factor tensors, which is what makes large-registry
hot-swap serving (``repro.serve.adapter_pool``) affordable.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.adapters import Adapter, RebasedAdapter
from repro.core.peft import AdapterSet, _set_path, flatten_paths
from repro.core.quantize import base_matmul

__all__ = [
    "AdapterBank",
    "BankedAdapter",
    "adapter_signature",
    "tenant_path_adapters",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class _BankPath:
    """Bank storage for one adapted parameter path."""

    groups: Tuple[Any, ...]            # adapter pytrees, bank axis G_i + 1
    id_maps: Tuple[jnp.ndarray, ...]   # per group: (n_tenants + 1,) int32
    stacked: bool = dataclasses.field(metadata=dict(static=True))
    delta_forms: Tuple[bool, ...] = dataclasses.field(
        metadata=dict(static=True)
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BankedAdapter(Adapter):
    """Per-request gathered adapter application (the model-visible leaf).

    Lives at an adapted path in the tree ``AdapterBank.subtree`` builds:
    ``groups`` leaves carry a leading bank axis, ``ids`` the per-request
    local bank rows (0 = neutral).  For scan-stacked paths both carry a
    leading layer axis that ``jax.lax.scan`` slices away before ``apply``
    runs.  ``apply`` gathers each group along the bank axis and applies
    row-wise under ``vmap`` — see the module docstring for why delta-form
    and non-delta groups combine differently.
    """

    delta_form = False

    groups: Tuple[Any, ...]
    ids: Tuple[jnp.ndarray, ...]       # per group: (B,) local bank rows
    delta_forms: Tuple[bool, ...] = dataclasses.field(
        metadata=dict(static=True)
    )

    def apply(self, x: jnp.ndarray, w: jnp.ndarray,
              backend: str = "reference") -> jnp.ndarray:
        # Under backend="pallas" one delta-form group may fuse the shared
        # base matmul with its row gather (Adapter.banked_linear — LoRA's
        # banked-gather kernel); remaining delta-form groups add their
        # gathered delta (banked_delta: fused kernel or the reference
        # jnp.take + vmap), neutral rows contributing an exact 0.
        # Non-delta groups compute member rows' full apply and
        # jnp.where-select over the base result.
        y = None
        deferred = []
        for g, lid, dform in zip(self.groups, self.ids, self.delta_forms):
            if y is None and dform and backend == "pallas":
                y = g.banked_linear(x, w, lid, backend)
                if y is not None:
                    continue
            deferred.append((g, lid, dform))
        if y is None:
            # the shared-base matmul honors the backend (and a quantized
            # base dispatches bitwise-identically either way)
            y = base_matmul(x, w, backend)
        # the per-request adapter work runs under a name scope, by which
        # a profile attributes its device time (the shared base matmul
        # stays outside it)
        with jax.named_scope("adapter"):
            for g, lid, dform in deferred:
                if dform:
                    y = y + g.banked_delta(x, lid, backend)
                else:
                    sel = jax.tree_util.tree_map(
                        lambda leaf: jnp.take(leaf, lid, axis=0), g
                    )
                    full = jax.vmap(lambda a, xr: a.apply(xr, w))(sel, x)
                    mask = (lid > 0).reshape((-1,) + (1,) * (y.ndim - 1))
                    y = jnp.where(mask, full, y)
        return y


TenantEntry = Union[AdapterSet, Tuple[Any, AdapterSet]]


def tenant_path_adapters(
    name: str, entry: TenantEntry
) -> Dict[str, Tuple[Adapter, Any]]:
    """Normalize one tenant into flat ``path -> (adapter, leaf_spec)``.

    Folded-QuanTA members (``AdapterLeafSpec.fold``) are wrapped in
    :class:`RebasedAdapter` against the tenant's own folded base weight,
    which REQUIRES the ``(params, adapter_set)`` pair ``attach`` returned.
    Shared by :meth:`AdapterBank.build` and the hot-swap registry
    (``repro.serve.adapter_pool.AdapterStore``) so both layouts bank the
    exact same member pytrees.
    """
    if isinstance(entry, tuple):
        t_params, aset = entry
        flat_t = flatten_paths(t_params)
    else:
        aset = entry
        flat_t = None
    if not isinstance(aset, AdapterSet):
        raise TypeError(
            f"tenant {name!r}: expected an AdapterSet (or a "
            f"(params, AdapterSet) pair), got {type(aset).__name__}"
        )
    specs = {s.path: s for s in aset.specs}
    out: Dict[str, Tuple[Adapter, Any]] = {}
    for path, adapter in aset.flat().items():
        spec = specs[path]
        if spec.method == "quanta" and getattr(spec, "fold", True):
            if flat_t is None:
                raise ValueError(
                    f"tenant {name!r} is folded QuanTA: attach "
                    "folds the frozen copy into the base weights, "
                    "so the bank needs the (params, adapter_set) "
                    "pair attach returned to rebase it onto the "
                    "shared params (or retrain with "
                    "PeftConfig(fold=False) for factor-only "
                    "residency)"
                )
            adapter = RebasedAdapter(adapter, flat_t[path])
        out[path] = (adapter, spec)
    return out


def adapter_signature(adapter: Adapter):
    """Hashable structure signature grouping bank members: pytree
    structure (method class + static metadata) plus leaf shapes/dtypes.
    Members sharing a signature stack into one gather group."""
    return (
        jax.tree_util.tree_structure(adapter),
        tuple(
            (tuple(leaf.shape), str(jnp.asarray(leaf).dtype))
            for leaf in jax.tree_util.tree_leaves(adapter)
        ),
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AdapterBank:
    """N tenants' adapters stacked for shared-base multi-tenant serving.

    Build with :meth:`build`; serve with
    ``ServingEngine(model, base_params, adapters=bank)`` and
    ``engine.submit(req, adapter="sst2")``.  ``subtree(key, adapter_ids)``
    is the model-side entry point (via ``peft.adapter_subtree``).
    """

    tree: Dict[str, Any]               # nested dict of _BankPath
    names: Tuple[str, ...] = dataclasses.field(metadata=dict(static=True))

    # ------------------------------------------------------------- identity
    @property
    def num_tenants(self) -> int:
        return len(self.names)

    def id_of(self, name: Optional[str]) -> int:
        """Global adapter id for a tenant name (``None`` -> 0 = base)."""
        if name is None:
            return 0
        try:
            return 1 + self.names.index(name)
        except ValueError:
            raise KeyError(
                f"unknown adapter {name!r}; bank serves {self.names}"
            ) from None

    # ------------------------------------------------------------ selection
    def subtree(self, key: str, adapter_ids=None) -> Dict[str, Any]:
        """Nested tree of :class:`BankedAdapter` for one model scan group.

        ``adapter_ids`` (B,) int32 global ids — a traced argument of the
        serving jits.  Raises without it: a bank cannot be applied
        un-selected (training against a bank is not a thing; train
        per-tenant ``AdapterSet``s and re-``build``).
        """
        sub = self.tree.get(key, {})
        if not sub:
            return {}
        if adapter_ids is None:
            raise ValueError(
                "AdapterBank needs per-request adapter_ids; this entry "
                "point does not thread them (training/forward paths serve "
                "single AdapterSets only)"
            )
        ids = jnp.asarray(adapter_ids, jnp.int32)

        def build(node):
            if isinstance(node, dict):
                return {k: build(v) for k, v in node.items()}
            lids = tuple(jnp.take(m, ids, axis=0) for m in node.id_maps)
            if node.stacked:
                n_layers = jax.tree_util.tree_leaves(node.groups[0])[0].shape[0]
                lids = tuple(
                    jnp.broadcast_to(i, (n_layers,) + i.shape) for i in lids
                )
            return BankedAdapter(node.groups, lids, node.delta_forms)

        return build(sub)

    # ------------------------------------------------------------ shardings
    def bank_axis_tree(self) -> "AdapterBank":
        """A congruent pytree marking each leaf's bank-axis index (-1 for
        ``id_maps``) — consumed by ``launch.shardings.peft_shardings`` to
        optionally DP-split the bank axis without re-deriving layout."""

        def per(node):
            if isinstance(node, dict):
                return {k: per(v) for k, v in node.items()}
            ax = 1 if node.stacked else 0
            return _BankPath(
                groups=tuple(
                    jax.tree_util.tree_map(lambda _: ax, g)
                    for g in node.groups
                ),
                id_maps=tuple(-1 for _ in node.id_maps),
                stacked=node.stacked,
                delta_forms=node.delta_forms,
            )

        return AdapterBank(tree=per(self.tree), names=self.names)

    # ------------------------------------------------------------- building
    @staticmethod
    def build(
        base_params: Dict[str, Any],
        tenants: Mapping[str, TenantEntry],
    ) -> "AdapterBank":
        """Pack trained tenants into a bank over ``base_params``.

        ``tenants`` maps tenant name -> either the tenant's
        :class:`AdapterSet` (methods whose attach leaves the base weights
        untouched: LoRA / DoRA / KronA), or the full
        ``(params, adapter_set)`` pair ``attach`` returned — REQUIRED for
        QuanTA, whose attach folds the frozen copy into the base: the
        tenant's folded weight at each adapted path is carried into the
        bank via :class:`RebasedAdapter`.  Insertion order fixes the
        global adapter ids: ``names[i]`` serves as id ``1 + i``; id 0 is
        the bare base model.
        """
        names = tuple(tenants)
        flat_base = flatten_paths(base_params)
        # path -> list of (tenant_idx, adapter, spec)
        per_path: Dict[str, list] = {}
        for t_idx, (name, entry) in enumerate(tenants.items()):
            for path, (adapter, spec) in tenant_path_adapters(
                name, entry
            ).items():
                per_path.setdefault(path, []).append((t_idx, adapter, spec))

        tree: Dict[str, Any] = {}
        for path, members in sorted(per_path.items()):
            stacked = members[0][2].stacked
            if any(s.stacked != stacked for _, _, s in members):
                raise ValueError(
                    f"path {path}: tenants disagree on stacked layout"
                )
            w0 = flat_base[path]
            # group members by structure signature (method class + static
            # metadata via tree_structure, and leaf shapes/dtypes):
            # heterogeneous ranks/schemes become separate gather groups.
            sigs: Dict[Any, list] = {}
            for t_idx, adapter, _ in members:
                sig = adapter_signature(adapter)
                sigs.setdefault(sig, []).append((t_idx, adapter))
            groups, id_maps, dforms = [], [], []
            for mems in sigs.values():
                a0 = mems[0][1]
                if stacked:
                    neutral = jax.vmap(lambda a, wl: a.neutral(wl))(a0, w0)
                else:
                    neutral = a0.neutral(w0)
                axis = 1 if stacked else 0
                entries = [neutral] + [a for _, a in mems]
                groups.append(jax.tree_util.tree_map(
                    lambda *ls: jnp.stack(ls, axis=axis), *entries
                ))
                idm = np.zeros((len(names) + 1,), np.int32)
                for local, (t_idx, _) in enumerate(mems, start=1):
                    idm[1 + t_idx] = local
                id_maps.append(jnp.asarray(idm))
                dforms.append(bool(a0.delta_form))
            _set_path(tree, path, _BankPath(
                groups=tuple(groups), id_maps=tuple(id_maps),
                stacked=stacked, delta_forms=tuple(dforms),
            ))
        return AdapterBank(tree=tree, names=names)
