"""Adapter lifecycle: host-side tenant registry + fixed-capacity resident
bank with hot-swap row residency.

A production multi-tenant deployment serves far more trained adapters
than fit (or belong) on the accelerator: thousands of registered tenants,
a few dozen actually decoding at any moment.  The static
``core.bank.AdapterBank`` bakes every tenant into the device layout at
build time — fine for 8 tenants, wrong for 1000.  This module splits
tenancy into two tiers:

* :class:`AdapterStore` — the **registry**.  Tenants live host-side as
  their raw factor pytrees (normalized through
  ``core.bank.tenant_path_adapters``, so folded-QuanTA tenants carry
  their ``RebasedAdapter`` dense base and fold-free QuanTA / LoRA / DoTA
  tenants are just factors).  Append-only up to ``max_tenants``;
  registration order fixes each tenant's **stable global id** — the id
  requests carry, which survives every residency change.
* :class:`AdapterPool` — the **resident bank**.  Device arrays in exactly
  the ``_BankPath`` layout the static bank uses, but with a fixed
  ``capacity + 1`` rows per structure group (row 0 = neutral).  The pool
  is what the serving jits consume — via :meth:`AdapterPool.device_bank`,
  an ``AdapterBank`` whose leaf shapes NEVER change — so loading or
  evicting a tenant recompiles nothing.

Residency mechanics
-------------------
``load(name)`` allocates one bank row per adapted (path, group) from a
free-list :class:`RowAllocator` (double-free/foreign-row guarded, like
``paging.BlockAllocator``) and scatters the tenant's factors into those
rows with ONE donated jitted update per structure profile
(``leaf.at[row].set`` — row indices are traced scalars, so churn never
retraces; the jit compiles once per distinct tenant structure).  The
``id_maps`` are host ``numpy`` vectors mapping global id -> local row:
a swap rewrites two integers, and the next tick's jit dispatch picks the
new mapping up as a plain traced argument.  ``evict(name)`` zeroes the
tenant's id_map entries and frees its rows — the stale factor rows are
unreachable (no id maps to them) and get overwritten by the next load.

Eviction policy is LRU by serving traffic: every ``acquire``/``release``
stamps the tenant with a monotonic clock, and a full group evicts its
least-recently-used **unpinned** occupant.  Pinning is refcounted:
``ServingEngine`` acquires a tenant at admission (the last admission
check — an unloadable tenant defers, it never tears a wave) and releases
at slot free / preemption, so an in-flight tenant can never be evicted
out from under a decoding slot (``evict`` refuses, returning False).

The engine threads ``device_bank()`` as a **traced argument** of every
serving jit (prefill wave, chunked prefill, fused decode) — unlike the
static bank, which the jit lambdas close over — because swapped rows
must be visible to already-compiled programs.  Global ids ride per-slot
``adapter_ids`` exactly as before; a preempted request requeues with its
id intact and re-acquires (possibly reloading after an eviction) at
re-admission.

``stats()`` surfaces the byte split the registry/resident divide exists
for: ``adapter_bytes_resident`` (device bank rows, fixed by capacity)
vs ``adapter_bytes_registry`` (host factor bytes, grows with tenants) —
a fold-free QuanTA tenant's marginal resident cost is just its factor
rows.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bank import (
    AdapterBank, TenantEntry, _BankPath, adapter_signature,
    tenant_path_adapters,
)
from repro.core.peft import _set_path, flatten_paths
from repro.serve.paging import addressable_nbytes

__all__ = ["AdapterPool", "AdapterStore", "RowAllocator"]


class RowAllocator:
    """LIFO free-list over bank rows ``1..capacity`` (row 0 = neutral,
    never handed out).  Double-free and foreign-row frees raise — the
    allocator is the single source of truth for row ownership, so
    corruption here silently serves one tenant another's factors."""

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError("need at least one resident row")
        self.capacity = capacity
        # pop() hands out low rows first (deterministic tests); the set
        # shadows the list for an O(1) double-free guard.
        self._free: List[int] = list(range(capacity, 0, -1))
        self._free_set = set(self._free)
        self.peak_in_use = 0

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.capacity - len(self._free)

    def alloc(self) -> int:
        if not self._free:
            raise MemoryError("adapter bank full: no free resident rows")
        row = self._free.pop()
        self._free_set.discard(row)
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return row

    def free(self, row: int) -> None:
        row = int(row)
        if not (0 < row <= self.capacity):
            raise ValueError(f"freeing invalid bank row {row}")
        if row in self._free_set:
            raise ValueError(f"double free of bank row {row}")
        self._free.append(row)
        self._free_set.add(row)


class AdapterStore:
    """Host-side tenant registry: name -> raw adapter factors.

    ``register`` accepts exactly what ``AdapterBank.build`` accepts per
    tenant — an ``AdapterSet``, or the ``(params, adapter_set)`` pair
    ``attach`` returned (required for folded QuanTA) — and normalizes it
    once via ``core.bank.tenant_path_adapters``.  Registration order
    fixes stable global ids ``1..max_tenants`` (0 = base model);
    ``max_tenants`` caps the registry because the resident bank's
    ``id_maps`` are sized ``(max_tenants + 1,)`` at pool build.
    """

    def __init__(self, *, max_tenants: int):
        if max_tenants < 1:
            raise ValueError("max_tenants must be positive")
        self.max_tenants = max_tenants
        self._names: List[str] = []
        self._members: Dict[str, Dict[str, Tuple[Any, Any]]] = {}
        self._nbytes = 0

    # ------------------------------------------------------------ registry
    def register(self, name: str, entry: TenantEntry) -> int:
        """Register a trained tenant; returns its stable global id."""
        if name in self._members:
            raise ValueError(f"tenant {name!r} already registered")
        if len(self._names) >= self.max_tenants:
            raise ValueError(
                f"registry full: max_tenants={self.max_tenants} "
                "(sized at construction — it bounds the resident bank's "
                "id_map extent)"
            )
        members = tenant_path_adapters(name, entry)
        self._members[name] = members
        self._names.append(name)
        self._nbytes += sum(
            addressable_nbytes(leaf)
            for adapter, _ in members.values()
            for leaf in jax.tree_util.tree_leaves(adapter)
        )
        return len(self._names)

    def get(self, name: str) -> Dict[str, Tuple[Any, Any]]:
        """Flat ``path -> (adapter, leaf_spec)`` for one tenant."""
        try:
            return self._members[name]
        except KeyError:
            raise KeyError(
                f"unknown adapter {name!r}; registry holds "
                f"{len(self._names)} tenant(s)"
            ) from None

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(self._names)

    @property
    def num_tenants(self) -> int:
        return len(self._names)

    def id_of(self, name: Optional[str]) -> int:
        """Stable global adapter id (``None`` -> 0 = base model)."""
        if name is None:
            return 0
        try:
            return 1 + self._names.index(name)
        except ValueError:
            raise KeyError(
                f"unknown adapter {name!r}; registry holds "
                f"{len(self._names)} tenant(s)"
            ) from None

    @property
    def nbytes(self) -> int:
        """Registry bytes: every registered tenant's factor leaves
        (summed as each registers; registered factors never change)."""
        return int(self._nbytes)


class AdapterPool:
    """Fixed-capacity resident bank over an :class:`AdapterStore`.

    Build with :meth:`build`; serve with
    ``ServingEngine(model, params, adapters=pool)``.  Duck-types the
    engine-facing surface of ``AdapterBank`` (``id_of`` /
    ``num_tenants``) while :meth:`device_bank` supplies the actual
    pytree the serving jits trace.
    """

    def __init__(self, store: AdapterStore, capacity: int,
                 tree: Dict[str, Any],
                 gindex: Dict[str, Dict[Any, int]],
                 stacked_of: Dict[str, bool],
                 profiles: frozenset):
        self.store = store
        self.capacity = capacity
        self.tree = tree
        self._gindex = gindex                  # path -> {signature: group}
        self._stacked = stacked_of             # path -> scan-stacked?
        self._known_profiles = profiles
        self._bank = AdapterBank(tree=tree, names=())
        self._alloc: Dict[Tuple[str, int], RowAllocator] = {
            (path, gi): RowAllocator(capacity)
            for path, sigs in gindex.items()
            for gi in sigs.values()
        }
        # name -> {"rows": {(path, group): row}, "pins": int, "stamp": int}
        self._resident: Dict[str, Dict[str, Any]] = {}
        self._clock = 0
        self._placed_mesh = None
        # one donated in-place row scatter, traced once per structure
        # profile (row indices are traced scalars, so churn within a
        # profile never retraces).  CPU ignores donation with a warning,
        # so only donate where the backend honors it.  The nested def
        # gives THIS pool its own jit identity (jax's tracing cache is
        # keyed by the underlying callable, so jitting the module-level
        # function directly would pool compile counts across AdapterPool
        # instances and break per-engine compile_guard accounting) and
        # the program a stable name, ``jit_swap_fn``.
        donate = () if jax.default_backend() == "cpu" else (0,)

        def swap_fn(groups, tenants, rows, stacked):
            return _scatter_rows(groups, tenants, rows, stacked)

        self.swap_fn = jax.jit(
            swap_fn, donate_argnums=donate, static_argnums=3,
        )
        # lifecycle gauges (merged into ServingEngine.stats each tick):
        # ``load_s`` sums the wall seconds of every ``_load``
        self.loads = 0
        self.load_s = 0.0
        self.evictions = 0
        self.acquire_denied = 0
        self.evict_denied = 0
        self._resident_nbytes = self._tree_nbytes()

    # ------------------------------------------------------------- building
    @staticmethod
    def build(base_params: Dict[str, Any], store: AdapterStore, *,
              capacity: int) -> "AdapterPool":
        """Derive the resident layout from the CURRENTLY registered
        tenants: one gather group per structure signature per adapted
        path, each with ``capacity + 1`` all-neutral rows.  Tenants
        registered later hot-load fine as long as their structure matches
        an existing group (a novel structure would need new device
        arrays, i.e. a rebuild)."""
        if capacity < 1:
            raise ValueError("capacity must be positive")
        if store.num_tenants == 0:
            raise ValueError(
                "register at least one tenant before building the pool "
                "(group layout derives from tenant structures)"
            )
        flat_base = flatten_paths(base_params)
        # path -> ordered {sig: (prototype adapter, spec)}
        protos: Dict[str, Dict[Any, Tuple[Any, Any]]] = {}
        profiles = set()
        for name in store.names:
            profile = []
            for path, (adapter, spec) in sorted(store.get(name).items()):
                sig = adapter_signature(adapter)
                per = protos.setdefault(path, {})
                if sig not in per:
                    per[sig] = (adapter, spec)
                profile.append((path, sig))
            profiles.add(tuple(profile))

        tree: Dict[str, Any] = {}
        gindex: Dict[str, Dict[Any, int]] = {}
        stacked_of: Dict[str, bool] = {}
        for path, per in sorted(protos.items()):
            stacked = next(iter(per.values()))[1].stacked
            if any(s.stacked != stacked for _, s in per.values()):
                raise ValueError(
                    f"path {path}: tenants disagree on stacked layout"
                )
            w0 = flat_base[path]
            groups, id_maps, dforms = [], [], []
            gindex[path] = {}
            stacked_of[path] = stacked
            for gi, (sig, (proto, _)) in enumerate(per.items()):
                if stacked:
                    neutral = jax.vmap(lambda a, wl: a.neutral(wl))(proto, w0)
                else:
                    neutral = proto.neutral(w0)
                axis = 1 if stacked else 0
                # capacity + 1 identical neutral rows: row 0 stays the
                # permanent neutral, rows 1..capacity await tenants
                groups.append(jax.tree_util.tree_map(
                    lambda leaf: jnp.stack([leaf] * (capacity + 1), axis),
                    neutral,
                ))
                # HOST-side id_maps (numpy): a swap rewrites two entries
                # in place; jit dispatch re-commits them every tick.
                id_maps.append(np.zeros((store.max_tenants + 1,), np.int32))
                dforms.append(bool(proto.delta_form))
                gindex[path][sig] = gi
            _set_path(tree, path, _BankPath(
                groups=tuple(groups), id_maps=tuple(id_maps),
                stacked=stacked, delta_forms=tuple(dforms),
            ))
        return AdapterPool(
            store, capacity, tree, gindex, stacked_of, frozenset(profiles),
        )

    # ------------------------------------------------------------- identity
    @property
    def num_tenants(self) -> int:
        return self.store.num_tenants

    def id_of(self, name: Optional[str]) -> int:
        return self.store.id_of(name)

    def device_bank(self) -> AdapterBank:
        """The pytree the serving jits trace — static leaf shapes, row
        contents hot-swapped between ticks."""
        return self._bank

    @property
    def num_resident(self) -> int:
        return len(self._resident)

    def is_resident(self, name: str) -> bool:
        return name in self._resident

    def pins_of(self, name: str) -> int:
        ent = self._resident.get(name)
        return 0 if ent is None else ent["pins"]

    @property
    def n_profiles(self) -> int:
        """Distinct tenant structure profiles — the swap jit's documented
        compile bound (one trace per profile; rows are traced)."""
        return len(self._known_profiles)

    # ------------------------------------------------------------ placement
    def place(self, mesh) -> None:
        """Device-place the resident groups under a mesh (replicated —
        ``launch.shardings.peft_shardings``'s adapter rule).  The host
        ``id_maps`` stay numpy: they are rewritten in place on swap."""
        from repro.launch.shardings import peft_shardings

        if mesh is None or self._placed_mesh is mesh:
            return
        sh = peft_shardings(mesh, self._bank)

        # _BankPath is frozen; rebuild nodes instead of mutating them
        def rebuild(node, node_sh):
            if isinstance(node, dict):
                return {k: rebuild(node[k], node_sh[k]) for k in node}
            return _BankPath(
                groups=tuple(
                    jax.device_put(g, gs)
                    for g, gs in zip(node.groups, node_sh.groups)
                ),
                id_maps=node.id_maps,
                stacked=node.stacked,
                delta_forms=node.delta_forms,
            )

        new_tree = rebuild(self.tree, sh.tree)
        self.tree.clear()
        self.tree.update(new_tree)
        self._placed_mesh = mesh
        self._resident_nbytes = self._tree_nbytes()

    # ------------------------------------------------------------ lifecycle
    def _path_node(self, path: str) -> _BankPath:
        node = self.tree
        for k in path.split("/"):
            node = node[k]
        return node

    def _touch(self, name: str) -> None:
        self._clock += 1
        self._resident[name]["stamp"] = self._clock

    def _profile_of(self, name: str):
        members = self.store.get(name)
        profile = []
        for path, (adapter, _) in sorted(members.items()):
            sig = adapter_signature(adapter)
            gi = self._gindex.get(path, {}).get(sig)
            if gi is None:
                raise ValueError(
                    f"tenant {name!r} (registered after the pool was "
                    f"built) has a structure at {path!r} matching no "
                    "resident group; rebuild the pool to add new "
                    "structure groups"
                )
            profile.append((path, gi, adapter))
        return profile

    def _load(self, name: str, profile) -> None:
        """Scatter the tenant's factors into freshly allocated rows —
        one donated jitted update — and point its id_map entries at
        them.  Callers ensured every needed group has a free row."""
        with jax.profiler.TraceAnnotation("pool.load", tenant=name):
            t0 = time.perf_counter()
            gid = self.store.id_of(name)
            rows: Dict[Tuple[str, int], int] = {}
            for path, gi, _ in profile:
                rows[(path, gi)] = self._alloc[(path, gi)].alloc()

            groups_in = tuple(
                self._path_node(path).groups[gi] for path, gi, _ in profile
            )
            tenants = tuple(adapter for _, _, adapter in profile)
            row_ixs = tuple(rows[(path, gi)] for path, gi, _ in profile)
            stacked = tuple(self._stacked[path] for path, gi, _ in profile)
            new_groups = self.swap_fn(groups_in, tenants, row_ixs, stacked)
            with jax.profiler.TraceAnnotation("serve.wait"):
                jax.block_until_ready(new_groups)    # honest load time

            for (path, gi, _), new_g in zip(profile, new_groups):
                node = self._path_node(path)
                gs = list(node.groups)
                gs[gi] = new_g
                _set_path(self.tree, path, _BankPath(
                    groups=tuple(gs), id_maps=node.id_maps,
                    stacked=node.stacked, delta_forms=node.delta_forms,
                ))
                node.id_maps[gi][gid] = rows[(path, gi)]
            self._resident[name] = {"rows": rows, "pins": 0, "stamp": 0}
            self._touch(name)
            self.loads += 1
            self.load_s += time.perf_counter() - t0

    def _evict(self, name: str) -> None:
        with jax.profiler.TraceAnnotation("pool.evict", tenant=name):
            ent = self._resident.pop(name)
            gid = self.store.id_of(name)
            for (path, gi), row in ent["rows"].items():
                node = self._path_node(path)
                node.id_maps[gi][gid] = 0        # unreachable before freed
                self._alloc[(path, gi)].free(row)
            self.evictions += 1

    def _ensure_resident(self, name: str) -> bool:
        if name in self._resident:
            return True
        profile = self._profile_of(name)
        # make room group by group: evict the LRU UNPINNED occupant of
        # each full group this tenant needs (evicting one tenant frees a
        # row in every group it occupies, so progress is monotone)
        for path, gi, _ in profile:
            key = (path, gi)
            while self._alloc[key].available == 0:
                victims = [
                    (ent["stamp"], n)
                    for n, ent in self._resident.items()
                    if ent["pins"] == 0 and key in ent["rows"]
                ]
                if not victims:
                    return False             # every occupant is in flight
                self._evict(min(victims)[1])
        self._load(name, profile)
        return True

    def acquire(self, name: Optional[str]) -> bool:
        """Pin a tenant for an in-flight request, loading (and evicting
        an LRU unpinned resident) if needed.  False = no row could be
        freed — the caller defers admission.  ``None`` (base model) is
        always ready."""
        if name is None:
            return True
        if not self._ensure_resident(name):
            self.acquire_denied += 1
            return False
        self._resident[name]["pins"] += 1
        self._touch(name)
        return True

    def release(self, name: Optional[str]) -> None:
        """Unpin after the request left its slot (completion or
        preemption).  The tenant stays resident until LRU-evicted."""
        if name is None:
            return
        ent = self._resident.get(name)
        if ent is None or ent["pins"] <= 0:
            raise ValueError(
                f"release of tenant {name!r} without a matching acquire"
            )
        ent["pins"] -= 1
        self._touch(name)

    def load(self, name: str) -> bool:
        """Make a tenant resident WITHOUT pinning (warm-up)."""
        ok = self._ensure_resident(name)
        if ok:
            self._touch(name)
        return ok

    def evict(self, name: str) -> bool:
        """Evict a resident tenant.  Refused (False) while any in-flight
        request pins it — re-issue after its slots drain; admission-time
        ``acquire`` reloads evicted tenants transparently."""
        ent = self._resident.get(name)
        if ent is None:
            return False
        if ent["pins"] > 0:
            self.evict_denied += 1
            return False
        self._evict(name)
        return True

    # -------------------------------------------------------------- gauges
    def resident_nbytes(self) -> int:
        """Device bytes of the resident bank (groups + id_maps) — fixed
        by capacity, NOT by tenant count (summed at build and again at
        placement, which is all that changes it)."""
        return self._resident_nbytes

    def _tree_nbytes(self) -> int:
        return int(sum(
            addressable_nbytes(leaf)
            for leaf in jax.tree_util.tree_leaves(self.tree)
        ))

    def stats(self) -> Dict[str, Any]:
        return {
            "adapter_bytes_resident": self.resident_nbytes(),
            "adapter_bytes_registry": self.store.nbytes,
            "adapter_residents": self.num_resident,
            "adapter_capacity": self.capacity,
            "adapter_loads": self.loads,
            "adapter_evictions": self.evictions,
            "adapter_acquire_denied": self.acquire_denied,
            "adapter_evict_denied": self.evict_denied,
            "adapter_load_s": self.load_s,
        }


def _scatter_rows(groups, tenants, rows, stacked):
    """Donated row scatter: write each tenant pytree into its bank row.
    ``rows`` are traced int scalars (churn re-dispatches, never
    retraces); ``stacked`` is static — scan-stacked groups carry the
    bank axis at 1 (``(L, G+1, ...)``)."""
    out = []
    for g, t, r, st in zip(groups, tenants, rows, stacked):
        if st:
            upd = lambda gl, tl: gl.at[:, r].set(tl)      # noqa: E731
        else:
            upd = lambda gl, tl: gl.at[r].set(tl)         # noqa: E731
        out.append(jax.tree_util.tree_map(upd, g, t))
    return tuple(out)
