"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this
module touches no jax device state — required because the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* first
jax init, while smoke tests and benches must keep seeing 1 device.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax

__all__ = [
    "make_abstract_mesh",
    "make_mesh",
    "make_production_mesh",
    "make_host_mesh",
    "dp_axes",
]


def _auto(n: int):
    # Auto (GSPMD-propagated) axes: ``jax.make_mesh`` defaults to
    # Explicit axes, under which ``with_sharding_constraint`` and the
    # models' sharded reshapes raise ShardingTypeError.
    return (jax.sharding.AxisType.Auto,) * n


def make_abstract_mesh(
    shape: Sequence[int], axis_names: Sequence[str]
) -> "jax.sharding.AbstractMesh":
    """Device-free mesh for sharding-rule validation and planning."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(shape), tuple(axis_names),
                        axis_types=_auto(len(shape)))


def make_mesh(shape: Sequence[int], axis_names: Sequence[str],
              devices=None) -> jax.sharding.Mesh:
    """Mesh over ``devices`` (default: all of them) with Auto axes —
    every mesh in this repo is built here."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=_auto(len(shape)), devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """TPU v5e: one pod = 16x16 = 256 chips; multi-pod = 2 pods = 512."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1,
                   devices=None) -> jax.sharding.Mesh:
    """Small data x model mesh (tests, examples, the four-chip smoke)."""
    return make_mesh((data, model), ("data", "model"), devices=devices)


def dp_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """The data-parallel axes of a mesh: ('pod','data') or ('data',)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
