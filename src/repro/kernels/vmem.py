"""Shared VMEM budgeting for every Pallas kernel in this package.

One grid step of a kernel holds its operand blocks, output block(s), and
scratch buffers in VMEM simultaneously.  ``vmem_footprint`` sums those
bytes from ``(shape, dtype)`` pairs so the jit'd wrappers (``ops.py``)
and the static kernel-contract checker (``repro.analysis.kernels``)
budget against the SAME arithmetic — the ad-hoc per-kernel estimates
this generalizes could silently drift from what the checker verifies.
"""

from __future__ import annotations

import math
from typing import Iterable, Sequence, Tuple

import jax.numpy as jnp

__all__ = ["VMEM_BUDGET_BYTES", "VMEM_TARGET_BYTES", "vmem_footprint"]

# ~12 MiB of the 16 MiB scoped-VMEM limit the v5e compiler applies by
# default: the budget the wrappers dispatch against and the contract
# checker enforces.  Checked against the compiler for a v5e: a
# quantized_matmul grid step this arithmetic sizes at 10.7 MiB compiles,
# one at 17.1 MiB is refused ("Ran out of memory in memory space vmem").
VMEM_BUDGET_BYTES = 12 * 2**20

# Per-target budgets for the contract checker (bytes of usable VMEM).
# CPU interpret mode has no real VMEM; kernels are still checked against
# the TPU budget so a config that validates on the container also fits
# the hardware it ships to.
VMEM_TARGET_BYTES = {
    "v5e": 12 * 2**20,      # 16 MiB physical
    "v4": 12 * 2**20,       # 16 MiB physical
    "v5p": 24 * 2**20,      # 32 MiB physical (larger headroom)
}


def _itemsize(dtype) -> int:
    """Bytes per element for a dtype or an explicit itemsize int."""
    if isinstance(dtype, int):
        return dtype
    return jnp.dtype(dtype).itemsize


def vmem_footprint(
    blocks: Iterable[Tuple[Sequence[int], object]],
) -> int:
    """Total bytes of a set of VMEM-resident blocks.

    ``blocks`` is an iterable of ``(shape, dtype)`` pairs; ``dtype`` may
    also be an explicit per-element byte count (int) for callers that
    budget a dtype-polymorphic kernel at a fixed width.
    """
    total = 0
    for shape, dtype in blocks:
        total += math.prod(shape) * _itemsize(dtype)
    return total
