"""Kernel benchmark (paper Limitations §: sequential tensor application
underutilizes the accelerator).

Measures wall time of the fused Pallas chain (interpret mode — CPU
validation only; TPU numbers come from Mosaic) and, more importantly,
reports the ANALYTIC HBM-traffic model that drives the §Perf roofline:

    staged traffic  = (2*N_T + small) * rows * d * bytes
    fused traffic   = (read + write) * rows * d * bytes
    => traffic reduction ~ N_T x

Also times the pure-jnp sequential path (what the paper's reference
implementation does) for CPU-relative comparison.
"""

from __future__ import annotations

import argparse
import time

import jax

from benchmarks.common import csv_row
from repro.core import QuantaAdapter
from repro.core.quanta import apply_sequential
from repro.kernels import quanta_apply_fused

CASES = [
    ("llama2_scheme_16-8-8-4", 4096, (16, 8, 8, 4)),
    ("qwen2_16-8-7", 896, (16, 8, 7)),
    ("phi3_16-8-8-5", 5120, (16, 8, 8, 5)),
]
SMOKE_CASES = [("smoke_4-4-4", 64, (4, 4, 4))]
ROWS = 2048
SMOKE_ROWS = 64


def traffic_model(d_in: int, d_out: int, n_tensors: int, rows: int,
                  bytes_per_el: int = 2) -> tuple:
    staged = (2 * n_tensors) * rows * max(d_in, d_out) * bytes_per_el
    fused = rows * (d_in + d_out) * bytes_per_el
    return staged, fused


def _time(fn, *args, reps=3):
    fn(*args)  # compile/warm
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps


def main(smoke: bool = False) -> list:
    out = []
    cases = SMOKE_CASES if smoke else CASES
    rows = SMOKE_ROWS if smoke else ROWS
    for name, d, dims in cases:
        ad = QuantaAdapter.create(jax.random.PRNGKey(0), d, dims_in=dims,
                                  init="normal")
        x = jax.random.normal(jax.random.PRNGKey(1), (rows, d))
        seq = jax.jit(lambda x: apply_sequential(
            x, ad.tensors, ad.dims_in, ad.pairs))
        t_seq = _time(seq, x)
        staged, fused = traffic_model(d, ad.d_out, len(ad.tensors), rows)
        print(csv_row(
            f"kernel/seq_jnp/{name}", 1e6 * t_seq,
            f"hbm_staged_bytes={staged}",
        ))
        fusedfn = jax.jit(lambda x: quanta_apply_fused(
            x, ad, block_rows=256, interpret=True))
        t_fused = _time(fusedfn, x, reps=1)   # interpret mode: slow on CPU
        print(csv_row(
            f"kernel/fused_pallas_interpret/{name}", 1e6 * t_fused,
            f"hbm_fused_bytes={fused};traffic_reduction="
            f"{staged / fused:.1f}x",
        ))
        out.append((name, t_seq, t_fused, staged / fused))
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes only (CI kernel-regression gate)")
    main(smoke=ap.parse_args().smoke)
