"""Flash-attention benchmark: reference vs Pallas kernel across
seq-len / window / GQA sweeps.

Wall times on this container compare the pure-JAX reference path against
the kernel in interpret mode (CPU validation only — the interpreter is
not representative of Mosaic throughput; TPU numbers come from the
hillclimb roofline).  The derived columns carry the numbers that ARE
meaningful everywhere: the visible-block fraction (the exact fraction of
the KV-block grid the kernel computes — compiled FLOPs ratio vs the
reference's full masked rows) and the modeled score-traffic savings.

Run:  PYTHONPATH=src python -m benchmarks.attention_bench [--smoke]

``--smoke`` runs one tiny case per variant — the CI kernel-regression
gate (any parity or dispatch breakage fails the step).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row
from repro.kernels.flash_attention import (
    decode_visible_blocks,
    visible_block_fraction,
)
from repro.models.attention import blockwise_causal_attention, decode_attention

# (name, seq, n_heads, n_kv_heads, head_dim, window, q_block, kv_block)
CASES = [
    ("s256_dense_gqa4", 256, 8, 2, 32, None, 64, 64),
    ("s256_window64", 256, 8, 2, 32, 64, 64, 64),
    ("s512_dense_mha", 512, 4, 4, 32, None, 128, 128),
    ("s512_window128_gqa8", 512, 8, 1, 32, 128, 128, 64),
]
SMOKE_CASES = [("s64_dense_gqa2", 64, 4, 2, 16, None, 32, 32)]

DECODE_CASES = [
    ("decode_s512_dense", 512, 8, 2, 32, None, 128),
    ("decode_s512_window128", 512, 8, 2, 32, 128, 128),
]
SMOKE_DECODE_CASES = [("decode_s64_dense", 64, 4, 2, 16, None, 32)]

BATCH = 2


def _time(fn, *args, reps=2):
    jax.block_until_ready(fn(*args))  # compile/warm
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps


def main(smoke: bool = False) -> None:
    cases = SMOKE_CASES if smoke else CASES
    dec_cases = SMOKE_DECODE_CASES if smoke else DECODE_CASES
    tol = dict(rtol=2e-5, atol=2e-5)

    for name, s, h, kvh, hd, window, bq, bk in cases:
        q = jax.random.normal(jax.random.PRNGKey(0), (BATCH, s, h, hd))
        k = jax.random.normal(jax.random.PRNGKey(1), (BATCH, s, kvh, hd))
        v = jax.random.normal(jax.random.PRNGKey(2), (BATCH, s, kvh, hd))
        ref = jax.jit(lambda q, k, v: blockwise_causal_attention(
            q, k, v, q_block=bq, window=window))
        fl = jax.jit(lambda q, k, v: blockwise_causal_attention(
            q, k, v, q_block=bq, kv_block=bk, window=window,
            backend="pallas"))
        y_ref, y_fl = ref(q, k, v), fl(q, k, v)
        np.testing.assert_allclose(
            np.asarray(y_ref), np.asarray(y_fl), **tol
        )
        frac = visible_block_fraction(s, bq, bk, window)
        t_ref = _time(ref, q, k, v)
        print(csv_row(f"attention/reference/{name}", 1e6 * t_ref,
                      "visible_fraction=1.00;score_hbm=full"))
        t_fl = _time(fl, q, k, v)
        print(csv_row(
            f"attention/flash_interpret/{name}", 1e6 * t_fl,
            f"visible_fraction={frac:.3f};"
            f"flops_ratio={frac:.3f};score_hbm=0",
        ))

    for name, s_max, h, kvh, hd, window, bk in dec_cases:
        q = jax.random.normal(jax.random.PRNGKey(3), (BATCH, 1, h, hd))
        kc = jax.random.normal(jax.random.PRNGKey(4), (BATCH, s_max, kvh, hd))
        vc = jax.random.normal(jax.random.PRNGKey(5), (BATCH, s_max, kvh, hd))
        lens = jnp.array([s_max // 3 + 1, s_max], jnp.int32)[:BATCH]
        ref = jax.jit(lambda q, kc, vc, ln: decode_attention(
            q, kc, vc, ln, window=window))
        fl = jax.jit(lambda q, kc, vc, ln: decode_attention(
            q, kc, vc, ln, window=window, kv_block=bk, backend="pallas"))
        np.testing.assert_allclose(
            np.asarray(ref(q, kc, vc, lens)),
            np.asarray(fl(q, kc, vc, lens)), **tol
        )
        n_blocks = s_max // bk
        vis = decode_visible_blocks(s_max, bk, window)
        t_ref = _time(ref, q, kc, vc, lens)
        print(csv_row(f"attention/reference/{name}", 1e6 * t_ref,
                      f"kv_blocks={n_blocks}"))
        t_fl = _time(fl, q, kc, vc, lens)
        print(csv_row(
            f"attention/flash_interpret/{name}", 1e6 * t_fl,
            f"kv_blocks_computed<={vis}/{n_blocks}",
        ))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes only (CI kernel-regression gate)")
    args = ap.parse_args()
    main(smoke=args.smoke)
